"""Compiles for a described TPU v5e (v5e:2x2) at real widths.

The TPU compiler is installed even where no chip is attached, and it
refuses what the chip would refuse: block shapes off the tiling, kernels
over the scoped VMEM limit, programs over device memory.  Nothing runs,
so these tests say nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers each import every test file.  All such tests stay in this
one file, so they land on one worker.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_arch
from repro.models import build_model
from repro.optim import AdamW

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_yi_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((8, 32, 256, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 4, 256, 128), jnp.float32,
                              sharding=one_chip)
    _compile_kernel(flash_attention, q, kv, kv)


def test_rms_norm_compiles_at_yi_widths(one_chip):
    from repro.kernels.rmsnorm import rms_norm
    _compile_kernel(
        rms_norm,
        jax.ShapeDtypeStruct((2048, 4096), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip))


def test_ce_loss_compiles_at_yi_widths(one_chip):
    from repro.kernels.ce_loss import ce_loss
    _compile_kernel(
        ce_loss,
        jax.ShapeDtypeStruct((2048, 4096), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((64000, 4096), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip))


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.mamba2 import mamba_dims
    cfg = get_arch("mamba2-2.7b")
    _, h, n = mamba_dims(cfg)
    p, chunk = cfg.ssm.head_dim, cfg.ssm.chunk_size
    b, s = 2, 4 * chunk

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    _compile_kernel(lambda x, dt, a, bm, cm: ssd_scan(x, dt, a, bm, cm,
                                                      chunk=chunk),
                    spec(b, s, h, p), spec(b, s, h), spec(h),
                    spec(b, s, h, n), spec(b, s, h, n))


@pytest.mark.parametrize("n_chips", [1, 4])
def test_trainer_step_fits_v5e_at_yi_widths(topo, n_chips):
    """The trainer's own donated step, for yi-6b at its published widths
    cut to one layer, at ElasticTrainer's default 8 x 256 batch per chip:
    params and AdamW state are updated in place, and the program fits one
    chip's HBM."""
    from repro.elastic.trainer import make_train_step
    model = build_model(dataclasses.replace(get_arch("yi-6b"), n_layers=1))
    opt = AdamW()
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    mesh = Mesh(np.asarray(topo.devices[:n_chips]), ("data",))
    step = make_train_step(model, opt, mesh, warmup_steps=20,
                           total_steps=10_000)
    batch = {k: jax.ShapeDtypeStruct((8 * n_chips, 256), jnp.int32)
             for k in ("tokens", "labels")}
    mem = step.lower(params, opt_state, batch,
                     jax.ShapeDtypeStruct((), jnp.float32)
                     ).compile().memory_analysis()
    state = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((params, opt_state)))
    # aliased bytes count the device's padding of the small leaves
    assert mem.alias_size_in_bytes >= state
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert per_chip <= V5E_HBM_BYTES, per_chip
