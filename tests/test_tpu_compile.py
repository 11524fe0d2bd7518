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


def _cell_arch(cell):
    """A training cell's model as the benchmark runs it: granite at its
    published widths cut to 4 layers, or ``bench/configs``' DeepSeek."""
    import json
    import os
    import sys
    if cell == "granite":
        return dataclasses.replace(get_arch("granite-moe-3b-a800m"),
                                   n_layers=4)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import harness
    with open(os.path.join(root, "bench", "configs",
                           "deepseek-v2-lite-5l.json")) as f:
        return harness.arch_config(json.load(f))


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


# (batch, heads, kv heads, seq, d_qk, d_v, scale) of the training cells
CELL_ATTENTION = {"granite": (2, 24, 8, 2048, 64, 64, 0.125),
                  "deepseek": (2, 16, 16, 2048, 192, 128, 0.1)}


@pytest.mark.parametrize("cell", sorted(CELL_ATTENTION))
def test_flash_attention_trains_at_the_cells_widths(one_chip, cell):
    """Forward and backward (the custom VJP's dK/dV and dQ kernels) of the
    kernel at a training cell's attention widths, causal at 2 x 2048: all
    three kernels compile for the chip, under their own names."""
    from repro.kernels.flash_attention import flash_attention
    b, h, kv, s, d_qk, d_v, scale = CELL_ATTENTION[cell]

    def loss(q, k, v, ct):
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=scale)
                       * ct)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    hlo = _compile_kernel(jax.value_and_grad(loss, (0, 1, 2)),
                          spec(b, h, s, d_qk), spec(b, kv, s, d_qk),
                          spec(b, kv, s, d_v), spec(b, h, s, d_v)).as_text()
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in hlo, name


@pytest.fixture(scope="module")
def chip():
    """The attached TPU: these tests run the program on it."""
    if jax.default_backend() != "tpu":
        pytest.skip("runs on the TPU only")


def _cell_layer(cell):
    """A cell's attention layer at its published widths, its parameters
    and a (2, 2048, d_model) input: granite's GQA, DeepSeek's MLA."""
    from repro.models import attention, mla
    from repro.models.layers import materialize
    cfg = _cell_arch(cell)
    if cell == "granite":
        defs = attention.attn_defs(cfg)

        def apply(p, x):
            return attention.attn_apply(p, x, cfg=cfg, causal=True, window=0)
    else:
        defs = mla.mla_defs(cfg)

        def apply(p, x):
            return mla.mla_apply(p, x, cfg)
    p = materialize(defs, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 2048, cfg.d_model))
    return apply, p, x


@pytest.mark.parametrize("cell", sorted(CELL_ATTENTION))
def test_chip_layer_takes_the_kernel_and_no_scores(chip, cell):
    """On the chip a cell's attention layer and its gradient run through
    the Pallas kernels, and no (2, H, 2048, 2048) float32 score tensor is
    left in the program."""
    import re
    apply, p, x = _cell_layer(cell)
    hlo = jax.jit(jax.grad(lambda p, x: jnp.sum(apply(p, x) ** 2))).lower(
        p, x).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(r"f32\[2,\d+,2048,2048\]", hlo)


@pytest.mark.parametrize("cell", sorted(CELL_ATTENTION))
def test_chip_kernel_gradients_match_attend_direct(chip, dispatch_on, cell):
    """On the chip, two of a cell's attention layers scanned under
    ``jax.checkpoint`` (the training step's remat scan): the kernel's loss
    and gradients against the materialised-scores path's, both at default
    precision."""
    apply, p, x = _cell_layer(cell)
    stacked = jax.tree.map(lambda w: jnp.stack([w, 0.5 * w]), p)

    def grads(platform):
        def loss(ps, x):
            @jax.checkpoint
            def body(h, p):
                return h + apply(p, h), None
            h, _ = jax.lax.scan(body, x, ps)
            return jnp.mean(h * h)

        with dispatch_on(platform):
            return jax.jit(jax.value_and_grad(loss, (0, 1)))(stacked, x)

    want, gw = grads("cpu")
    got, gg = grads("tpu")
    assert abs(float(got) - float(want)) <= 1e-3 * abs(float(want))
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(gg)[0],
                            jax.tree.leaves(gw)):
        gap = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert gap < 2e-2, (jax.tree_util.keystr(path), gap)


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


@pytest.mark.parametrize("n_chips", [1, 4])
def test_trainer_step_routes_granite_through_ragged_dots(topo, n_chips):
    """granite-moe-3b-a800m at its published widths cut to 4 layers, at the
    benchmark's 2 x 2048 batch per chip: on every chip the experts run as
    ragged dots over that chip's own 4096 x 8 routed rows, forward and for
    the weight gradients.  No (40, 4096, ...) all-experts tensor is left,
    no chip holds the whole batch's routed rows, and the donated step fits
    one chip's HBM."""
    import re
    from repro.elastic.trainer import make_train_step
    model = build_model(_cell_arch("granite"))
    opt = AdamW()
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    mesh = Mesh(np.asarray(topo.devices[:n_chips]), ("data",))
    step = make_train_step(model, opt, mesh, warmup_steps=20,
                           total_steps=10_000)
    batch = {k: jax.ShapeDtypeStruct((2 * n_chips, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = step.lower(params, opt_state, batch,
                          jax.ShapeDtypeStruct((), jnp.float32)).compile()
    hlo = compiled.as_text()
    ragged = set(re.findall(r"%ragged-dot[\w.-]* = f32\[([\d,]+)\]", hlo))
    assert ragged >= {"32768,512", "32768,1536", "40,1536,512",
                      "40,512,1536"}, ragged
    assert not re.search(r"f32\[40,4096,", hlo)
    if n_chips > 1:
        assert not re.search(rf"\[{32768 * n_chips}\b", hlo)
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert per_chip <= V5E_HBM_BYTES, per_chip


def test_trainer_step_fits_v5e_for_the_deepseek_cell(topo):
    """The benchmark's deepseek-v2-lite-5l (one chip's share of an 8-way
    expert-parallel DeepSeek-V2-Lite: a dense and 4 MoE layers, 8 of 64
    experts held, 12,800 vocabulary rows) at its 2 x 2048 batch: the
    donated step fits one chip, the held experts run as ragged dots over
    all 4096 x 6 routed rows in 8 groups, and its temporaries stay at the
    3.45 GB it compiled to when the cell was added (5% of room)."""
    import re
    from repro.elastic.trainer import make_train_step
    model = build_model(_cell_arch("deepseek"))
    opt = AdamW()
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    step = make_train_step(model, opt, mesh, warmup_steps=2,
                           total_steps=10_000)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = step.lower(params, opt_state, batch,
                          jax.ShapeDtypeStruct((), jnp.float32)).compile()
    ragged = set(re.findall(r"%ragged-dot[\w.-]* = f32\[([\d,]+)\]",
                            compiled.as_text()))
    assert ragged >= {"24576,1408", "24576,2048", "8,2048,1408",
                      "8,1408,2048"}, ragged
    mem = compiled.memory_analysis()
    state = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((params, opt_state)))
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes <= 1.05 * 3_452_616_704, \
        mem.temp_size_in_bytes
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert per_chip <= V5E_HBM_BYTES, per_chip


@pytest.mark.parametrize("cell,n_chips", [("granite", 1), ("granite", 4),
                                          ("deepseek", 1)])
def test_trainer_step_trains_attention_through_the_kernel(topo, monkeypatch,
                                                          cell, n_chips):
    """The trainer's donated step for a training cell, with the dispatch
    seeing the TPU it is compiled for: every layer's attention runs the
    three flash kernels (on each chip's own rows when the batch is split
    over chips), and no (2, H, 2048, 2048) float32 score tensor is left."""
    import re
    from repro.elastic.trainer import make_train_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(_cell_arch(cell))
    opt = AdamW()
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    mesh = Mesh(np.asarray(topo.devices[:n_chips]), ("data",))
    step = make_train_step(model, opt, mesh, warmup_steps=2,
                           total_steps=10_000)
    batch = {k: jax.ShapeDtypeStruct((2 * n_chips, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = step.lower(params, opt_state, batch,
                          jax.ShapeDtypeStruct((), jnp.float32)).compile()
    hlo = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in hlo, name
    assert not re.search(r"f32\[\d+,\d+,2048,2048\]", hlo)
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert per_chip <= V5E_HBM_BYTES, per_chip
