"""Substrate tests: attention paths, optimizer, data pipeline, checkpoint,
sharding rules, roofline parsing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import batch_spec, sanitize, zero1_spec
from repro.models.attention import attend_blockwise, attend_direct
from repro.roofline import Roofline, parse_collectives


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 37),
                                           (False, 0), (False, 37)])
@pytest.mark.parametrize("cap", [0.0, 20.0])
def test_blockwise_equals_direct(causal, window, cap):
    rng = np.random.RandomState(0)
    b, s, kv, g, d = 2, 200, 2, 3, 16
    q = jnp.asarray(rng.randn(b, s, kv, g, d) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv, d) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, d) * 0.3, jnp.float32)
    pos = jnp.arange(s)
    kw = dict(q_pos=pos, k_pos=pos, causal=causal, window=window,
              logit_cap=cap, scale=d ** -0.5)
    a = attend_direct(q, k, v, **kw)
    bw = attend_blockwise(q, k, v, q_block=64, kv_block=48, **kw)
    assert float(jnp.max(jnp.abs(a - bw))) < 2e-5


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_on_quadratic():
    from repro.optim import AdamW
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(
            lambda pp: jnp.sum((pp["w"] - 1.0) ** 2))(p)
        p2, s2 = opt.update(g, s, p)
        return p2, s2, loss

    for _ in range(300):
        params, state, loss = step(params, state)
    assert float(loss) < 1e-3


def test_lr_schedule_and_scaling():
    from repro.optim import linear_scaling, warmup_cosine
    assert float(warmup_cosine(jnp.int32(0), warmup_steps=10)) == 0.0
    mid = float(warmup_cosine(jnp.int32(5), warmup_steps=10))
    assert 0.4 < mid < 0.6
    top = float(warmup_cosine(jnp.int32(10), warmup_steps=10,
                              total_steps=100))
    assert abs(top - 1.0) < 1e-5
    assert linear_scaling(8) == 8.0
    assert linear_scaling(64, max_scale=32) == 32.0


def test_grad_clip_bounds_update():
    from repro.optim import AdamW
    opt = AdamW(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    huge = {"w": jnp.full(4, 1e9)}
    p2, _ = opt.update(huge, state, params)
    assert float(jnp.max(jnp.abs(p2["w"]))) < 10.0


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------


def test_pipeline_deterministic_and_resumes():
    from repro.data import DataConfig, TokenPipeline
    cfg = DataConfig(vocab_size=1000, seq_len=32, per_node_batch=4, seed=9)
    p1 = TokenPipeline(cfg)
    b1 = p1.next_batch(2)
    b2 = p1.next_batch(3)
    assert b1["tokens"].shape == (8, 32)
    assert b2["tokens"].shape == (12, 32)
    assert p1.samples_consumed == 20

    # restore mid-stream on a different "node count" (elastic rescale):
    p2 = TokenPipeline(cfg)
    p2.restore({"consumed": 8})
    b2b = p2.next_batch(3)
    np.testing.assert_array_equal(b2["tokens"], b2b["tokens"])

    # no sample served twice across the rescale
    p3 = TokenPipeline(cfg)
    a = p3.next_batch(2)["tokens"]
    b = p3.next_batch(3)["tokens"]
    assert not any((row == a).all(-1).any() for row in b)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import Snapshot, load_checkpoint, save_checkpoint
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.int32)}}
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(path, tree, meta={"step": 7})
    restored, meta = load_checkpoint(path, tree)
    assert meta == {"step": 7}
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(x, y),
                 tree, restored)

    snap = Snapshot.take(tree, step=3)
    back = snap.restore()
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(x, y),
                 tree, back)


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------


def _mesh():
    return AbstractMesh((16, 16), ("data", "model"))


def test_sanitize_drops_nondivisible_axes():
    m = _mesh()
    assert sanitize((49155, 1024), P("model", None), m) in (P(), P(None))
    assert sanitize((64000, 1024), P("model", None), m) == P("model")
    assert sanitize((100, 512), P(None, "model"), m) == P(None, "model")


def test_zero1_spec_shards_over_data():
    m = _mesh()
    s = zero1_spec((8192, 28672), P(None, "model"), m, ("data",))
    assert s == P("data", "model")
    # non-divisible first dim falls through to no extra sharding
    s2 = zero1_spec((49155, 1024), P(None, "model"), m, ("data",))
    assert s2[0] is None or s2[0] == "data"


def test_batch_spec_divisibility():
    m = _mesh()
    assert batch_spec((256, 4096), m, ("data",)) == P("data")
    assert batch_spec((1, 524288), m, ("data",)) == P(None)
    m3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert batch_spec((256, 4096), m3, ("pod", "data")) == P(("pod", "data"))
    # batch_spec unwraps single-axis tuples; P("pod") == P(("pod",)) only
    # on newer jax, so compare against the unwrapped form directly.
    assert batch_spec((2, 1), m3, ("pod", "data")) == P("pod")


# ---------------------------------------------------------------------------
# Roofline parsing
# ---------------------------------------------------------------------------


HLO_SAMPLE = """
  %all-reduce.1 = f32[16,2048]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %all-reduce.2 = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-reduce(%a, %b), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[1024,512]{1,0} all-gather(%y), replica_groups={{0,1}}, dimensions={0}
  %foo = f32[2,2]{1,0} add(%p, %q)
  %cp-start = f32[4]{0} collective-permute-start(%z), source_target_pairs={{0,1}}
"""


def test_parse_collectives_counts_and_bytes():
    st = parse_collectives(HLO_SAMPLE)
    assert st.counts["all-reduce"] == 2
    assert st.counts["all-gather"] == 1
    assert st.counts["collective-permute"] == 1
    ar1 = 16 * 2048 * 4
    ar2 = 2 * 8 * 4 * 4
    ag = 1024 * 512 * 2
    assert st.bytes_by_kind["all-reduce"] == ar1 + ar2
    assert st.bytes_by_kind["all-gather"] == ag
    assert st.link_bytes > 0


def test_roofline_terms():
    r = Roofline(arch="x", shape="train_4k", mesh="m", n_devices=256,
                 hlo_flops=197e12 * 256, hlo_bytes=819e9 * 256 * 2,
                 collective_link_bytes=50e9 * 3,
                 model_flops=197e12 * 128, n_params=1, n_active_params=1)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 3.0) < 1e-9
    assert r.bottleneck == "collective"
    assert abs(r.useful_flops_ratio - 0.5) < 1e-9
