"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in ``repro.kernels.ref`` (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import flash_attention, rms_norm, ssd_scan
from repro.kernels.ref import flash_attention_ref, rms_norm_ref, ssd_scan_ref
from repro.models.mamba2 import ssd_chunked


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 2, 1, 128, 64),    # MQA
    (2, 4, 2, 160, 32),    # GQA, ragged seq
    (1, 8, 8, 96, 128),    # MHA
])
@pytest.mark.parametrize("causal,window,cap", [
    (True, 0, 0.0), (True, 64, 0.0), (True, 0, 50.0), (False, 0, 0.0),
])
def test_flash_attention_matches_ref(dtype, shape, causal, window, cap):
    b, h, kv, s, d = shape
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d) * 0.3, dtype)
    k = jnp.asarray(rng.randn(b, kv, s, d) * 0.3, dtype)
    v = jnp.asarray(rng.randn(b, kv, s, d) * 0.3, dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          logit_cap=cap, block_q=64, block_k=64,
                          interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal, window=window,
                               logit_cap=cap)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < _tol(dtype), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 64, 2, 16, 8), (2, 128, 3, 32, 16)])
@pytest.mark.parametrize("chunk", [32, 64])
def test_ssd_scan_matches_ref(dtype, shape, chunk):
    b, s, h, p, n = shape
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(b, s, h, p) * 0.5, dtype)
    dt = jnp.asarray(rng.rand(b, s, h) * 0.5 + 0.01, jnp.float32)
    a = jnp.asarray(-np.exp(rng.randn(h) * 0.3), jnp.float32)
    bm = jnp.asarray(rng.randn(b, s, h, n) * 0.4, dtype)
    cm = jnp.asarray(rng.randn(b, s, h, n) * 0.4, dtype)
    got = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    want, _ = ssd_scan_ref(x, dt, a, bm, cm)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < (0.08 if dtype == jnp.bfloat16 else 1e-4), err


def test_ssd_chunked_model_path_matches_naive():
    """The model's XLA chunked path is itself validated against the naive
    recurrence, and is chunk-size invariant."""
    rng = np.random.RandomState(2)
    b, s, h, p, n = 2, 96, 2, 8, 4
    x = jnp.asarray(rng.randn(b, s, h, p) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.rand(b, s, h) * 0.5 + 0.01, jnp.float32)
    a = jnp.asarray(-np.exp(rng.randn(h) * 0.3), jnp.float32)
    bm = jnp.asarray(rng.randn(b, s, h, n) * 0.4, jnp.float32)
    cm = jnp.asarray(rng.randn(b, s, h, n) * 0.4, jnp.float32)
    want, want_state = ssd_scan_ref(x, dt, a, bm, cm)
    for chunk in (16, 32, 96):
        got, state = ssd_chunked(x, dt, a, bm, cm, chunk=chunk)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4
        assert float(jnp.max(jnp.abs(state - want_state))) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(3, 50, 96), (1, 7, 256), (2, 256, 128)])
def test_rms_norm_matches_ref(dtype, shape):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(*shape), dtype)
    s = jnp.asarray(rng.randn(shape[-1]) * 0.1, jnp.float32)
    got = rms_norm(x, s, block_rows=32, interpret=True)
    want = rms_norm_ref(x, s)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < _tol(dtype), err


def test_flash_attention_long_and_ragged():
    """Non-multiple sequence lengths exercise the padding/mask path."""
    rng = np.random.RandomState(4)
    b, h, kv, sq, sk, d = 1, 2, 1, 130, 190, 32
    q = jnp.asarray(rng.randn(b, h, sq, d) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(b, kv, sk, d) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(b, kv, sk, d) * 0.3, jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    want = flash_attention_ref(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def _attend_direct_bhsd(q, k, v, *, causal, window, cap, scale):
    """``attend_direct`` on the kernel's (B, heads, S, D) layout."""
    from repro.models.attention import attend_direct
    b, h, s, d = q.shape
    kv = k.shape[1]
    qg = q.transpose(0, 2, 1, 3).reshape(b, s, kv, h // kv, d)
    out = attend_direct(qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                        q_pos=jnp.arange(s), k_pos=jnp.arange(s),
                        causal=causal, window=window, logit_cap=cap,
                        scale=scale)
    return out.reshape(b, s, h, -1).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("h,kv,d_qk,d_v,causal,window,cap", [
    (6, 2, 64, 64, True, 0, 0.0),       # GQA
    (2, 2, 192, 128, True, 0, 0.0),     # MLA's head sizes
    (6, 2, 64, 64, True, 100, 0.0),     # sliding window, off the blocks
    (4, 2, 32, 32, True, 0, 20.0),      # soft-cap
    (2, 1, 32, 32, False, 0, 0.0),      # bidirectional
], ids=["gqa", "mla", "window", "softcap", "bidirectional"])
def test_flash_attention_grads_match_attend_direct(h, kv, d_qk, d_v, causal,
                                                   window, cap):
    """The kernel's output and its custom VJP (the dK/dV and dQ kernels)
    against ``jax.grad`` of the materialised-scores path under a random
    cotangent, S = 256 in blocks of 128 (so blocks are skipped, masked and
    run whole)."""
    from repro.kernels.flash_attention import flash_attention as fa
    rng = np.random.RandomState(7)
    b, s = 2, 256
    q = jnp.asarray(rng.randn(b, h, s, d_qk), jnp.float32)
    k = jnp.asarray(rng.randn(b, kv, s, d_qk), jnp.float32)
    v = jnp.asarray(rng.randn(b, kv, s, d_v), jnp.float32)
    ct = jnp.asarray(rng.randn(b, h, s, d_v), jnp.float32)
    kw = dict(causal=causal, window=window, scale=d_qk ** -0.5)

    def kernel(q, k, v):
        return fa(q, k, v, logit_cap=cap, block_q=128, block_k=128,
                  interpret=True, **kw)

    def direct(q, k, v):
        return _attend_direct_bhsd(q, k, v, cap=cap, **kw)

    got, vjp = jax.vjp(kernel, q, k, v)
    want, vjp_want = jax.vjp(direct, q, k, v)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    for name, g, w in zip("qkv", vjp(ct), vjp_want(ct)):
        err = float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        assert err < 1e-5, (name, err)


def _layer(arch, window_of):
    from repro.configs import get_arch
    from repro.models import attention as A
    from repro.models.layers import materialize
    cfg = get_arch(arch).reduced()
    p = materialize(A.attn_defs(cfg), jax.random.key(1))
    return cfg, p, [window_of(cfg, spec) for spec in cfg.layer_pattern]


def test_pallas_backend_model_equivalence(dispatch_on):
    """The Pallas flash-attention kernel, reached through the dispatch's
    ``platform`` (interpret mode here), matches the XLA path the CPU takes,
    output and parameter gradients, in yi's layer and both of gemma2's
    (sliding window and global, with soft-capping)."""
    from repro.models import attention as A

    def window_of(cfg, spec):
        return cfg.sliding_window if spec.mixer == "swa" else 0

    x = jnp.asarray(np.random.RandomState(0).randn(2, 128, 128) * 0.5,
                    jnp.float32)
    for arch in ("yi-6b", "gemma2-27b"):
        cfg, p, windows = _layer(arch, window_of)
        for window in windows:
            def loss(p):
                out = A.attn_apply(p, x, cfg=cfg, causal=True, window=window)
                return jnp.sum(out * x), out

            (_, want), gw = jax.value_and_grad(loss, has_aux=True)(p)
            with dispatch_on("tpu"):
                (_, got), gg = jax.value_and_grad(loss, has_aux=True)(p)
            assert float(jnp.max(jnp.abs(got - want))) < 5e-5, (arch, window)
            for n in gw:
                err = float(jnp.max(jnp.abs(gg[n] - gw[n]))
                            / jnp.max(jnp.abs(gw[n])))
                assert err < 1e-4, (arch, window, n, err)


@pytest.mark.parametrize("platform,s,sk,cross,kernel", [
    ("tpu", 2048, 2048, False, True),    # the training cells' attention
    ("tpu", 256, 256, False, True),
    ("cpu", 2048, 2048, False, False),   # CPU: XLA's path
    ("tpu", 2048, 2048, True, False),    # cross-attention
    ("tpu", 2048, 1500, False, False),   # queries and keys differ
    ("tpu", 200, 200, False, False),     # ragged: off the kernel's blocks
    ("tpu", 1, 1, False, False),         # one token
])
def test_flash_dispatch_rule(platform, s, sk, cross, kernel):
    """Which path ``attend`` takes follows from the platform and the shapes
    alone: the kernel for tileable self-attention on the TPU, XLA for the
    rest; and the traced program holds a ``pallas_call`` exactly then."""
    from repro.models import attention as A
    assert A.flash_eligible(platform, s, sk, cross) is kernel
    q = jax.ShapeDtypeStruct((1, s, 2, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, sk, 1, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: A.attend(
        q, k, v, causal=not cross, window=0, logit_cap=0.0, scale=0.1,
        cross=cross, platform=platform))(q, kv, kv)
    assert ("pallas_call" in str(jaxpr)) is kernel


def test_explicit_positions_take_the_xla_path():
    """Positions other than ``arange(S)`` bypass the kernel, whose masks
    assume them."""
    from repro.models import attention as A
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q: A.attend(
        q, q, q, causal=True, window=0, logit_cap=0.0, scale=0.1,
        q_pos=jnp.arange(256) + 3, platform="tpu"))(q)
    assert "pallas_call" not in str(jaxpr)


def test_mla_kernel_path_matches_xla_without_padding_v(dispatch_on):
    """DeepSeek's MLA through the kernel (q and k at 192, v at 128, no
    padding) against the XLA path, output and every gradient."""
    from repro.configs import get_arch
    from repro.models import mla
    from repro.models.layers import materialize
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    p = materialize(mla.mla_defs(cfg), jax.random.key(3))
    x = jax.random.normal(jax.random.key(5), (2, 128, cfg.d_model))
    wts = jax.random.normal(jax.random.key(6), (2, 128, cfg.d_model))

    def loss(p):
        return jnp.sum(mla.mla_apply(p, x, cfg) * wts)

    want, gw = jax.value_and_grad(loss)(p)
    with dispatch_on("tpu"):
        got, gg = jax.value_and_grad(loss)(p)
        jaxpr = str(jax.make_jaxpr(loss)(p))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for n in gw:
        err = float(jnp.max(jnp.abs(gg[n] - gw[n]))
                    / jnp.max(jnp.abs(gw[n])))
        assert err < 1e-4, (n, err)
    assert "pallas_call" in jaxpr and "pad[" not in jaxpr


def test_flash_attention_under_remat_scan_matches_xla(dispatch_on):
    """The path both training cells take: attention layers scanned under
    ``jax.checkpoint``, kernel (interpret mode) against XLA, gradients of
    the stacked weights and of the input."""
    from repro.models import attention as A
    cfg, p, _ = _layer("yi-6b", lambda cfg, spec: 0)
    stacked = jax.tree.map(lambda w: jnp.stack([w, 0.5 * w]), p)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 256, 128) * 0.5,
                    jnp.float32)

    def loss(ps, x):
        @jax.checkpoint
        def body(h, p):
            return h + A.attn_apply(p, h, cfg=cfg, causal=True,
                                    window=0), None
        h, _ = jax.lax.scan(body, x, ps)
        return jnp.sum(h * h)

    want, gw = jax.value_and_grad(loss, (0, 1))(stacked, x)
    with dispatch_on("tpu"):
        got, gg = jax.value_and_grad(loss, (0, 1))(stacked, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(100, 500, 64), (64, 1000, 32),
                                   (33, 257, 16)])
def test_ce_loss_kernel_matches_ref(dtype, shape):
    from repro.kernels.ops import ce_loss
    from repro.kernels.ref import ce_loss_ref
    t, v, d = shape
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(t, d) * 0.5, dtype)
    w = jnp.asarray(rng.randn(v, d) * 0.3, dtype)
    lbl = jnp.asarray(rng.randint(0, v, (t,)), jnp.int32)
    got = ce_loss(x, w, lbl, block_rows=32, block_v=128, interpret=True)
    want = ce_loss_ref(x, w, lbl)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    assert float(jnp.max(jnp.abs(got - want))) < tol
