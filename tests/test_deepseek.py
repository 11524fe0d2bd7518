"""DeepSeek-V2-Lite in the program: YaRN rope and the latent attention's
scale, the MoE layer that holds a share of the routed experts, its
unrenormalised gates and sequence-wise aux loss, each against the closed
form or the plain reference (``bench/reference/deepseek_v2.py``), and the
models that hold every expert left as they were."""
import dataclasses
import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import deepseek_v2 as ref  # noqa: E402
from repro.configs import RoutingConfig, YarnConfig, get_arch  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.models.layers import (materialize, rope_freqs,  # noqa: E402
                                 softmax_scale, yarn_mscale)

CELL_FILE = os.path.join(ROOT, "bench", "configs", "deepseek-v2-lite-5l.json")


def _share(cfg, n_routed, held, first, **routing):
    """``cfg`` with a router over ``n_routed`` experts, of which it holds
    ``held`` from ``first`` on."""
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=held),
        routing=dataclasses.replace(cfg.routing, n_routed=n_routed,
                                    first_held=first, **routing))


def _tiny_moe(top_k=3, d_expert=32):
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=top_k, d_expert=d_expert, n_shared=2))


def _slice_experts(params, first, held):
    return dict(params, **{n: params[n][first:first + held]
                           for n in ("w_gate", "w_up", "w_down")})


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_are_the_closed_form():
    """64 rope dims, theta 1e4, factor 40 over 4096 positions, beta 32 / 1:
    the ramp runs over pairs 10..23, frequencies below it kept, above it
    divided by 40."""
    y = YarnConfig()
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    i = np.arange(32, dtype=np.float64)
    extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    got = np.asarray(rope_freqs(64, 10000.0, y), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_array_equal(got[:11], np.asarray(rope_freqs(
        64, 10000.0))[:11])
    np.testing.assert_allclose(got[23:], want[23:], rtol=2e-6)


def test_yarn_attention_scale_is_m_squared():
    y = get_arch("deepseek-v2-lite-16b").yarn
    m = yarn_mscale(y.factor, y.mscale_all_dim)
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert m * m == pytest.approx(1.589627, abs=1e-6)
    assert softmax_scale(192, y) == pytest.approx(0.114722, abs=1e-6)
    assert softmax_scale(192) == 192 ** -0.5
    # cos and sin are scaled by m(mscale) / m(mscale_all_dim) = 1
    assert yarn_mscale(y.factor, y.mscale) / m == 1.0


# ---------------------------------------------------------------------------
# MLA against the reference
# ---------------------------------------------------------------------------


def test_mla_matches_the_reference_and_its_gradients():
    """The program's training-path MLA (YaRN rope, ``m ** 2`` scale, V at
    its own head size, materialised scores) against the reference's
    (split nope / rope logits), output and every gradient, at HIGHEST."""
    from repro.models import mla
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    assert cfg.yarn is not None
    p = materialize(mla.mla_defs(cfg), jax.random.key(3))
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.key(4),
                                           p["kv_norm"].shape)
    x = jax.random.normal(jax.random.key(5), (2, 40, cfg.d_model))
    wts = jax.random.normal(jax.random.key(6), (2, 40, cfg.d_model))
    a = {"n_heads": cfg.n_heads, "norm_eps": cfg.norm_eps,
         "rope_theta": cfg.rope_theta,
         "mla": dataclasses.asdict(cfg.mla),
         "yarn": dataclasses.asdict(cfg.yarn)}

    def program(p, x):
        return jnp.sum(mla.mla_apply(p, x, cfg) * wts)

    def reference(p, x):
        return jnp.sum(ref.mla(a, p.__getitem__, x, jnp.float32,
                               ref.HIGHEST) * wts)

    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(reference, (0, 1))(p, x)
        got, gg = jax.value_and_grad(program, (0, 1))(p, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for path, g in jax.tree_util.tree_flatten_with_path(gg)[0]:
        w = {jax.tree_util.keystr(q): v for q, v in
             jax.tree_util.tree_flatten_with_path(gw)[0]}[
                 jax.tree_util.keystr(path)]
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=2e-5 * float(jnp.max(jnp.abs(w))),
                                   err_msg=jax.tree_util.keystr(path))


def test_mla_decode_applies_yarn_and_the_scale():
    """Prefill then one decode step against the full forward, for the
    reduced deepseek-v2-lite-16b with YaRN on: the absorbed decode path
    takes the same rope and scale as the training path."""
    from repro.models import build_model
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    assert cfg.yarn == get_arch("deepseek-v2-lite-16b").yarn
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (2, 33)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.forward(params, {"tokens": toks})[0][:, -1]
        _, cache = model.prefill(params, {"tokens": toks[:, :32]},
                                 cache_len=40)
        cache = jax.tree.map(lambda c: c.astype(jnp.float32), cache)
        got = model.decode_step(params, cache, toks[:, 32:], jnp.int32(32))
    err = float(jnp.max(jnp.abs(got[0][:, 0] - want)))
    # the cache is kept in bfloat16: its rounding, far below what plain
    # rope or the unscaled softmax move (checked by the mutation of either)
    assert err < 0.02 * float(jnp.max(jnp.abs(want))), err


# ---------------------------------------------------------------------------
# the held-expert MoE layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["dropless", "dense"])
@pytest.mark.parametrize("seq_aux", [True, False])
def test_held_shares_sum_to_the_uncut_layer(strategy, seq_aux):
    """Eight routed experts, top-3, held 2 a share: over the four shares
    the routed parts of the output add up to the layer that holds all
    eight, with the shared experts counted once, and every share's aux
    loss is the whole layer's."""
    cfg = _tiny_moe()
    whole = _share(cfg, 8, 8, 0, norm_topk=False, seq_aux=seq_aux)
    params = materialize(M.moe_defs(whole), jax.random.key(7))
    x = jax.random.normal(jax.random.key(8), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, aux = M.moe_apply(params, x, whole, strategy=strategy)
        shared = M.mlp_apply(params["shared"], x, cfg.mlp_activation)
        total = shared
        for first in range(0, 8, 2):
            part = _share(whole, 8, 2, first)
            y, aux_s = M.moe_apply(_slice_experts(params, first, 2), x, part,
                                   strategy=strategy)
            assert float(aux_s) == float(aux)
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("first", [0, 3, 6])
def test_held_share_dropless_matches_dense(first):
    """The oracle on a held share: the dropless path is the dense path's
    function, output, aux loss and the gradients of input, router, held
    experts and shared experts, with gates not renormalised."""
    cfg = _share(_tiny_moe(), 8, 2, first, norm_topk=False, seq_aux=True)
    params = materialize(M.moe_defs(cfg), jax.random.key(9))
    assert params["router"].shape[-1] == 8 and params["w_gate"].shape[0] == 2
    x = jax.random.normal(jax.random.key(10), (2, 24, cfg.d_model))
    wts = jax.random.normal(jax.random.key(11), x.shape)

    def grads(strategy):
        def f(p, x):
            y, aux = M.moe_apply(p, x, cfg, strategy=strategy)
            return jnp.sum(y * wts) + aux, (y, aux)
        return jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))(params, x)

    with jax.default_matmul_precision("highest"):
        (gd, (yd, ad)), (gs, (ys, as_)) = grads("dense"), grads("dropless")
        top_idx = M._route(params, x.reshape(48, -1), cfg.moe)[0]
    held = np.asarray((top_idx >= first) & (top_idx < first + 2))
    assert 0 < held.sum() < held.size

    def close(a, b):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(a))))

    close(yd, ys)
    assert float(ad) == pytest.approx(float(as_), abs=1e-7)
    jax.tree.map(close, gd, gs)


def test_gates_are_the_softmax_without_renormalisation():
    cfg = _tiny_moe()
    params = materialize(M.moe_defs(cfg), jax.random.key(12))
    x2d = jax.random.normal(jax.random.key(13), (16, cfg.d_model))
    probs = jax.nn.softmax(x2d @ params["router"], axis=-1)
    idx, vals, _ = M._route(params, x2d, cfg.moe, cfg.routing)
    assert not cfg.routing.norm_topk
    np.testing.assert_allclose(
        np.asarray(vals), np.take_along_axis(np.asarray(probs),
                                             np.asarray(idx), 1), rtol=1e-6)
    assert float(jnp.max(vals.sum(-1))) < 1.0
    _, renorm, _ = M._route(params, x2d, cfg.moe, RoutingConfig())
    np.testing.assert_allclose(np.asarray(renorm.sum(-1)), 1.0, rtol=1e-6)


def test_seq_aux_is_deepseeks_moe_gate():
    """coef * mean over sequences of sum_e f_e P_e, f_e a sequence's
    assignments to e over S·k/E, P_e its mean router probability."""
    cfg = _tiny_moe()
    params = materialize(M.moe_defs(cfg), jax.random.key(14))
    b, s, k, e = 3, 10, cfg.moe.top_k, cfg.n_routed
    x2d = jax.random.normal(jax.random.key(15), (b * s, cfg.d_model))
    idx, _, aux = M._route(params, x2d, cfg.moe, cfg.routing, b)
    probs = np.asarray(jax.nn.softmax(x2d @ params["router"], -1))
    idx = np.asarray(idx)
    want = 0.0
    for q in range(b):
        f = np.bincount(idx[q * s:(q + 1) * s].ravel(), minlength=e) \
            / (s * k / e)
        want += np.sum(f * probs[q * s:(q + 1) * s].mean(0))
    want *= cfg.moe.router_aux_coef / b
    assert float(aux) == pytest.approx(want, rel=1e-5)


# ---------------------------------------------------------------------------
# the models that hold every expert
# ---------------------------------------------------------------------------

# sha256 of str(make_jaxpr(moe_apply)) for the reduced granite on a
# (2, 16, d) input, as the layer traced before routing shares existed
GRANITE_MOE_JAXPR = {
    "dropless": "1d83327f4cce24f69bdc81d435f2d336"
                "cc455397fdad03dabcddd77a14744cbc",
    "dense": "1d2a4c4b835b101419714b99525d6805"
             "426d811e3c7212700ebec4f0164d0c0b",
}


@pytest.mark.parametrize("strategy", sorted(GRANITE_MOE_JAXPR))
def test_granite_moe_layer_traces_as_before(strategy):
    cfg = get_arch("granite-moe-3b-a800m").reduced()
    p = materialize(M.moe_defs(cfg), jax.random.key(0))
    text = str(jax.make_jaxpr(lambda p, x: M.moe_apply(
        p, x, cfg, strategy=strategy))(p, jnp.zeros((2, 16, cfg.d_model))))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        GRANITE_MOE_JAXPR[strategy]


@pytest.mark.parametrize("name", ["granite-moe-3b-a800m", "yi-6b"])
def test_models_without_shares_keep_their_defaults(name):
    """Every expert held, gates renormalised, Switch aux, plain rope."""
    cfg = get_arch(name)
    assert cfg.routing == RoutingConfig() and cfg.yarn is None
    assert cfg.reduced().routing == RoutingConfig()
    if cfg.moe is not None:
        assert cfg.n_routed == cfg.moe.n_experts
        assert M.moe_defs(cfg)["router"].shape == (cfg.d_model,
                                                   cfg.moe.n_experts)


def test_reduced_keeps_a_held_share_and_yarn():
    full = get_arch("deepseek-v2-lite-16b")
    assert full.n_routed == full.moe.n_experts == 64
    small = _share(full, 64, 8, 16).reduced()
    assert small.yarn == full.yarn
    assert small.moe.n_experts < small.n_routed <= 8
    assert small.routing.first_held + small.moe.n_experts <= small.n_routed
    assert (small.routing.norm_topk, small.routing.seq_aux) == (False, True)


# ---------------------------------------------------------------------------
# the benchmark's configuration
# ---------------------------------------------------------------------------


def test_cell_file_is_one_chips_share_of_the_published_model():
    from bench import harness
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    arch = harness.arch_config(cfg)
    assert ref.unmodelled(arch, cfg["arch"]) == []
    assert (arch.n_routed, arch.moe.n_experts, arch.moe.top_k) == (64, 8, 6)
    assert (arch.n_layers, arch.vocab_size) == (5, 102400 // 8)
    assert [s.mlp for s in arch.layer_pattern] == ["dense"] + ["moe"] * 4
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}


def test_flops_per_token_hand_count():
    """Per token: MLA projections 2048*3072 + 2048*512 + 2048*64 +
    512*16*256 + 2048*2048 a layer; the dense SwiGLU 3*2048*10944; per MoE
    layer the router 2048*64, the shared SwiGLU 3*2048*2816 and 6*8/64 of
    a routed one, 3*2048*1408; the head 12800*2048; scores 6*2048*16*320
    a layer."""
    with open(CELL_FILE) as f:
        a = json.load(f)["arch"]
    mla = 6_291_456 + 1_048_576 + 131_072 + 2_097_152 + 4_194_304
    moe = 131_072 + 17_301_504 + 0.75 * 8_650_752
    per_token = 6 * (5 * mla + 67_239_936 + 4 * moe + 26_214_400) \
        + 5 * 6 * 2048 * 16 * 320
    assert ref.flops_per_token(a, 2048) == per_token
    assert per_token == 1_862_270_976


SEED = 2**31 + 41


def _tiny_cell(monkeypatch):
    """The cell at its reference's tiny size, run on the CPU."""
    from bench import device, harness
    monkeypatch.setattr(device, "require",
                        lambda devices, chips: {"bf16_flops_per_s": math.nan})
    monkeypatch.setattr(harness, "memory_stats", lambda d: {
        "bytes_in_use": 0, "peak_bytes_in_use": 0})
    cell = harness.load_cell("deepseek-v2-lite-5l.hole-long")
    cell.config = ref.tiny(cell.config)
    return cell


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_tiny_run_is_correct_only_when_sound(fault, monkeypatch):
    """The whole run through the trainer at the tiny size: sound it comes
    out correct, with the step returning its state unchanged or seeing
    half its rows it does not."""
    import time
    from bench import harness
    from repro.models import build_model
    cell = _tiny_cell(monkeypatch)
    wrap = None
    if fault == "unchanged":
        loss = jax.jit(build_model(harness.arch_config(cell.config)).loss)
        wrap = lambda fn, n, mesh: (  # noqa: E731
            lambda p, o, batch, lr: (p, o, loss(p, batch)))
    elif fault == "half_batch":
        wrap = lambda fn, n, mesh: (lambda p, o, batch, lr: fn(  # noqa: E731
            p, o, {k: v[:v.shape[0] // 2] for k, v in batch.items()}, lr))
    result = harness.run(cell, SEED, 0.5, False, time.perf_counter(), wrap)
    assert result["correct"] is (fault is None), result["checks"]
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference with float8 matmul operands in the program's place,
    at the tiny size: the cell's limits reject it."""
    from bench import check
    from bench.reference import lm, train as reference
    cell = _tiny_cell(monkeypatch)
    nodes = cell.traffic["check_nodes"]
    want = reference.train(cell.config, SEED, nodes)
    got = reference.train(cell.config, SEED, nodes, dt=jnp.bfloat16,
                          prec=lm.FP8)
    values = check.numbers(got, want)
    correct, table = check.judge(values, {
        k: v for k, v in cell.limits.items() if k in values})
    assert not correct, values


def _undefined_outside_groups(lhs, rhs, group_sizes):
    """``_grouped`` as XLA's ragged dot behaves on a TPU v5e: rows past
    the last group come out of it undefined (NaN here), in the output and
    in the rows' gradient; and, at worst, the weight gradient takes them
    into the last group."""
    beyond = jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes)

    @jax.custom_vjp
    def f(a, b):
        out = jax.lax.ragged_dot(a, b, group_sizes,
                                 preferred_element_type=jnp.float32)
        return jnp.where(beyond[:, None], jnp.nan, out)

    def f_fwd(a, b):
        return f(a, b), (a, b)

    def f_bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
            a, b, group_sizes, preferred_element_type=jnp.float32), a, b)
        da, db = vjp(g)
        extra = jnp.einsum("ti,tj->ij", a * beyond[:, None],
                           g * beyond[:, None])
        return (jnp.where(beyond[:, None], jnp.nan, da),
                db.at[-1].add(extra))

    f.defvjp(f_fwd, f_bwd)
    return f(lhs, rhs)


def test_held_share_keeps_the_rows_outside_its_groups_out(monkeypatch):
    """Whatever a grouped matmul leaves in the rows of the assignments to
    experts not held, the held share's output and every gradient are the
    dense path's, and finite."""
    cfg = _share(_tiny_moe(), 8, 2, 4, norm_topk=False, seq_aux=True)
    params = materialize(M.moe_defs(cfg), jax.random.key(16))
    x = jax.random.normal(jax.random.key(17), (2, 24, cfg.d_model))
    wts = jax.random.normal(jax.random.key(18), x.shape)

    def grads(strategy):
        def f(p, x):
            y, aux = M.moe_apply(p, x, cfg, strategy=strategy)
            return jnp.sum(y * wts) + aux, y
        return jax.grad(f, argnums=(0, 1), has_aux=True)(params, x)

    with jax.default_matmul_precision("highest"):
        (gd, yd) = grads("dense")
        monkeypatch.setattr(M, "_grouped", _undefined_outside_groups)
        (gs, ys) = grads("dropless")

    def close(a, b):
        assert bool(jnp.all(jnp.isfinite(b)))
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(a))))

    close(yd, ys)
    jax.tree.map(close, gd, gs)
