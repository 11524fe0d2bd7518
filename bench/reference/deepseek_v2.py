"""Plain reference of DeepSeek-V2(-Lite): latent attention with YaRN rope,
a leading dense SwiGLU layer, then MoE layers of routed and shared
experts, of which a configuration may hold one chip's share.

Per layer: ``x += mla(rmsnorm(x))`` then ``x += mlp(rmsnorm(x))``, the MLP
kind from the file's ``layer_pattern`` (the pattern repeats over
``n_layers``).  Latent attention, for head ``h``: ``q = x W_q`` split into
``q_nope`` (``qk_nope_head_dim``) and ``q_pe`` (``qk_rope_head_dim``);
the latent ``c = rmsnorm(x W_dkv)`` of rank ``kv_lora_rank``;
``k_nope = c W_uk``, ``v = c W_uv`` (``v_head_dim``), and one rope key
``k_pe = x W_kr`` shared by every head.  The logits are ``q_nope .
k_nope + q_pe . k_pe``, both rotated parts in the rotate-half form,
scaled by ``(nope + rope) ** -0.5 * m ** 2`` with ``m = 0.1 *
mscale_all_dim * ln(factor) + 1``; a causal softmax weighs ``v``.  The
rope's frequencies are YaRN's as DeepSeek-V2 blends them
(``DeepseekV2YarnRotaryEmbedding``): ``f_i = theta ** (-2i / D)``, kept
for pairs below ``low``, divided by ``factor`` above ``high``, linear
between, where ``low``/``high`` are the pairs that turn ``beta_fast`` /
``beta_slow`` times over ``original_max_position_embeddings`` positions;
cos and sin are scaled by ``m(mscale) / m(mscale_all_dim)``.

The MoE router is a float32 softmax over all ``routing.n_routed`` experts
(in the control, rounded like every other matmul); its top-k
probabilities are the gates, renormalised to sum to 1 only where
``routing.norm_topk``.  The layer holds experts ``first_held ..
first_held + moe.n_experts - 1``: its output is the gate-weighted sum of
the SwiGLU outputs of the held experts among each token's top-k, plus the
shared experts' one SwiGLU of width ``n_shared * d_expert``.  The
load-balance loss covers all routed experts: where ``routing.seq_aux``,
per sequence ``f_e`` = its assignments to ``e`` over ``S k / E`` and
``P_e`` = its mean probability, ``coef * sum_e f_e P_e`` averaged over
the sequences (DeepSeek's ``MoEGate``); otherwise ``lm``'s Switch form.
RMSNorm multiplies by ``1 + scale``.  The loss is the mean next-token
cross-entropy over ``(B, S - 1)`` positions plus the load-balance losses.

Parameters, initialisation and arithmetic are ``lm``'s (the program's
parameter paths, ``normal(fold_in(key(seed), crc32(path))) * scale``;
float32 at ``lm.HIGHEST`` the reference, bfloat16 at ``lm.DEFAULT`` or
``lm.FP8`` the controls).  Each layer is rematerialised and attention and
the loss head run over blocks of rows, to fit a chip beside the
trainer's freed state.
"""
from __future__ import annotations

import copy
import math
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.reference.lm import HIGHEST, ROW_BLOCK, matmul, rms_norm

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _routed(a: Dict) -> int:
    return a["routing"]["n_routed"] or a["moe"]["n_experts"]


def leaf_specs(a: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Path name -> (shape, init scale); scale 0 means zeros."""
    d, v, H = a["d_model"], a["vocab_size"], a["n_heads"]
    m = a["mla"]
    nope, rd, vd, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                       m["v_head_dim"], m["kv_lora_rank"])
    pattern = a["layer_pattern"]
    n = a["n_layers"] // len(pattern)
    s = {"['embed']": ((v, d), d ** -0.5), "['final_norm']": ((d,), 0.0)}
    if not a["tie_embeddings"]:
        s["['unembed']"] = ((v, d), d ** -0.5)
    for i, spec in enumerate(pattern):
        b = f"['blocks'][{i}]"
        s[b + "['mixer_norm']"] = ((n, d), 0.0)
        s[b + "['mixer']['wq']"] = ((n, d, H * (nope + rd)), d ** -0.5)
        s[b + "['mixer']['w_dkv']"] = ((n, d, r), d ** -0.5)
        s[b + "['mixer']['kv_norm']"] = ((n, r), 0.0)
        s[b + "['mixer']['w_kr']"] = ((n, d, rd), d ** -0.5)
        s[b + "['mixer']['w_uk']"] = ((n, r, H * nope), r ** -0.5)
        s[b + "['mixer']['w_uv']"] = ((n, r, H * vd), r ** -0.5)
        s[b + "['mixer']['wo']"] = ((n, H * vd, d), (H * vd) ** -0.5)
        s[b + "['mlp_norm']"] = ((n, d), 0.0)
        mb = b + "['mlp']"
        if spec["mlp"] == "dense":
            f = a["dense_d_ff"] or a["d_ff"]
            s[mb + "['w_gate']"] = ((n, d, f), d ** -0.5)
            s[mb + "['w_up']"] = ((n, d, f), d ** -0.5)
            s[mb + "['w_down']"] = ((n, f, d), f ** -0.5)
            continue
        moe = a["moe"]
        e, de = moe["n_experts"], moe["d_expert"]
        s[mb + "['router']"] = ((n, d, _routed(a)), d ** -0.5)
        s[mb + "['w_gate']"] = ((n, e, d, de), d ** -0.5)
        s[mb + "['w_up']"] = ((n, e, d, de), d ** -0.5)
        s[mb + "['w_down']"] = ((n, e, de, d), de ** -0.5)
        if moe["n_shared"]:
            f = de * moe["n_shared"]
            s[mb + "['shared']['w_gate']"] = ((n, d, f), d ** -0.5)
            s[mb + "['shared']['w_up']"] = ((n, d, f), d ** -0.5)
            s[mb + "['shared']['w_down']"] = ((n, f, d), f ** -0.5)
    return s


def init(a: Dict, seed: int) -> Params:
    """The initial weights for ``seed``, made on the device."""
    specs = leaf_specs(a)

    @jax.jit
    def make(key):
        out = {}
        for path, (shape, scale) in specs.items():
            if scale == 0.0:
                out[path] = jnp.zeros(shape, jnp.float32)
            else:
                k = jax.random.fold_in(key, zlib.crc32(path.encode()))
                out[path] = jax.random.normal(k, shape, jnp.float32) * scale
        return out

    return make(jax.random.key(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x, a):
    """x: (B, S, H, D), rotate-half form, positions 0..S-1, with YaRN's
    frequencies and cos/sin scale where the file states ``yarn``."""
    s, d = x.shape[1], x.shape[-1]
    theta, y = a["rope_theta"], a.get("yarn")
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    scale = 1.0
    if y:
        def pair(rotations):
            return d * math.log(y["original_max_position_embeddings"] / (
                rotations * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(pair(y["beta_fast"])), 0)
        high = min(math.ceil(pair(y["beta_slow"])), d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        keep = 1.0 - ramp
        freqs = freqs / y["factor"] * (1.0 - keep) + freqs * keep
        scale = (_mscale(y["factor"], y["mscale"])
                 / _mscale(y["factor"], y["mscale_all_dim"]))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (S, D/2)
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attention_scale(a: Dict) -> float:
    m = a["mla"]
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    y = a.get("yarn")
    if y and y["mscale_all_dim"]:
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def mla(a, w, h, dt, prec):
    """Latent attention of ``h`` (B, S, d); ``w(name)`` gives a weight."""
    mm = matmul(prec)
    B, S, _ = h.shape
    H, m = a["n_heads"], a["mla"]
    nope, rd = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd = m["v_head_dim"]
    q = mm("bsd,df->bsf", h, w("wq").astype(dt)).reshape(B, S, H, nope + rd)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], a)
    c = rms_norm(mm("bsd,dr->bsr", h, w("w_dkv").astype(dt)), w("kv_norm"),
                 a["norm_eps"])
    k_pe = rope(mm("bsd,dr->bsr", h, w("w_kr").astype(dt))[:, :, None, :],
                a)[:, :, 0]                                   # (B, S, rd)
    k_nope = mm("bsr,rf->bsf", c, w("w_uk").astype(dt)).reshape(B, S, H, nope)
    v = mm("bsr,rf->bsf", c, w("w_uv").astype(dt)).reshape(B, S, H, vd)
    scale = attention_scale(a)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def rows(qn, qp, q0):
        s = (mm("bqhn,bkhn->bhqk", qn, k_nope)
             + mm("bqhr,bkr->bhqk", qp, k_pe)).astype(jnp.float32) * scale
        qpos = q0 + jnp.arange(qn.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1).astype(dt)
        return mm("bhqk,bkhv->bqhv", probs, v)

    out = jnp.concatenate([rows(q_nope[:, i:i + ROW_BLOCK],
                                q_pe[:, i:i + ROW_BLOCK], i)
                           for i in range(0, S, ROW_BLOCK)], axis=1)
    return mm("bsf,fd->bsd", out.reshape(B, S, H * vd), w("wo").astype(dt))


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def moe(a, w, x, B, dt, prec):
    """The held experts' and the shared experts' output for the rows
    ``x`` (B·S, d) of ``B`` sequences, and the load-balance loss;
    ``w(*names)`` gives a weight of the layer's MLP."""
    mm = matmul(prec)
    cfg, routing = a["moe"], a["routing"]
    E, k = _routed(a), cfg["top_k"]
    first, held = routing["first_held"], cfg["n_experts"]
    logits = mm("td,de->te", x.astype(jnp.float32), w("router"))
    probs = jax.nn.softmax(logits, axis=-1)                        # (T, E)
    top, idx = jax.lax.top_k(probs, k)
    if routing["norm_topk"]:
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)             # (T, k, E)
    gates = jnp.sum(chosen * top[..., None], axis=1)               # (T, E)
    if routing["seq_aux"]:
        S = x.shape[0] // B
        f = chosen.reshape(B, S * k, E).sum(1) * (E / (S * k))
        P = probs.reshape(B, S, E).mean(1)
        aux = cfg["router_aux_coef"] * jnp.mean(jnp.sum(f * P, axis=-1))
    else:
        f = jnp.mean((gates > 0).astype(jnp.float32), axis=0) * (E / k)
        aux = cfg["router_aux_coef"] * E * jnp.sum(f * jnp.mean(probs, 0))

    def expert(y, ws):
        wg, wu, wd, g = ws
        return y + g[:, None].astype(dt) * swiglu(
            x, wg.astype(dt), wu.astype(dt), wd.astype(dt), mm), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (w("w_gate"), w("w_up"), w("w_down"),
                         gates[:, first:first + held].T))
    if cfg["n_shared"]:
        y = y + swiglu(x, *(w("shared", n).astype(dt)
                            for n in ("w_gate", "w_up", "w_down")), mm)
    return y, aux


def loss(a: Dict, p: Params, tokens: jax.Array, *, dt=jnp.float32,
         prec=HIGHEST) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (B, S), plus the
    load-balance losses of the MoE layers."""
    mm = matmul(prec)
    eps = a["norm_eps"]
    pattern = a["layer_pattern"]
    B, S = tokens.shape
    x = p["['embed']"][tokens].astype(dt)
    aux = jnp.zeros((), jnp.float32)
    for layer in range(a["n_layers"]):
        i, n = layer % len(pattern), layer // len(pattern)

        @jax.checkpoint
        def block(x, p=p, i=i, n=n):
            def w(*names):
                return p[f"['blocks'][{i}]"
                         + "".join(f"['{k}']" for k in names)][n]
            x = x + mla(a, lambda name: w("mixer", name),
                        rms_norm(x, w("mixer_norm"), eps), dt, prec)
            h = rms_norm(x, w("mlp_norm"), eps).reshape(B * S, -1)
            if pattern[i]["mlp"] == "dense":
                y = swiglu(h, *(w("mlp", name).astype(dt) for name in
                                ("w_gate", "w_up", "w_down")), mm)
                aux_l = jnp.zeros((), jnp.float32)
            else:
                y, aux_l = moe(a, lambda *names: w("mlp", *names), h, B, dt,
                               prec)
            return x + y.reshape(x.shape), aux_l
        x, aux_l = block(x)
        aux = aux + aux_l
    x = rms_norm(x, p["['final_norm']"], eps)
    head = p["['embed']" if a["tie_embeddings"] else "['unembed']"].astype(dt)

    @jax.checkpoint
    def ce(xb, labels):
        logits = mm("bsd,vd->bsv", xb, head).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    total = sum(ce(x[:, i:min(i + ROW_BLOCK, S - 1)],
                   tokens[:, i + 1:min(i + ROW_BLOCK, S - 1) + 1])
                for i in range(0, S - 1, ROW_BLOCK))
    return total / (B * (S - 1)) + aux


# ---------------------------------------------------------------------------
# the rest of the contract
# ---------------------------------------------------------------------------


def matmul_params_per_token(a: Dict) -> float:
    """Matmul parameters a token passes through: every layer's latent
    attention projections, the dense layer's SwiGLU, each MoE layer's
    router, shared experts and its held experts at their expectation
    (``top_k * n_experts / n_routed`` of an expert a token: each of its
    top-k falls on a held expert with that chance), and the head."""
    d, H, m = a["d_model"], a["n_heads"], a["mla"]
    nope, rd, vd, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                       m["v_head_dim"], m["kv_lora_rank"])
    attn = d * H * (nope + rd) + d * r + d * rd + r * H * (nope + vd) \
        + H * vd * d
    pattern = a["layer_pattern"]
    total = 0.0
    for layer in range(a["n_layers"]):
        if pattern[layer % len(pattern)]["mlp"] == "dense":
            mlp = 3 * d * (a["dense_d_ff"] or a["d_ff"])
        else:
            moe = a["moe"]
            E = _routed(a)
            expert = 3 * d * moe["d_expert"]
            mlp = d * E + moe["n_shared"] * expert \
                + moe["top_k"] * moe["n_experts"] / E * expert
        total += attn + mlp
    return total + a["vocab_size"] * d


def flops_per_token(a: Dict, seq_len: int) -> float:
    """``bench/flops.py``'s conventions: 6 x the matmul parameters a
    token passes through, and 6 x sequence x heads x (QK + V head sizes)
    a layer for the scores and their weighted values (forward and
    backward, the full square)."""
    m = a["mla"]
    qk_v = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return float(6 * matmul_params_per_token(a)
                 + 6 * a["n_layers"] * seq_len * a["n_heads"] * qk_v)


def file_only(arch) -> Dict[str, float]:
    """No parameter of this reference lacks a field in ``ArchConfig``."""
    return {}


def unmodelled(arch, a: Dict) -> List[str]:
    """The features of the program's ``ArchConfig`` that this reference
    does not compute, each by its name: anything but latent attention
    without a query LoRA in every layer, followed by a SwiGLU MLP or
    MoE layer, plain pre-norms, unscaled embeddings and logits."""
    terms = {
        "mla": arch.mla is None,
        "mla.q_lora_rank": bool(arch.mla and arch.mla.q_lora_rank),
        "layer_pattern": any(s.mixer != "attn" or s.mlp not in ("dense",
                                                                "moe")
                             for s in arch.layer_pattern),
        "ssm": arch.ssm is not None,
        "encoder": arch.encoder is not None,
        "frontend": arch.frontend != "none",
        "sliding_window": bool(arch.sliding_window),
        "post_norms": arch.post_norms,
        "qk_norm": arch.qk_norm,
        "scale_embeddings": arch.scale_embeddings,
        "attn_logit_softcap": bool(arch.attn_logit_softcap),
        "final_logit_softcap": bool(arch.final_logit_softcap),
        "mlp_activation": arch.mlp_activation != "swiglu",
    }
    return [name for name, missing in terms.items() if missing]


TINY_SEQ_LEN = 64
TINY_WIDTHS = {"d_model": 128, "n_heads": 4, "n_kv_heads": 4, "head_dim": 48,
               "d_ff": 64, "dense_d_ff": 256, "vocab_size": 512}
TINY_MLA = {"kv_lora_rank": 64, "qk_nope_head_dim": 32,
            "qk_rope_head_dim": 16, "v_head_dim": 32}
TINY_D_EXPERT = 64


def tiny(cfg: Dict) -> Dict:
    """A configuration file's dict at ``reduced()``-like widths, its
    structure kept: the layer pattern and depth, the router's width, the
    held share, top-k, shared experts, YaRN and the gates."""
    cfg = copy.deepcopy(cfg)
    widths = dict(TINY_WIDTHS)
    cfg["changes"].update(widths)
    cfg["arch"].update(widths)
    for part in ("changes", "arch"):
        cfg[part].setdefault("mla", {}).update(TINY_MLA)
        cfg[part].setdefault("moe", {})["d_expert"] = TINY_D_EXPERT
    cfg["train"]["seq_len"] = TINY_SEQ_LEN
    return cfg
