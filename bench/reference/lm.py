"""Plain reference of a decoder LM: Llama-style dense blocks (Yi) or
Granite-MoE blocks, its loss, and its parameter initialisation.

Per layer: ``x += attn(rmsnorm(x))`` then ``x += mlp(rmsnorm(x))``.
Attention is grouped-query with rotary embeddings (rotate-half form,
``theta`` from the configuration) and a causal softmax; query head ``h``
reads key/value head ``h // (n_heads / n_kv_heads)``.  The MLP is SwiGLU,
or a mixture of experts: a float32 router (in the control, rounded like
every other matmul), softmax over all experts, the
top-k probabilities renormalised to sum to 1 as gates, every token's
output the gate-weighted sum of its experts' SwiGLU outputs, and a
Switch-style load-balance loss ``coef * E * sum_e f_e P_e`` (f_e the
share of tokens routed to expert e, scaled by E / k; P_e its mean
probability) added to the loss.  RMSNorm multiplies by ``1 + scale``.  The
loss is the mean next-token cross-entropy over ``(B, S - 1)`` positions.
Granite's embedding, attention, residual and logits multipliers are
applied with the values the configuration file states.

Parameters are a flat dict keyed by the path names the program's own
parameter tree gives them (``"['blocks'][0]['mixer']['wq']"``): the
initialisation recipe the program documents draws each leaf from
``normal(fold_in(key(seed), crc32(path))) * scale``, so the reference can
start from the same weights without taking them from the program.

``dtype`` and ``precision`` select the arithmetic: float32 at
``HIGHEST`` is the reference.  ``FP8`` is the control: bfloat16
activations, and every matmul's operands rounded to float8 (e4m3, one
scale per tensor) before a default-precision product, one precision
below the single bfloat16 pass in which the program's float32 matmuls run
on the TPU.  Statistics of norms, softmaxes and the loss stay in
float32.  To fit a chip beside the optimizer state, each layer is
rematerialised and attention and the loss head run over blocks of rows.

The rest of the reference contract (``bench/reference/__init__.py``):
``flops_per_token`` is ``bench/flops.py``'s count, ``file_only`` gives
the multipliers above as the program runs them, ``unmodelled`` names
what of the program's model this file does not compute, and ``tiny``
cuts a configuration to the CPU tests' size.
"""
from __future__ import annotations

import copy
import math
import zlib
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.flops import flops_per_token  # noqa: F401  (the contract's)

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT
FP8 = "fp8"                      # the control's matmuls (see above)
E4M3_MAX = 448.0
ROW_BLOCK = 512                  # query rows per attention / loss block

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def leaf_specs(a: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Path name -> (shape, init scale); scale 0 means zeros."""
    d, v, L = a["d_model"], a["vocab_size"], a["n_layers"]
    h, kv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    s = {"['embed']": ((v, d), d ** -0.5),
         "['final_norm']": ((d,), 0.0)}
    if not a["tie_embeddings"]:
        s["['unembed']"] = ((v, d), d ** -0.5)
    b = "['blocks'][0]"
    s[b + "['mixer_norm']"] = ((L, d), 0.0)
    s[b + "['mixer']['wq']"] = ((L, d, h * hd), d ** -0.5)
    s[b + "['mixer']['wk']"] = ((L, d, kv * hd), d ** -0.5)
    s[b + "['mixer']['wv']"] = ((L, d, kv * hd), d ** -0.5)
    s[b + "['mixer']['wo']"] = ((L, h * hd, d), (h * hd) ** -0.5)
    s[b + "['mlp_norm']"] = ((L, d), 0.0)
    moe = a.get("moe")
    if moe:
        e, de = moe["n_experts"], moe["d_expert"]
        s[b + "['mlp']['router']"] = ((L, d, e), d ** -0.5)
        s[b + "['mlp']['w_gate']"] = ((L, e, d, de), d ** -0.5)
        s[b + "['mlp']['w_up']"] = ((L, e, d, de), d ** -0.5)
        s[b + "['mlp']['w_down']"] = ((L, e, de, d), de ** -0.5)
    else:
        f = a["d_ff"]
        s[b + "['mlp']['w_gate']"] = ((L, d, f), d ** -0.5)
        s[b + "['mlp']['w_up']"] = ((L, d, f), d ** -0.5)
        s[b + "['mlp']['w_down']"] = ((L, f, d), f ** -0.5)
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


def fp8(x):
    """Round to float8 e4m3 with one scale for the tensor; the gradient
    passes through."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / E4M3_MAX + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(prec):
    """``einsum(spec, a, b)`` in the arithmetic ``prec`` names."""
    if prec == FP8:
        return lambda spec, a, b: jnp.einsum(spec, fp8(a), fp8(b),
                                             precision=DEFAULT)
    return partial(jnp.einsum, precision=prec)


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale)).astype(x.dtype)


def rope(x, theta):
    """x: (B, S, H, D), rotate-half form, positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attention(a, p, l, h, dt, prec):
    mm = matmul(prec)
    b = "['blocks'][0]['mixer']"
    B, S, _ = h.shape
    H, KV, D = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    q = mm("bsd,df->bsf", h, p[b + "['wq']"][l].astype(dt)).reshape(B, S, H, D)
    k = mm("bsd,df->bsf", h, p[b + "['wk']"][l].astype(dt)).reshape(B, S, KV, D)
    v = mm("bsd,df->bsf", h, p[b + "['wv']"][l].astype(dt)).reshape(B, S, KV, D)
    q, k = rope(q, a["rope_theta"]), rope(k, a["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scale = a.get("attention_multiplier") or D ** -0.5
    kpos = jnp.arange(S)

    @jax.checkpoint
    def rows(qb, q0):
        s = mm("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32) * scale
        qpos = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1).astype(dt)
        return mm("bhqk,bkhd->bqhd", probs, v)

    out = jnp.concatenate([rows(q[:, i:i + ROW_BLOCK], i)
                           for i in range(0, S, ROW_BLOCK)], axis=1)
    return mm("bsf,fd->bsd", out.reshape(B, S, H * D),
              p[b + "['wo']"][l].astype(dt))


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def mlp(a, p, l, h, dt, prec):
    """Returns (output, load-balance loss)."""
    mm = matmul(prec)
    b = "['blocks'][0]['mlp']"
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    moe = a.get("moe")
    if not moe:
        y = swiglu(x, *(p[b + f"['{w}']"][l].astype(dt)
                        for w in ("w_gate", "w_up", "w_down")), mm)
        return y.reshape(B, S, d), jnp.zeros((), jnp.float32)
    e, k = moe["n_experts"], moe["top_k"]
    logits = mm("td,de->te", x.astype(jnp.float32), p[b + "['router']"][l])
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    gates = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                    * top[..., None], axis=1)                     # (T, E)
    routed = jnp.mean((gates > 0).astype(jnp.float32), axis=0) * (e / k)
    aux = moe["router_aux_coef"] * e * jnp.sum(routed * jnp.mean(probs, 0))

    def expert(y, w):
        wg, wu, wd, g = w
        return y + g[:, None].astype(dt) * swiglu(
            x, wg.astype(dt), wu.astype(dt), wd.astype(dt), mm), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p[b + "['w_gate']"][l], p[b + "['w_up']"][l],
                         p[b + "['w_down']"][l], gates.T))
    return y.reshape(B, S, d), aux


def loss(a: Dict, p: Params, tokens: jax.Array, *, dt=jnp.float32,
         prec=HIGHEST) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (B, S), plus the
    load-balance loss of the MoE layers."""
    mm = matmul(prec)
    eps = a["norm_eps"]
    res = a.get("residual_multiplier", 1.0)
    x = (p["['embed']"][tokens] * a.get("embedding_multiplier", 1.0)
         ).astype(dt)
    aux = jnp.zeros((), jnp.float32)
    for l in range(a["n_layers"]):
        @jax.checkpoint
        def layer(x, p=p, l=l):
            nb = "['blocks'][0]"
            x = x + res * attention(a, p, l, rms_norm(
                x, p[nb + "['mixer_norm']"][l], eps), dt, prec)
            y, aux_l = mlp(a, p, l, rms_norm(
                x, p[nb + "['mlp_norm']"][l], eps), dt, prec)
            return x + res * y, aux_l
        x, aux_l = layer(x)
        aux = aux + aux_l
    x = rms_norm(x, p["['final_norm']"], eps)
    head = p["['embed']" if a["tie_embeddings"] else "['unembed']"]
    head = head.astype(dt)
    scaling = a.get("logits_scaling", 1.0)
    B, S = tokens.shape

    @jax.checkpoint
    def ce(xb, labels):
        logits = mm("bsd,vd->bsv", xb, head).astype(jnp.float32) / scaling
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    total = sum(ce(x[:, i:min(i + ROW_BLOCK, S - 1)],
                   tokens[:, i + 1:min(i + ROW_BLOCK, S - 1) + 1])
                for i in range(0, S - 1, ROW_BLOCK))
    return total / (B * (S - 1)) + aux


# ---------------------------------------------------------------------------
# the rest of the contract
# ---------------------------------------------------------------------------


def file_only(arch) -> Dict[str, float]:
    """The file's keys that the program's ``ArchConfig`` has no field of
    that name for, which this reference applies, at the values the
    program runs: no multiplier, and its own attention scale."""
    return {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "logits_scaling": 1.0,
            "attention_multiplier": arch.query_scale or arch.head_dim ** -0.5}


def unmodelled(arch, a: Dict) -> List[str]:
    """The features of the program's ``ArchConfig`` that this reference
    does not compute, each by its name: anything but one attention layer
    repeated, with a SwiGLU MLP of width ``d_ff`` or routed experts
    alone, full causal attention at the scale the file states (or
    ``head_dim ** -0.5`` where it states none), plain pre-norms and
    unscaled embeddings."""
    pattern = arch.layer_pattern
    scale = arch.head_dim ** -0.5
    terms = {
        "layer_pattern": not (len(pattern) == 1
                              and pattern[0].mixer == "attn"),
        "mla": arch.mla is not None,
        "ssm": arch.ssm is not None,
        "encoder": arch.encoder is not None,
        "frontend": arch.frontend != "none",
        "sliding_window": bool(arch.sliding_window),
        "post_norms": arch.post_norms,
        "qk_norm": arch.qk_norm,
        "scale_embeddings": arch.scale_embeddings,
        "attn_logit_softcap": bool(arch.attn_logit_softcap),
        "final_logit_softcap": bool(arch.final_logit_softcap),
        "query_scale": (arch.query_scale or scale)
        != a.get("attention_multiplier", scale),
        # computed as the file's ``moe`` says, not as the pattern says
        "mlp_kind": any(s.mlp != ("moe" if a.get("moe") else "dense")
                        for s in pattern),
        "mlp_activation": arch.mlp_activation != "swiglu",
        "dense_d_ff": arch.dense_d_ff not in (0, arch.d_ff),
        "moe.n_shared": bool(arch.moe and arch.moe.n_shared),
    }
    return [name for name, missing in terms.items() if missing]


TINY_SEQ_LEN = 64
TINY_WIDTHS = {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
               "d_ff": 256, "vocab_size": 512}
TINY_MOE = {"n_experts": 4, "top_k": 2, "d_expert": 64}


def tiny(cfg: Dict) -> Dict:
    """A configuration file's dict at ``reduced()``-like widths, the
    structure (pattern, MoE routing, untied head) kept."""
    cfg = copy.deepcopy(cfg)
    widths = dict(TINY_WIDTHS)
    if cfg["arch"].get("moe"):
        widths["d_ff"] = TINY_MOE["d_expert"]
        cfg["changes"]["moe"] = dict(TINY_MOE)
        cfg["arch"]["moe"].update(TINY_MOE)
        cfg["arch"]["attention_multiplier"] = TINY_WIDTHS["head_dim"] ** -0.5
    cfg["changes"].update(widths)
    cfg["arch"].update(widths)
    cfg["train"]["seq_len"] = TINY_SEQ_LEN
    return cfg
