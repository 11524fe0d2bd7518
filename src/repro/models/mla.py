"""DeepSeek-V2 Multi-head Latent Attention (MLA). [arXiv:2405.04434]

Keys/values are compressed into a per-token latent ``c_kv`` of rank
``kv_lora_rank`` plus a single shared RoPE key.  The rope is YaRN-scaled
where the configuration says (``cfg.yarn``), and the logits are scaled by
``layers.softmax_scale``: ``qk ** -0.5``, times ``m ** 2`` under YaRN.  The decode path uses the
*absorbed* formulation: query projections are folded through ``w_uk`` /
``w_uv`` so the KV cache stores only ``(rank + rope_dim)`` floats per token
— this is the mechanism that makes MLA serve long contexts cheaply, and is
what ``decode_32k`` lowers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.attention import NEG_INF, attend_blockwise, attend_direct, \
    BLOCKWISE_THRESHOLD
from repro.models.layers import (ParamDef, apply_rope, dense_def, rms_norm,
                                 softmax_scale)


def mla_defs(cfg: ArchConfig, model_shards: int = 1, dtype=jnp.float32) -> dict:
    mla = cfg.mla
    assert mla is not None
    d, h = cfg.d_model, cfg.n_heads
    qk = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    h_spec = P(None, "model") if h % model_shards == 0 else P(None, None)
    return {
        "wq": dense_def(d, h * qk, h_spec, dtype=dtype),
        "w_dkv": dense_def(d, mla.kv_lora_rank, P(None, None), dtype=dtype),
        "kv_norm": ParamDef((mla.kv_lora_rank,), spec=P(), init="zeros",
                            dtype=jnp.float32),
        "w_kr": dense_def(d, mla.qk_rope_head_dim, P(None, None), dtype=dtype),
        "w_uk": dense_def(mla.kv_lora_rank, h * mla.qk_nope_head_dim, h_spec,
                          dtype=dtype),
        "w_uv": dense_def(mla.kv_lora_rank, h * mla.v_head_dim, h_spec,
                          dtype=dtype),
        "wo": dense_def(h * mla.v_head_dim, d,
                        P("model", None) if h % model_shards == 0 else P(None, None),
                        dtype=dtype),
    }


def mla_apply(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Full-sequence (train / prefill) MLA. x: (B,S,d)."""
    mla = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    qk = nope + rope_d

    pos = jnp.arange(s)
    with jax.named_scope("mla/q"):
        q = (x @ p["wq"]).reshape(b, s, h, qk)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta, cfg.yarn)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    with jax.named_scope("mla/kv_latent"):
        c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], pos,
                            cfg.rope_theta, cfg.yarn)
        k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, nope)
        v = (c_kv @ p["w_uv"]).reshape(b, s, h, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope_d))], axis=-1)

    # KV == H heads, group size 1; pad V up to qk dim not needed — attend_*
    # contracts q·k on last dim and p·v on v's own dim, but our helpers
    # assume same head_dim.  Pad v to qk (zeros) and slice after.
    with jax.named_scope("mla/attend"):
        qh = q_full.reshape(b, s, h, 1, qk)
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - vd)))
        kwargs = dict(q_pos=pos, k_pos=pos, causal=True, window=0,
                      logit_cap=0.0, scale=softmax_scale(qk, cfg.yarn))
        if s > BLOCKWISE_THRESHOLD:
            out = attend_blockwise(qh, k, v_pad, **kwargs)
        else:
            out = attend_direct(qh, k, v_pad, **kwargs)
        out = out.reshape(b, s, h, qk)[..., :vd]
    with jax.named_scope("mla/out"):
        return out.reshape(b, s, h * vd) @ p["wo"]


# ---------------------------------------------------------------------------
# Decode with latent cache (absorbed formulation)
# ---------------------------------------------------------------------------


def init_mla_cache(cfg: ArchConfig, batch: int, cache_len: int,
                   dtype=jnp.bfloat16) -> dict:
    mla = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, cache_len, mla.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, mla.qk_rope_head_dim), dtype),
    }


def mla_cache_specs(batch_axes, seq_axes) -> dict:
    return {"c_kv": P(batch_axes, seq_axes, None),
            "k_rope": P(batch_axes, seq_axes, None)}


def mla_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
               cfg: ArchConfig) -> tuple[jax.Array, dict]:
    """One-token MLA decode. x: (B,1,d)."""
    mla = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    nope, rope_d, vd = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    qk = nope + rope_d
    rank = mla.kv_lora_rank

    x1 = x[:, 0, :]
    q = (x1 @ p["wq"]).reshape(b, h, qk)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    posv = jnp.full((1,), 1, jnp.int32) * pos
    q_rope = apply_rope(q_rope[:, None], posv, cfg.rope_theta,
                        cfg.yarn)[:, 0]

    c_new = rms_norm(x1 @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope((x1 @ p["w_kr"])[:, None, None, :], posv,
                        cfg.rope_theta, cfg.yarn)[:, 0, 0]

    c_kv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_new[:, None, :].astype(cache["c_kv"].dtype), (0, pos, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], kr_new[:, None, :].astype(cache["k_rope"].dtype),
        (0, pos, 0))

    # absorbed: q_lat[b,h,r] = sum_n q_nope[b,h,n] * w_uk[r, h, n]
    w_uk = p["w_uk"].reshape(rank, h, nope)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)

    s_lat = jnp.einsum("bhr,btr->bht", q_lat, c_kv.astype(q_lat.dtype))
    s_rope = jnp.einsum("bhd,btd->bht", q_rope, k_rope.astype(q_rope.dtype))
    scores = (s_lat + s_rope).astype(jnp.float32) * softmax_scale(
        qk, cfg.yarn)
    valid = jnp.arange(c_kv.shape[1]) <= pos
    scores = jnp.where(valid[None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)

    out_lat = jnp.einsum("bht,btr->bhr", probs.astype(c_kv.dtype), c_kv)
    w_uv = p["w_uv"].reshape(rank, h, vd)
    out = jnp.einsum("bhr,rhv->bhv", out_lat, w_uv)
    out = (out.reshape(b, h * vd) @ p["wo"])[:, None, :]
    return out, {"c_kv": c_kv, "k_rope": k_rope}
