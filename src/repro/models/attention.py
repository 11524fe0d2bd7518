"""Grouped-query attention (GQA/MQA) with RoPE, sliding windows, logit
soft-capping, cross-attention and decode-with-KV-cache.

Three execution paths, chosen by :func:`attend` from the platform and the
shapes:

* the Pallas flash-attention kernel (``repro.kernels.flash_attention``,
  forward and backward) — self-attention on the TPU whose length tiles
  into the kernel's blocks (:func:`flash_eligible`);
* ``attend_direct`` — materializes the score matrix; everything else up
  to ``BLOCKWISE_THRESHOLD`` (CPU, cross-attention, ragged lengths);
* ``attend_blockwise`` — online-softmax over KV chunks (flash-attention
  algorithm expressed in XLA via ``lax.scan``); longer sequences, so the
  dry-run's compiled memory stays bounded.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels.flash_attention import BLOCKS
from repro.kernels.ops import flash_attention
from repro.models.layers import (ParamDef, apply_rope, data_mesh, dense_def,
                                 softcap)

NEG_INF = -2.0 ** 30  # large-but-finite; keeps bf16/fp32 softmax NaN-free

BLOCKWISE_THRESHOLD = 8192   # switch to online-softmax path above this
# Force the flash-style blockwise path at any length (perf variant knob).
FORCE_BLOCKWISE = False
Q_BLOCK = 1024
KV_BLOCK = 1024


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def attn_defs(cfg: ArchConfig, model_shards: int = 1, cross: bool = False,
              d_model: int = 0, n_heads: int = 0, n_kv: int = 0,
              head_dim: int = 0, dtype=jnp.float32) -> dict:
    """QKV/O projections.  Heads shard over the ``model`` mesh axis when they
    divide it; otherwise the projection is replicated (TP idle for that
    tensor — see DESIGN.md / roofline notes)."""
    d = d_model or cfg.d_model
    h = n_heads or cfg.n_heads
    kv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.head_dim
    q_spec = P(None, "model") if h % model_shards == 0 else P(None, None)
    kv_spec = P(None, "model") if kv % model_shards == 0 else P(None, None)
    o_spec = P("model", None) if h % model_shards == 0 else P(None, None)
    return {
        "wq": dense_def(d, h * hd, q_spec, dtype=dtype),
        "wk": dense_def(d, kv * hd, kv_spec, dtype=dtype),
        "wv": dense_def(d, kv * hd, kv_spec, dtype=dtype),
        "wo": dense_def(h * hd, d, o_spec, scale=(h * hd) ** -0.5, dtype=dtype),
    }


# ---------------------------------------------------------------------------
# Score-level helpers
# ---------------------------------------------------------------------------


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: int) -> jax.Array:
    """(Sq, Sk) additive bias from causal + sliding-window constraints."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF)


def attend_direct(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  q_pos: jax.Array, k_pos: jax.Array, causal: bool,
                  window: int, logit_cap: float, scale: float) -> jax.Array:
    """q: (B,Sq,KV,G,D)  k,v: (B,Sk,KV,D)  ->  (B,Sq,KV,G,D)."""
    scores = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32) * scale
    scores = softcap(scores, logit_cap)
    scores = scores + _mask_bias(q_pos, k_pos, causal, window)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,btkd->bqkgd", probs, v)


def attend_blockwise(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     q_pos: jax.Array, k_pos: jax.Array, causal: bool,
                     window: int, logit_cap: float, scale: float,
                     q_block: int = Q_BLOCK,
                     kv_block: int = KV_BLOCK) -> jax.Array:
    """Flash-attention contraction in XLA: scan over KV blocks with an
    online softmax, scanned over query blocks to bound live memory."""
    B, Sq, KV, G, _ = q.shape
    Sk, D = k.shape[1], v.shape[-1]
    nq = -(-Sq // q_block)
    nk = -(-Sk // kv_block)
    q_pad, kv_pad = nq * q_block - Sq, nk * kv_block - Sk

    qb = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
    qb = qb.reshape(B, nq, q_block, KV, G, D)
    qpos = jnp.pad(q_pos, (0, q_pad), constant_values=-1)
    qpos = qpos.reshape(nq, q_block)
    kb = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    kb = kb.reshape(B, nk, kv_block, KV, D)
    vb = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    vb = vb.reshape(B, nk, kv_block, KV, D)
    kpos = jnp.pad(k_pos, (0, kv_pad), constant_values=2**30)
    kpos = kpos.reshape(nk, kv_block)

    def q_step(_, qi):
        q_i, qpos_i = qi                       # (B,qb,KV,G,D), (qb,)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_i, v_i, kpos_i = ki
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_i, k_i).astype(jnp.float32)
            s = softcap(s * scale, logit_cap)
            s = s + _mask_bias(qpos_i, kpos_i, causal, window)
            # exclude padded KV positions (kpos sentinel) in all mask modes
            s = jnp.where(kpos_i[None, None, None, None, :] < Sk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(v_i.dtype), v_i
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpos),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)       # (B,KV,G,qb,D)

    _, outs = jax.lax.scan(q_step, None, (qb.swapaxes(0, 1), qpos))
    # outs: (nq, B, KV, G, qb, D) -> (B, nq, qb, KV, G, D) -> (B, Sq, KV, G, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * q_block, KV, G, D)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# The dispatch
# ---------------------------------------------------------------------------


def flash_eligible(platform: str, s: int, sk: int, cross: bool) -> bool:
    """Whether attention takes the Pallas kernel: on the TPU, where it
    compiles natively, for self-attention (``cross`` false) whose length
    is a whole number of the kernel's smallest block."""
    return (platform == "tpu" and not cross and s == sk
            and s % BLOCKS[-1] == 0)


def _flash(q, k, v, *, causal: bool, window: int, logit_cap: float,
           scale: float) -> jax.Array:
    """The kernel on (B,S,heads,D) operands.  Under a data-parallel
    context mesh each shard runs it on its own rows (``shard_map``): the
    partitioner cannot split a Mosaic call, and would gather the batch."""
    def heads_first(x):
        return x.transpose(0, 2, 1, 3)

    def local(q, k, v):
        return heads_first(flash_attention(
            heads_first(q), heads_first(k), heads_first(v), causal=causal,
            window=window, logit_cap=logit_cap, scale=scale))

    mesh = data_mesh()
    if mesh is not None:
        local = jax.shard_map(local, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False)
    return local(q, k, v)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
           window: int, logit_cap: float, scale: float,
           q_pos: Optional[jax.Array] = None, cross: bool = False,
           platform: Optional[str] = None) -> jax.Array:
    """q: (B,S,H,Dqk); k: (B,Sk,KV,Dqk); v: (B,Sk,KV,Dv) -> (B,S,H,Dv).

    The Pallas kernel where :func:`flash_eligible` holds for ``platform``
    (``jax.default_backend()`` unless given; the kernel runs interpreted
    off the TPU), else XLA: ``attend_direct``, or ``attend_blockwise``
    above ``BLOCKWISE_THRESHOLD``.  ``q_pos`` defaults to ``arange(S)``;
    explicit positions take the XLA path."""
    B, S, H, Dqk = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    platform = platform or jax.default_backend()
    if flash_eligible(platform, S, Sk, cross or q_pos is not None):
        return _flash(q, k, v, causal=causal, window=window,
                      logit_cap=logit_cap, scale=scale)
    kwargs = dict(q_pos=jnp.arange(S) if q_pos is None else q_pos,
                  k_pos=jnp.arange(Sk), causal=causal, window=window,
                  logit_cap=logit_cap, scale=scale)
    qg = q.reshape(B, S, KV, H // KV, Dqk)
    if FORCE_BLOCKWISE or max(S, Sk) > BLOCKWISE_THRESHOLD:
        out = attend_blockwise(qg, k, v, **kwargs)
    else:
        out = attend_direct(qg, k, v, **kwargs)
    return out.reshape(B, S, H, v.shape[-1])


# ---------------------------------------------------------------------------
# Full-sequence attention layer (train / prefill)
# ---------------------------------------------------------------------------


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


def attn_apply(p: dict, x: jax.Array, *, cfg: ArchConfig, causal: bool,
               window: int, positions: Optional[jax.Array] = None,
               n_heads: int = 0, n_kv: int = 0, head_dim: int = 0,
               memory: Optional[jax.Array] = None,
               use_rope: bool = True) -> jax.Array:
    """Self- (or cross-, when ``memory`` is given) attention over a full
    sequence. x: (B, S, d_model)."""
    B, S, _ = x.shape
    H = n_heads or cfg.n_heads
    KV = n_kv or cfg.n_kv_heads
    D = head_dim or cfg.head_dim
    kv_src = memory if memory is not None else x
    Sk = kv_src.shape[1]

    q = _split_heads(x @ p["wq"], H, D)
    k = _split_heads(kv_src @ p["wk"], KV, D)
    v = _split_heads(kv_src @ p["wv"], KV, D)

    q_pos = positions if positions is not None else jnp.arange(S)
    k_pos = jnp.arange(Sk)
    if use_rope and memory is None:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, k_pos, cfg.rope_theta)

    out = attend(q, k, v, causal=causal, window=window,
                 logit_cap=cfg.attn_logit_softcap,
                 scale=cfg.query_scale or D ** -0.5,
                 q_pos=positions, cross=memory is not None)
    return out.reshape(B, S, H * D) @ p["wo"]


# ---------------------------------------------------------------------------
# Decode step with KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, cache_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
    }


def kv_cache_specs(batch_axes, seq_axes) -> dict:
    return {"k": P(batch_axes, seq_axes, None, None),
            "v": P(batch_axes, seq_axes, None, None)}


def attn_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array, *,
                cfg: ArchConfig, window: int, n_heads: int = 0,
                n_kv: int = 0, head_dim: int = 0,
                use_rope: bool = True) -> tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, d_model); cache k/v: (B, W, KV, D).

    The cache is a ring buffer when ``window`` is non-zero (slot =
    pos % W); otherwise slot = pos.  Keys are stored rotated at their
    absolute position, so no re-rotation is needed at read time.
    """
    B, _, _ = x.shape
    H = n_heads or cfg.n_heads
    KV = n_kv or cfg.n_kv_heads
    D = head_dim or cfg.head_dim
    G = H // KV
    W = cache["k"].shape[1]

    q = _split_heads(x @ p["wq"], H, D)
    k_new = _split_heads(x @ p["wk"], KV, D)
    v_new = _split_heads(x @ p["wv"], KV, D)
    if use_rope:
        posv = jnp.full((1,), 1, jnp.int32) * pos
        q = apply_rope(q, posv, cfg.rope_theta)
        k_new = apply_rope(k_new, posv, cfg.rope_theta)

    slot = jnp.where(window, pos % jnp.maximum(W, 1), pos)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))

    # Valid slots: ring buffer is fully valid once pos+1 >= W; before that
    # only slots <= pos hold data.  (All cached absolute positions <= pos,
    # and > pos - W by ring construction, so causality/window are implied.)
    valid = (jnp.arange(W) <= pos) | (pos >= W)

    qh = q.reshape(B, 1, KV, G, D)
    scale = cfg.query_scale or D ** -0.5
    s = jnp.einsum("bqkgd,btkd->bkgqt", qh, k).astype(jnp.float32) * scale
    s = softcap(s, cfg.attn_logit_softcap)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v)
    out = out.reshape(B, 1, H * D) @ p["wo"]
    return out, {"k": k, "v": v}


def cross_attn_decode(p: dict, x: jax.Array, cross_kv: dict, *,
                      cfg: ArchConfig, n_heads: int = 0, n_kv: int = 0,
                      head_dim: int = 0) -> jax.Array:
    """Cross-attention against a precomputed encoder-memory KV cache."""
    B = x.shape[0]
    H = n_heads or cfg.n_heads
    KV = n_kv or cfg.n_kv_heads
    D = head_dim or cfg.head_dim
    G = H // KV
    q = _split_heads(x @ p["wq"], H, D).reshape(B, 1, KV, G, D)
    k, v = cross_kv["k"], cross_kv["v"]
    scale = cfg.query_scale or D ** -0.5
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v)
    return out.reshape(B, 1, H * D) @ p["wo"]
