"""Model assembly: decoder LM (dense / MoE / SSM / hybrid patterns),
encoder-decoder, scan-over-blocks, losses, prefill and decode.

The layer stack is organized as ``n_blocks`` repetitions of
``cfg.layer_pattern``; parameters for each pattern position are stacked
with a leading ``n_blocks`` axis and the whole stack runs under one
``lax.scan`` (keeps HLO size O(pattern) instead of O(n_layers) — critical
for compiling 46–80-layer configs on 512 devices).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape, LayerSpec
from repro.models import attention as attn
from repro.models import mamba2, mla, moe as moe_mod
from repro.models.layers import (
    ParamDef,
    dense_def,
    mlp_apply,
    mlp_defs,
    rms_norm,
    softcap,
)

Pytree = Any


def _norm(d_model: int) -> ParamDef:
    return ParamDef((d_model,), spec=P(), init="zeros", dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Per-layer defs
# ---------------------------------------------------------------------------


def block_defs(cfg: ArchConfig, spec: LayerSpec, model_shards: int,
               dtype) -> dict:
    d: dict = {}
    if spec.mixer in ("attn", "swa"):
        d["mixer_norm"] = _norm(cfg.d_model)
        if cfg.mla is not None:
            d["mixer"] = mla.mla_defs(cfg, model_shards, dtype)
        else:
            d["mixer"] = attn.attn_defs(cfg, model_shards, dtype=dtype)
    elif spec.mixer == "mamba":
        d["mixer_norm"] = _norm(cfg.d_model)
        d["mixer"] = mamba2.mamba_defs(cfg, model_shards, dtype)
    else:
        raise ValueError(spec.mixer)
    if cfg.post_norms:
        d["mixer_post_norm"] = _norm(cfg.d_model)

    if cfg.is_encdec:
        d["cross_norm"] = _norm(cfg.d_model)
        d["cross"] = attn.attn_defs(cfg, model_shards, dtype=dtype)

    if spec.mlp == "dense":
        d["mlp_norm"] = _norm(cfg.d_model)
        d["mlp"] = mlp_defs(cfg.d_model, cfg.dense_d_ff or cfg.d_ff,
                            dtype=dtype)
    elif spec.mlp == "moe":
        d["mlp_norm"] = _norm(cfg.d_model)
        d["mlp"] = moe_mod.moe_defs(cfg, model_shards, dtype)
    elif spec.mlp != "none":
        raise ValueError(spec.mlp)
    if cfg.post_norms and spec.mlp != "none":
        d["mlp_post_norm"] = _norm(cfg.d_model)
    return d


# ---------------------------------------------------------------------------
# Per-layer apply (full sequence)
# ---------------------------------------------------------------------------


def apply_block(cfg: ArchConfig, spec: LayerSpec, p: dict, x: jax.Array, *,
                memory: Optional[jax.Array] = None,
                moe_strategy: str = "dropless",
                long_serving: bool = False) -> tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    if spec.mixer == "mamba":
        out = mamba2.mamba_apply(p["mixer"], h, cfg)
    else:
        window = cfg.sliding_window if spec.mixer == "swa" else 0
        if long_serving and cfg.sliding_window:
            window = cfg.sliding_window  # bounded-KV long-context mode
        if cfg.mla is not None:
            out = mla.mla_apply(p["mixer"], h, cfg)
        else:
            out = attn.attn_apply(p["mixer"], h, cfg=cfg, causal=True,
                                  window=window)
    if cfg.post_norms:
        out = rms_norm(out, p["mixer_post_norm"], cfg.norm_eps)
    x = x + out

    if cfg.is_encdec and memory is not None:
        h = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        out = attn.attn_apply(p["cross"], h, cfg=cfg, causal=False, window=0,
                              memory=memory, use_rope=False)
        x = x + out

    if spec.mlp != "none":
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        if spec.mlp == "moe":
            out, a = moe_mod.moe_apply(p["mlp"], h, cfg,
                                       strategy=moe_strategy)
            aux = aux + a
        else:
            out = mlp_apply(p["mlp"], h, cfg.mlp_activation)
        if cfg.post_norms:
            out = rms_norm(out, p["mlp_post_norm"], cfg.norm_eps)
        x = x + out
    return x, aux


# ---------------------------------------------------------------------------
# Per-layer decode apply
# ---------------------------------------------------------------------------


def _uses_ring(cfg: ArchConfig, spec: LayerSpec, long_serving: bool) -> bool:
    """Bounded (ring-buffer) KV: SWA layers always; all attention layers in
    long-context serving mode (jamba / gemma2 — see DESIGN.md)."""
    return bool(cfg.sliding_window) and (spec.mixer == "swa" or long_serving)


def init_block_cache(cfg: ArchConfig, spec: LayerSpec, batch: int,
                     cache_len: int, n_frames: int = 0,
                     long_serving: bool = False,
                     dtype=jnp.bfloat16) -> dict:
    c: dict = {}
    if spec.mixer == "mamba":
        c["mamba"] = mamba2.init_mamba_cache(cfg, batch, dtype)
    elif cfg.mla is not None:
        c["mla"] = mla.init_mla_cache(cfg, batch, cache_len, dtype)
    else:
        w = cfg.sliding_window if _uses_ring(cfg, spec, long_serving) \
            else cache_len
        c["kv"] = attn.init_kv_cache(batch, min(w, cache_len),
                                     cfg.n_kv_heads, cfg.head_dim, dtype)
    if cfg.is_encdec:
        c["cross"] = attn.init_kv_cache(batch, n_frames, cfg.n_kv_heads,
                                        cfg.head_dim, dtype)
    return c


def block_cache_specs(cfg: ArchConfig, spec: LayerSpec, batch_axes,
                      seq_axes) -> dict:
    c: dict = {}
    if spec.mixer == "mamba":
        c["mamba"] = mamba2.mamba_cache_specs(batch_axes)
    elif cfg.mla is not None:
        c["mla"] = mla.mla_cache_specs(batch_axes, seq_axes)
    else:
        c["kv"] = attn.kv_cache_specs(batch_axes, seq_axes)
    if cfg.is_encdec:
        c["cross"] = attn.kv_cache_specs(batch_axes, None)
    return c


def apply_block_decode(cfg: ArchConfig, spec: LayerSpec, p: dict,
                       x: jax.Array, cache: dict, pos: jax.Array,
                       *, long_serving: bool = False) -> tuple[jax.Array, dict]:
    new_cache = dict(cache)
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    if spec.mixer == "mamba":
        out, new_cache["mamba"] = mamba2.mamba_decode(p["mixer"], h, cache["mamba"], cfg)
    elif cfg.mla is not None:
        out, new_cache["mla"] = mla.mla_decode(p["mixer"], h, cache["mla"], pos, cfg)
    else:
        ring = _uses_ring(cfg, spec, long_serving)
        out, new_cache["kv"] = attn.attn_decode(
            p["mixer"], h, cache["kv"], pos, cfg=cfg,
            window=cfg.sliding_window if ring else 0)
    if cfg.post_norms:
        out = rms_norm(out, p["mixer_post_norm"], cfg.norm_eps)
    x = x + out

    if cfg.is_encdec:
        h = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        out = attn.cross_attn_decode(p["cross"], h, cache["cross"], cfg=cfg)
        x = x + out

    if spec.mlp != "none":
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        if spec.mlp == "moe":
            out, _ = moe_mod.moe_apply(p["mlp"], h, cfg, strategy="dense")
        else:
            out = mlp_apply(p["mlp"], h, cfg.mlp_activation)
        if cfg.post_norms:
            out = rms_norm(out, p["mlp_post_norm"], cfg.norm_eps)
        x = x + out
    return x, new_cache
