"""Top-level Model wrapper: parameter defs, init, loss, prefill, decode and
``input_specs`` for every assigned architecture.

One class covers all 10 architectures; behaviour is driven entirely by the
``ArchConfig`` (layer pattern, MoE/MLA/SSM sub-configs, enc-dec, frontend
stubs).
"""
from __future__ import annotations

import math
from functools import cached_property, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba2, transformer as T
from repro.models import moe as moe_mod
from repro.models.layers import ParamDef, rms_norm, softcap

Pytree = Any


# Perf knob (EXPERIMENTS.md §Perf): when False, the (B,S,V) logits are
# never materialized in fp32 — max/exp stay in the logits dtype and only
# the vocab reduction accumulates in fp32.  Halves the byte traffic of the
# loss head at a small numerics cost.
CE_UPCAST = True


def _cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    if CE_UPCAST:
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return (logz - gold).mean()
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m                                  # logits dtype
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1, dtype=jnp.float32)
    logz = jnp.log(sumexp) + m[..., 0].astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold.astype(jnp.float32)).mean()


class Model:
    def __init__(self, cfg: ArchConfig, *, model_shards: int = 1,
                 dtype=jnp.float32, moe_strategy: str = "dropless",
                 remat: bool = True, long_serving: bool = False,
                 scan_unroll=1):
        self.cfg = cfg
        self.model_shards = model_shards
        self.dtype = dtype
        self.moe_strategy = moe_strategy
        self.remat = remat
        self.long_serving = long_serving
        # scan_unroll=True fully unrolls the layer stack; the dry-run uses
        # this so XLA cost_analysis counts every block (a while-loop body is
        # costed once regardless of trip count)
        self.scan_unroll = scan_unroll

    # ------------------------------------------------------------------
    # Parameter definitions
    # ------------------------------------------------------------------

    @cached_property
    def defs(self) -> Pytree:
        cfg, dtype, shards = self.cfg, self.dtype, self.model_shards
        d: dict = {
            "embed": ParamDef((cfg.vocab_size, cfg.d_model),
                              spec=P("model", None),
                              scale=cfg.d_model ** -0.5, dtype=dtype),
            "final_norm": T._norm(cfg.d_model),
            "blocks": tuple(
                L.stack_defs(T.block_defs(cfg, spec, shards, dtype),
                             cfg.n_blocks)
                for spec in cfg.layer_pattern
            ),
        }
        if not cfg.tie_embeddings:
            d["unembed"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                    spec=P("model", None),
                                    scale=cfg.d_model ** -0.5, dtype=dtype)
        if cfg.is_encdec:
            enc = cfg.encoder
            enc_layer = {
                "attn_norm": T._norm(enc.d_model),
                "attn": attn.attn_defs(cfg, shards, d_model=enc.d_model,
                                       n_heads=enc.n_heads,
                                       n_kv=enc.n_kv_heads,
                                       head_dim=enc.head_dim, dtype=dtype),
                "mlp_norm": T._norm(enc.d_model),
                "mlp": L.mlp_defs(enc.d_model, enc.d_ff, dtype=dtype),
            }
            d["encoder"] = {
                "layers": L.stack_defs(enc_layer, enc.n_layers),
                "final_norm": T._norm(enc.d_model),
            }
        return d

    def init(self, rng: jax.Array) -> Pytree:
        return L.materialize(self.defs, rng)

    def abstract_params(self) -> Pytree:
        return L.abstract(self.defs)

    def pspecs(self) -> Pytree:
        return L.pspecs(self.defs)

    def n_params(self) -> int:
        return sum(math.prod(d.shape) for d in jax.tree.leaves(
            self.defs, is_leaf=lambda x: isinstance(x, ParamDef)))

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE counts top_k + shared experts)."""
        cfg = self.cfg
        if cfg.moe is None:
            return self.n_params()
        total = 0
        for d in jax.tree.leaves(self.defs,
                                 is_leaf=lambda x: isinstance(x, ParamDef)):
            total += math.prod(d.shape)
        # subtract inactive routed experts
        moe = cfg.moe
        n_moe_layers = sum(s.mlp == "moe" for s in self.cfg.layer_specs())
        per_expert = 3 * cfg.d_model * moe.d_expert
        inactive = n_moe_layers * (moe.n_experts - moe.top_k) * per_expert
        return total - inactive

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------

    def _embed(self, params, batch: dict) -> jax.Array:
        cfg = self.cfg
        x = params["embed"][batch["tokens"]]
        if cfg.scale_embeddings:
            x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)
        if cfg.frontend == "vision" and "frontend_embeds" in batch:
            x = jnp.concatenate(
                [batch["frontend_embeds"].astype(x.dtype), x], axis=1)
        return x

    def _logits(self, params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("bsd,vd->bsv", x, table)
        if not CE_UPCAST and not cfg.final_logit_softcap:
            return logits            # keep bf16; CE accumulates in fp32
        return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)

    # ------------------------------------------------------------------
    # Encoder (enc-dec models)
    # ------------------------------------------------------------------

    def _encode(self, params, frames: jax.Array) -> jax.Array:
        cfg, enc = self.cfg, self.cfg.encoder

        def body(x, p):
            h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
            x = x + attn.attn_apply(p["attn"], h, cfg=cfg, causal=False,
                                    window=0, n_heads=enc.n_heads,
                                    n_kv=enc.n_kv_heads,
                                    head_dim=enc.head_dim)
            h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
            x = x + L.mlp_apply(p["mlp"], h, cfg.mlp_activation)
            return x, None

        if self.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, frames.astype(self.dtype),
                            params["encoder"]["layers"],
                            unroll=self.scan_unroll)
        return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    # ------------------------------------------------------------------
    # Forward (train / prefill)
    # ------------------------------------------------------------------

    def forward(self, params, batch: dict) -> tuple[jax.Array, jax.Array]:
        """Returns (logits, aux_loss)."""
        cfg = self.cfg
        memory = None
        if cfg.is_encdec:
            memory = self._encode(params, batch["frames"])
        x = self._embed(params, batch)

        def one_layer(spec):
            def f(p, x):
                return T.apply_block(cfg, spec, p, x, memory=memory,
                                     moe_strategy=self.moe_strategy,
                                     long_serving=self.long_serving)
            # long patterns (deepseek: 27 layers in one scan block) must be
            # checkpointed per layer, or backward keeps the whole block's
            # activations live at once
            if self.remat and len(cfg.layer_pattern) > 4:
                f = jax.checkpoint(f)
            return f

        layer_fns = [one_layer(spec) for spec in cfg.layer_pattern]

        def body(carry, p_blocks):
            x, aux = carry
            for i in range(len(cfg.layer_pattern)):
                x, a = layer_fns[i](p_blocks[i], x)
                aux = aux + a
            return (x, aux), None

        if self.remat and len(cfg.layer_pattern) <= 4:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"], unroll=self.scan_unroll)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self._logits(params, x), aux

    def loss(self, params, batch: dict) -> jax.Array:
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        if self.cfg.frontend == "vision":
            # frontend positions carry no next-token loss
            logits = logits[:, -labels.shape[1]:]
        return _cross_entropy(logits[:, :-1], labels[:, 1:]) + aux

    # ------------------------------------------------------------------
    # Serving: cache init + one-token decode
    # ------------------------------------------------------------------

    def init_cache(self, batch: int, cache_len: int, n_frames: int = 0,
                   dtype=jnp.bfloat16) -> Pytree:
        cfg = self.cfg

        def one(spec):
            c = T.init_block_cache(cfg, spec, batch, cache_len,
                                   n_frames=n_frames,
                                   long_serving=self.long_serving,
                                   dtype=dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_blocks, *a.shape)), c)

        return tuple(one(spec) for spec in cfg.layer_pattern)

    def cache_specs(self, batch_axes, seq_axes) -> Pytree:
        cfg = self.cfg

        def one(spec):
            c = T.block_cache_specs(cfg, spec, batch_axes, seq_axes)
            return jax.tree.map(lambda s: P(None, *s), c,
                                is_leaf=lambda s: isinstance(s, P))

        return tuple(one(spec) for spec in cfg.layer_pattern)

    def decode_step(self, params, cache: Pytree, tokens: jax.Array,
                    pos: jax.Array) -> tuple[jax.Array, Pytree]:
        """tokens: (B,1) int32; pos: scalar int32 (absolute position)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.scale_embeddings:
            x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)

        def body(x, xs):
            p_blocks, c_blocks = xs
            new_c = []
            for i, spec in enumerate(cfg.layer_pattern):
                x, nc = T.apply_block_decode(cfg, spec, p_blocks[i], x,
                                             c_blocks[i], pos,
                                             long_serving=self.long_serving)
                new_c.append(nc)
            return x, tuple(new_c)

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache),
                                    unroll=self.scan_unroll)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self._logits(params, x), new_cache

    # ------------------------------------------------------------------
    # Prefill: full forward that also fills the decode cache
    # ------------------------------------------------------------------

    def prefill(self, params, batch: dict,
                cache_len: Optional[int] = None) -> tuple[jax.Array, Pytree]:
        """Returns (last-position logits, cache filled through S-1)."""
        cfg = self.cfg
        memory = None
        if cfg.is_encdec:
            memory = self._encode(params, batch["frames"])
        x = self._embed(params, batch)
        s = x.shape[1]
        cache_len = cache_len or s

        def body(x, p_blocks):
            caches = []
            for i, spec in enumerate(cfg.layer_pattern):
                h = rms_norm(x, p_blocks[i]["mixer_norm"], cfg.norm_eps)
                c: dict = {}
                if spec.mixer == "mamba":
                    out, c["mamba"] = _mamba_prefill(cfg, p_blocks[i]["mixer"], h)
                elif cfg.mla is not None:
                    out, c["mla"] = _mla_prefill(cfg, p_blocks[i]["mixer"], h,
                                                 cache_len)
                else:
                    ring = T._uses_ring(cfg, spec, self.long_serving)
                    window = cfg.sliding_window if (
                        spec.mixer == "swa" or (self.long_serving and
                                                cfg.sliding_window)) else 0
                    out, c["kv"] = _attn_prefill(
                        cfg, p_blocks[i]["mixer"], h, window=window,
                        ring=ring, cache_len=cache_len)
                if cfg.post_norms:
                    out = rms_norm(out, p_blocks[i]["mixer_post_norm"],
                                   cfg.norm_eps)
                x = x + out
                if cfg.is_encdec:
                    hh = rms_norm(x, p_blocks[i]["cross_norm"], cfg.norm_eps)
                    x = x + attn.attn_apply(
                        p_blocks[i]["cross"], hh, cfg=cfg, causal=False,
                        window=0, memory=memory, use_rope=False)
                    c["cross"] = _cross_kv(cfg, p_blocks[i]["cross"], memory)
                if spec.mlp != "none":
                    hh = rms_norm(x, p_blocks[i]["mlp_norm"], cfg.norm_eps)
                    if spec.mlp == "moe":
                        out, _ = moe_mod.moe_apply(p_blocks[i]["mlp"], hh, cfg,
                                                   strategy=self.moe_strategy)
                    else:
                        out = L.mlp_apply(p_blocks[i]["mlp"], hh,
                                          cfg.mlp_activation)
                    if cfg.post_norms:
                        out = rms_norm(out, p_blocks[i]["mlp_post_norm"],
                                       cfg.norm_eps)
                    x = x + out
                caches.append(c)
            return x, tuple(caches)

        x, cache = jax.lax.scan(body, x, params["blocks"],
                                unroll=self.scan_unroll)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x[:, -1:])
        return logits, cache

    # ------------------------------------------------------------------
    # Input specs (ShapeDtypeStruct stand-ins; no allocation)
    # ------------------------------------------------------------------

    def input_specs(self, shape: InputShape) -> dict:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct
        if shape.kind == "train":
            batch: dict = {}
            if cfg.frontend == "vision":
                nt = cfg.n_frontend_tokens
                batch["tokens"] = tok((b, s - nt), jnp.int32)
                batch["labels"] = tok((b, s - nt), jnp.int32)
                batch["frontend_embeds"] = tok((b, nt, cfg.d_model),
                                               jnp.bfloat16)
            elif cfg.is_encdec:
                batch["tokens"] = tok((b, s), jnp.int32)
                batch["labels"] = tok((b, s), jnp.int32)
                batch["frames"] = tok((b, s // 4, cfg.encoder.d_model),
                                      jnp.bfloat16)
            else:
                batch["tokens"] = tok((b, s), jnp.int32)
                batch["labels"] = tok((b, s), jnp.int32)
            return batch
        if shape.kind == "prefill":
            batch = {"tokens": tok((b, s), jnp.int32)}
            if cfg.frontend == "vision":
                nt = cfg.n_frontend_tokens
                batch["tokens"] = tok((b, s - nt), jnp.int32)
                batch["frontend_embeds"] = tok((b, nt, cfg.d_model),
                                               jnp.bfloat16)
            elif cfg.is_encdec:
                batch["frames"] = tok((b, s // 4, cfg.encoder.d_model),
                                      jnp.bfloat16)
            return batch
        # decode: one new token against a cache of length s
        abstract_cache = jax.eval_shape(
            lambda: self.init_cache(b, s, n_frames=s // 4 if cfg.is_encdec
                                    else 0))
        return {
            "tokens": tok((b, 1), jnp.int32),
            "pos": tok((), jnp.int32),
            "cache": abstract_cache,
        }


# ---------------------------------------------------------------------------
# Prefill helpers (forward pass that also emits the decode cache)
# ---------------------------------------------------------------------------


def _ring_fill(full: jax.Array, window: int) -> jax.Array:
    """(B,S,...) -> (B,W,...): slot i holds the latest position t with
    t % W == i (gather formulation; no duplicate-scatter ambiguity)."""
    s = full.shape[1]
    w = window
    if s <= w:
        pad = [(0, 0), (0, w - s)] + [(0, 0)] * (full.ndim - 2)
        return jnp.pad(full, pad)
    i = jnp.arange(w)
    t = (s - 1) - ((s - 1 - i) % w)
    return jnp.take(full, t, axis=1)


def _attn_prefill(cfg: ArchConfig, p: dict, h: jax.Array, *, window: int,
                  ring: bool, cache_len: int):
    b, s, _ = h.shape
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    out = attn.attn_apply(p, h, cfg=cfg, causal=True, window=window)
    k = (h @ p["wk"]).reshape(b, s, kv, hd)
    v = (h @ p["wv"]).reshape(b, s, kv, hd)
    k = L.apply_rope(k, jnp.arange(s), cfg.rope_theta)
    if ring:
        k = _ring_fill(k, cfg.sliding_window)
        v = _ring_fill(v, cfg.sliding_window)
    elif s < cache_len:
        k = jnp.pad(k, ((0, 0), (0, cache_len - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, cache_len - s), (0, 0), (0, 0)))
    c = {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
    return out, c


def _mla_prefill(cfg: ArchConfig, p: dict, h: jax.Array, cache_len: int):
    from repro.models import mla as mla_mod
    b, s, _ = h.shape
    out = mla_mod.mla_apply(p, h, cfg)
    c_kv = rms_norm(h @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope((h @ p["w_kr"])[:, :, None, :], jnp.arange(s),
                          cfg.rope_theta, cfg.yarn)[:, :, 0, :]
    if s < cache_len:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, cache_len - s), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, cache_len - s), (0, 0)))
    return out, {"c_kv": c_kv.astype(jnp.bfloat16),
                 "k_rope": k_rope.astype(jnp.bfloat16)}


def _mamba_prefill(cfg: ArchConfig, p: dict, h: jax.Array):
    ssm = cfg.ssm
    d_inner, n_heads, _ = mamba2.mamba_dims(cfg)
    b, s, _ = h.shape
    z = h @ p["wz"]
    x_pre = h @ p["wx"]
    b_pre = h @ p["wb"]
    c_pre = h @ p["wc"]
    x = jax.nn.silu(mamba2._causal_conv(x_pre, p["conv_x"]))
    bmat = jax.nn.silu(mamba2._causal_conv(b_pre, p["conv_b"]))
    cmat = jax.nn.silu(mamba2._causal_conv(c_pre, p["conv_c"]))
    dt = jax.nn.softplus((h @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xh = x.reshape(b, s, n_heads, ssm.head_dim)
    bh = mamba2._broadcast_groups(bmat, cfg, n_heads)
    ch = mamba2._broadcast_groups(cmat, cfg, n_heads)
    y, final_state = mamba2.ssd_chunked(xh, dt, a, bh, ch,
                                        chunk=ssm.chunk_size)
    y = y + xh * p["d_skip"][:, None].astype(y.dtype)
    y = y.reshape(b, s, d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["wo"]
    k = ssm.d_conv - 1
    cache = {
        "conv_x": _last_k(x_pre, k).astype(jnp.bfloat16),
        "conv_b": _last_k(b_pre, k).astype(jnp.bfloat16),
        "conv_c": _last_k(c_pre, k).astype(jnp.bfloat16),
        "state": final_state,
    }
    return out, cache


def _last_k(x: jax.Array, k: int) -> jax.Array:
    s = x.shape[1]
    if s >= k:
        return x[:, s - k:]
    return jnp.pad(x, ((0, 0), (k - s, 0), (0, 0)))


def _cross_kv(cfg: ArchConfig, p: dict, memory: jax.Array) -> dict:
    b, f, _ = memory.shape
    k = (memory @ p["wk"]).reshape(b, f, cfg.n_kv_heads, cfg.head_dim)
    v = (memory @ p["wv"]).reshape(b, f, cfg.n_kv_heads, cfg.head_dim)
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


def build_model(cfg: ArchConfig, **kw) -> Model:
    return Model(cfg, **kw)
