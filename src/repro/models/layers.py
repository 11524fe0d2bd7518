"""Parameter definitions and primitive layers shared by the model zoo.

Design: every module is described by a pytree of :class:`ParamDef` leaves
(shape, dtype, init scale, PartitionSpec).  From one defs tree we derive

* concrete parameters  (``materialize``),
* abstract parameters for ``jax.eval_shape``/dry-run (``abstract``),
* the sharding tree for pjit (``pspecs``).

This guarantees params / specs never drift apart structurally.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: P = P()
    dtype: Any = jnp.float32
    init: str = "normal"        # normal | zeros | ones
    scale: float = 0.02

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def materialize(defs: Pytree, rng: jax.Array, dtype=None) -> Pytree:
    """Instantiate a defs tree into concrete parameters.

    Each leaf gets an independent key derived from its tree path, so
    adding/removing parameters does not reshuffle others.
    """

    def make(path, d: ParamDef):
        leaf_dtype = dtype if dtype is not None else d.dtype
        if d.init == "zeros":
            return jnp.zeros(d.shape, leaf_dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, leaf_dtype)
        # crc32, not hash(): Python str hashes are randomized per process,
        # which would make init non-reproducible across runs
        key = jax.random.fold_in(
            rng, zlib.crc32(jax.tree_util.keystr(path).encode()))
        return (jax.random.normal(key, d.shape, jnp.float32) * d.scale).astype(
            leaf_dtype
        )

    return jax.tree_util.tree_map_with_path(make, defs, is_leaf=_is_def)


def abstract(defs: Pytree, dtype=None) -> Pytree:
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype or d.dtype),
        defs,
        is_leaf=_is_def,
    )


def pspecs(defs: Pytree) -> Pytree:
    return jax.tree.map(lambda d: d.spec, defs, is_leaf=_is_def)


def stack_defs(defs: Pytree, n: int) -> Pytree:
    """Prepend a layer-stack dimension (for scan-over-blocks)."""

    def f(d: ParamDef) -> ParamDef:
        return dataclasses.replace(
            d, shape=(n, *d.shape), spec=P(None, *d.spec)
        )

    return jax.tree.map(f, defs, is_leaf=_is_def)


# ---------------------------------------------------------------------------
# Primitive ops
# ---------------------------------------------------------------------------


# When False, only the variance statistic is computed in fp32 and the
# normalize/gain multiplies stay in the residual dtype — keeps backward
# cotangents (and hence TP partial-sum all-reduces) in bf16.  Toggled by
# the dry-run perf variants (EXPERIMENTS.md §Perf).
NORM_MULT_FP32 = True


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    if NORM_MULT_FP32:
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps).astype(dtype)
    return x * r * (1.0 + scale).astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def geglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.gelu(gate, approximate=True) * up


ACTIVATIONS = {"swiglu": swiglu, "geglu": geglu}


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """Inverse frequencies of the rotary pairs; with ``yarn`` (a
    ``YarnConfig``) DeepSeek-V2's blend: frequencies past the ramp divided
    by ``factor``, those before it kept, linear over pairs ``low..high``."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if yarn is None:
        return freqs

    def dim(rotations):     # the pair that turns this often in the window
        return head_dim * math.log(yarn.original_max_position_embeddings / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(qk_dim: int, yarn=None) -> float:
    """The attention logits' scale: ``qk_dim ** -0.5``, times ``m ** 2``
    with ``m = yarn_mscale(factor, mscale_all_dim)`` under YaRN."""
    scale = qk_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  Under
    ``yarn`` cos and sin are scaled by ``mscale(mscale) / mscale(all_dim)``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, yarn)                 # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    ang = ang[..., None, :]                            # (..., S, 1, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / MLP defs
# ---------------------------------------------------------------------------


def dense_def(d_in: int, d_out: int, spec: P, scale: Optional[float] = None,
              dtype=jnp.float32) -> ParamDef:
    if scale is None:
        scale = d_in ** -0.5
    return ParamDef((d_in, d_out), spec=spec, scale=scale, dtype=dtype)


def mlp_defs(d_model: int, d_ff: int, dtype=jnp.float32) -> dict:
    """Gated MLP (SwiGLU / GeGLU): hidden sharded over the model axis."""
    return {
        "w_gate": dense_def(d_model, d_ff, P(None, "model"), dtype=dtype),
        "w_up": dense_def(d_model, d_ff, P(None, "model"), dtype=dtype),
        "w_down": dense_def(d_ff, d_model, P("model", None), dtype=dtype),
    }


def mlp_apply(p: dict, x: jax.Array, activation: str) -> jax.Array:
    act = ACTIVATIONS[activation]
    h = act(x @ p["w_gate"], x @ p["w_up"])
    return h @ p["w_down"]
