"""Fused RMSNorm Pallas TPU kernel: one pass over rows, fp32 statistics,
(1 + scale) gain — fuses what XLA would otherwise emit as several HBM
round-trips for large d_model.  The row tile is sized by
``tiling.tile_rows``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tile_rows


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)             # (rows, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))).astype(
        o_ref.dtype)


def rms_norm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
             block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (..., d); scale: (d,)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(tile_rows(block_rows, d * x.dtype.itemsize), rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    nr = x2.shape[0] // block_rows

    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
            pl.BlockSpec((d,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, scale)
    return out[:rows].reshape(orig_shape)
