"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Grid ``(B, H, nc)`` with the chunk dimension innermost and sequential: the
inter-chunk recurrent state (P × N) lives in VMEM scratch and is carried
across chunk iterations.  Within a chunk the computation is the quadratic
SSD form (decay-masked C·Bᵀ) — MXU matmuls over (L × N) and (L × L) tiles
— which is exactly how the paper's GPU algorithm adapts to the TPU memory
hierarchy: chunk tiles in VMEM, long-range state as a tiny carried
accumulator instead of a warp-level scan.

The kernel works on a head-major layout, ``(B, H, S, ·)``, so every block's
last two dims are ``(chunk, P)`` / ``(chunk, N)`` — the TPU tiling the
compiler requires — and ``dt`` arrives as a lane-dense ``(1, chunk)`` row.
Its column form and the within-chunk cumulative sums are masked lane or
sublane reductions (no in-kernel transpose or scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (L, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)      # (1, L)
    a = a_ref[pl.program_id(1)]                    # scalar, from SMEM
    bm = b_ref[0, 0].astype(jnp.float32)           # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)           # (L, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da_row = dt_row * a                            # (1, L), <= 0
    dt_col = jnp.sum(jnp.where(li == si, dt_row, 0.0), axis=1,
                     keepdims=True)                # (L, 1)
    cum_col = jnp.sum(jnp.where(si <= li, da_row, 0.0), axis=1,
                      keepdims=True)               # (L, 1): cum[l]
    cum_row = jnp.sum(jnp.where(li == si, cum_col, 0.0), axis=0,
                      keepdims=True)               # (1, L): cum[s]
    total = jnp.sum(da_row, axis=1, keepdims=True)  # (1, 1): cum[L-1]

    # intra-chunk: decay-masked (C Bᵀ) against dt-weighted x
    decay = jnp.where(si <= li, jnp.exp(cum_col - cum_row), 0.0)  # (L, L)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    w = cb * decay * dt_row
    y = jax.lax.dot(w, x, preferred_element_type=jnp.float32)     # (L, P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                         # (P, N)
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (L, N)x(P, N) -> (L, P)

    # state update: decay-to-end weighted outer products
    xw = x * (dt_col * jnp.exp(total - cum_col))   # (L, P)
    new_contrib = jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (P, N)
    state_ref[...] = state * jnp.exp(total) + new_contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
             cmat: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """Chunked SSD. x: (B,S,H,P); dt: (B,S,H); a: (H,); bmat/cmat:
    (B,S,H,N).  S must be a multiple of ``chunk`` (pad upstream).
    Returns y: (B,S,H,P)."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} not a multiple of chunk {chunk}")
    nc = s // chunk

    heads_major = lambda t: jnp.swapaxes(t, 1, 2)       # (B,H,S,·)
    dt_rows = jnp.swapaxes(dt, 1, 2)[:, :, None, :]     # (B,H,1,S)
    seq_block = lambda last: pl.BlockSpec(
        (1, 1, chunk, last), lambda b_, h_, ci: (b_, h_, ci, 0))

    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b, h, nc),
        in_specs=[
            seq_block(p),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda b_, h_, ci: (b_, h_, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            seq_block(n),
            seq_block(n),
        ],
        out_specs=seq_block(p),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(heads_major(x), dt_rows, a.astype(jnp.float32), heads_major(bmat),
      heads_major(cmat))
    return heads_major(y)
