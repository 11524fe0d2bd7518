"""Fused cross-entropy Pallas TPU kernel.

Motivated by §Perf: the loss head materializes (B·S, V) logits in fp32
(e.g. 256 GB/step for yi-6b train_4k).  This kernel streams the vocab
dimension through VMEM with an online logsumexp (the flash-attention
pattern applied to the loss): grid ``(rows, nv)`` with the vocab-block
dimension innermost and sequential; running (m, l, gold) state in VMEM
scratch; the (rows, V) logits tile never round-trips to HBM in fp32.

Inputs are the hidden states and the (vocab-sharded-friendly) embedding
table, so the kernel also fuses the final projection matmul.

Labels and losses travel as ``(T, 1)`` columns: a 1-D ``(rows,)`` block
does not match the TPU's tiled layout of a 1-D int32 operand, and the
v5e compiler refuses it.  Row and vocab tiles are sized by
``tiling.tile_rows``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tile_rows

NEG_INF = -2.0 ** 30


def _kernel(x_ref, w_ref, lbl_ref, loss_ref, m_ref, l_ref, gold_ref, *,
            block_v: int, vocab: int):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        gold_ref[...] = jnp.zeros_like(gold_ref)

    x = x_ref[...].astype(jnp.float32)              # (rows, d)
    w = w_ref[...].astype(jnp.float32)              # (block_v, d)
    logits = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    v_pos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(v_pos < vocab, logits, NEG_INF)

    m_prev = m_ref[...]                             # (rows, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.exp(logits - m_new).sum(
        axis=-1, keepdims=True)
    m_ref[...] = m_new

    # gold logit for labels that fall in this vocab block
    hit = (v_pos == lbl_ref[...])                   # (rows, 1) labels
    gold_ref[...] += jnp.where(hit, logits, 0.0).sum(axis=-1, keepdims=True)

    @pl.when(vi == nv - 1)
    def _finish():
        logz = jnp.log(jnp.maximum(l_ref[...], 1e-30)) + m_ref[...]
        loss_ref[...] = logz - gold_ref[...]


def ce_loss(x: jax.Array, table: jax.Array, labels: jax.Array, *,
            block_rows: int = 256, block_v: int = 2048,
            interpret: bool = False) -> jax.Array:
    """Per-token cross-entropy. x: (T, d); table: (V, d); labels: (T,).
    Returns (T,) fp32 losses (mean-reduce outside)."""
    t, d = x.shape
    v = table.shape[0]
    block_rows = min(tile_rows(block_rows, d * x.dtype.itemsize), t)
    block_v = min(tile_rows(block_v, d * table.dtype.itemsize), v)
    labels = labels.reshape(t, 1)
    pr = (-t) % block_rows
    pv = (-v) % block_v
    if pr:
        x = jnp.pad(x, ((0, pr), (0, 0)))
        labels = jnp.pad(labels, ((0, pr), (0, 0)))
    if pv:
        table = jnp.pad(table, ((0, pv), (0, 0)))
    nr = x.shape[0] // block_rows
    nv = table.shape[0] // block_v

    out = pl.pallas_call(
        functools.partial(_kernel, block_v=block_v, vocab=v),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r, vi: (r, 0)),
            pl.BlockSpec((block_v, d), lambda r, vi: (vi, 0)),
            pl.BlockSpec((block_rows, 1), lambda r, vi: (r, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda r, vi: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_rows, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, table, labels)
    return out[:t, 0]
