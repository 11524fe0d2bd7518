"""Flash attention Pallas TPU kernels: forward and backward.

``flash_attention`` is differentiable (``jax.custom_vjp``).  Three kernels:

* ``flash_fwd`` — grid ``(B, H, nq, nk)``, the KV blocks innermost and
  sequential: the online-softmax state (m, l, acc) lives in VMEM scratch
  across them.  It writes the output and each row's log-sum-exp.
* ``flash_bwd_dkv`` — grid ``(B, KV, nk, G·nq)``: for one KV block it
  loops over the query blocks of every head in the block's group and
  accumulates dK and dV.  It works on transposed scores (keys × queries),
  so the per-query log-sum-exp and ``D = rowsum(dO ∘ O)`` broadcast as
  rows.
* ``flash_bwd_dq`` — grid ``(B, H, nq, nk)``: accumulates dQ over KV blocks.

Both backward kernels recompute P from q, k and the saved log-sum-exp
(the flash-attention-2 layout); no (S, S) tensor is ever written.

GQA is handled in the BlockSpec index maps (``kv_head = h // group``), so
grouped KV heads are never repeated.  Q and K have head size ``d_qk``, V
and the output ``d_v`` (MLA runs 192 / 128).  A block wholly outside the
causal triangle or the sliding window is not computed, and its index map
is clamped to a block that is computed next to it, so its tiles are not
fetched either; only the blocks that straddle an edge build an element
mask.

Arithmetic: float32 at the boundary and in the softmax state.  On the
chip each dot's operands reach the MXU as bfloat16 with float32
accumulation: one pass, which is what XLA's default precision does with
the same float32 operands.  In interpret mode, as on XLA's CPU backend,
the operands keep their own dtype.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
LANES = 128
# Larger blocks fill the MXU better and cost fewer grid steps; a block
# of 512 x 512 keeps every kernel's working set well inside VMEM.
BLOCKS = (512, 256, 128)
VMEM_LIMIT_BYTES = 64 << 20


def block_size(n: int) -> int:
    """The largest of ``BLOCKS`` that divides ``n`` (ragged lengths are
    padded to a multiple of 128, or run as one block when shorter)."""
    return next((b for b in BLOCKS if n % b == 0), min(n, BLOCKS[-1]))


class _Spec(NamedTuple):
    causal: bool
    window: int
    logit_cap: float
    scale: float
    block_q: int
    block_k: int
    seq_k: int          # keys before padding
    mxu_dtype: jnp.dtype
    interpret: bool


# ---------------------------------------------------------------------------
# Which blocks run
# ---------------------------------------------------------------------------


def _kv_range(sp: _Spec, qi, nk: int):
    """First and last KV block that query block ``qi`` attends to."""
    lo, hi = 0, nk - 1
    if sp.window:
        lo = jnp.maximum(qi * sp.block_q - sp.window + 1, 0) // sp.block_k
    if sp.causal:
        hi = jnp.minimum((qi * sp.block_q + sp.block_q - 1) // sp.block_k,
                         nk - 1)
    return lo, hi


def _q_range(sp: _Spec, ki, nq: int):
    """First and last query block that attends to KV block ``ki``."""
    lo, hi = 0, nq - 1
    if sp.causal:
        lo = (ki * sp.block_k) // sp.block_q
    if sp.window:
        hi = jnp.minimum((ki * sp.block_k + sp.block_k + sp.window - 2)
                         // sp.block_q, nq - 1)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _on_edge(sp: _Spec, qi, ki, padded_k: bool):
    """Whether block (qi, ki) holds masked elements as well as live ones."""
    q0, k0 = qi * sp.block_q, ki * sp.block_k
    edge = False
    if sp.causal:
        edge = edge | (k0 + sp.block_k - 1 > q0)
    if sp.window:
        edge = edge | (q0 + sp.block_q - 1 - k0 >= sp.window)
    if padded_k:
        edge = edge | (k0 + sp.block_k > sp.seq_k)
    return edge


def _live(sp: _Spec, qi, ki, shape, q_axis: int):
    """Element mask of block (qi, ki); ``q_axis`` is the queries' axis."""
    q_pos = qi * sp.block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                       q_axis)
    k_pos = ki * sp.block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                       1 - q_axis)
    ok = k_pos < sp.seq_k
    if sp.causal:
        ok &= k_pos <= q_pos
    if sp.window:
        ok &= k_pos > q_pos - sp.window
    return ok


def _run_block(sp: _Spec, body, run, qi, ki, padded_k: bool):
    """``body(masked)`` where the block runs, with the element mask only
    on blocks that straddle an edge."""
    edge = _on_edge(sp, qi, ki, padded_k)
    if edge is False:
        pl.when(run)(lambda: body(False))
        return
    pl.when(run & edge)(lambda: body(True))
    pl.when(run & jnp.logical_not(edge))(lambda: body(False))


# ---------------------------------------------------------------------------
# Shared arithmetic
# ---------------------------------------------------------------------------


def _dot(a, b, sp: _Spec, contract_b: int = 0):
    """``a @ b`` (``contract_b=0``) or ``a @ b.T`` (``contract_b=1``) with
    operands at ``sp.mxu_dtype`` and float32 accumulation."""
    return jax.lax.dot_general(
        a.astype(sp.mxu_dtype), b.astype(sp.mxu_dtype),
        (((1,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32)


def _logits(a, b, sp: _Spec):
    """Scaled, soft-capped ``a @ b.T`` and the cap's ``tanh`` (or None)."""
    s = _dot(a, b, sp, contract_b=1) * sp.scale
    if not sp.logit_cap:
        return s, None
    t = jnp.tanh(s / sp.logit_cap)
    return sp.logit_cap * t, t


def _col(row):
    """(1, n) -> (n, 1), through a lane-aligned transpose."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _row(col):
    """(n, 1) -> (1, n)."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1, :]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                sp: _Spec, padded_k: bool):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(masked: bool):
        s, _ = _logits(q_ref[0, 0], k_ref[0, 0], sp)          # (bq, bk)
        if masked:
            s = jnp.where(_live(sp, qi, ki, s.shape, 0), s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = corr * l_sc[...] + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = corr * acc_sc[...] + _dot(p, v_ref[0, 0], sp)
        m_sc[...] = m_new

    lo, hi = _kv_range(sp, qi, nk)
    _run_block(sp, body, (ki >= lo) & (ki <= hi), qi, ki, padded_k)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _row(m_sc[...] + jnp.log(l))


def _forward(q, k, v, sp: _Spec):
    b, h, sq, dqk = q.shape
    kv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // kv
    bq, bk = sp.block_q, sp.block_k
    nq, nk = sq // bq, sk // bk

    def kv_map(b_, h_, qi, ki):
        lo, hi = _kv_range(sp, qi, nk)
        return b_, h_ // g, _clamp(ki, lo, hi), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, sp=sp, padded_k=sk > sp.seq_k),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dqk), lambda b_, h_, qi, ki:
                         (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, dqk), kv_map),
            pl.BlockSpec((1, 1, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda b_, h_, qi, ki:
                         (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h_, qi, ki:
                         (b_, h_, 0, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=sp.interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, sp: _Spec, nq: int, padded_k: bool):
    ki, j = pl.program_id(2), pl.program_id(3)
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(masked: bool):
        q, do = q_ref[0, 0], do_ref[0, 0]
        s, t = _logits(k_ref[0, 0], q, sp)                   # (bk, bq)
        if masked:
            s = jnp.where(_live(sp, qi, ki, s.shape, 1), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])
        dv_sc[...] += _dot(p, do, sp)
        ds = p * (_dot(v_ref[0, 0], do, sp, contract_b=1) - di_ref[0, 0])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dk_sc[...] += _dot(ds, q, sp)

    lo, hi = _q_range(sp, ki, nq)
    _run_block(sp, body, (qi >= lo) & (qi <= hi), qi, ki, padded_k)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = (dk_sc[...] * sp.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc,
               lse_sc, di_sc, *, sp: _Spec, padded_k: bool):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        lse_sc[...] = _col(lse_ref[0, 0])
        di_sc[...] = _col(di_ref[0, 0])

    def body(masked: bool):
        k = k_ref[0, 0]
        s, t = _logits(q_ref[0, 0], k, sp)                   # (bq, bk)
        if masked:
            s = jnp.where(_live(sp, qi, ki, s.shape, 0), s, NEG_INF)
        p = jnp.exp(s - lse_sc[...])
        dp = _dot(do_ref[0, 0], v_ref[0, 0], sp, contract_b=1)
        ds = p * (dp - di_sc[...])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_sc[...] += _dot(ds, k, sp)

    lo, hi = _kv_range(sp, qi, nk)
    _run_block(sp, body, (ki >= lo) & (ki <= hi), qi, ki, padded_k)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = (dq_sc[...] * sp.scale).astype(dq_ref.dtype)


def _backward(q, k, v, o, lse, do, sp: _Spec):
    b, h, sq, dqk = q.shape
    kv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // kv
    bq, bk = sp.block_q, sp.block_k
    nq, nk = sq // bq, sk // bk
    padded_k = sk > sp.seq_k
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, :, None, :]                      # (B,H,1,S)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)

    # dK, dV: one KV block, every query block of its group's heads
    def q_map(b_, kh, ki, j):
        lo, hi = _q_range(sp, ki, nq)
        return b_, kh * g + j // nq, _clamp(j % nq, lo, hi), 0

    def row_map(b_, kh, ki, j):
        b_, h_, qi, _ = q_map(b_, kh, ki, j)
        return b_, h_, 0, qi

    def kv_own(b_, kh, ki, j):
        return b_, kh, ki, 0

    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, sp=sp, nq=nq, padded_k=padded_k),
        grid=(b, kv, nk, g * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dqk), q_map),
            pl.BlockSpec((1, 1, bk, dqk), kv_own),
            pl.BlockSpec((1, 1, bk, dv), kv_own),
            pl.BlockSpec((1, 1, bq, dv), q_map),
            pl.BlockSpec((1, 1, 1, bq), row_map),
            pl.BlockSpec((1, 1, 1, bq), row_map),
        ],
        out_specs=[pl.BlockSpec((1, 1, bk, dqk), kv_own),
                   pl.BlockSpec((1, 1, bk, dv), kv_own)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dqk), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=params,
        interpret=sp.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)

    # dQ: one query block, its KV blocks
    def own(b_, h_, qi, ki):
        return b_, h_, qi, 0

    def own_row(b_, h_, qi, ki):
        return b_, h_, 0, qi

    def kv_map(b_, h_, qi, ki):
        lo, hi = _kv_range(sp, qi, nk)
        return b_, h_ // g, _clamp(ki, lo, hi), 0

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sp=sp, padded_k=padded_k),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dqk), own),
            pl.BlockSpec((1, 1, bk, dqk), kv_map),
            pl.BlockSpec((1, 1, bk, dv), kv_map),
            pl.BlockSpec((1, 1, bq, dv), own),
            pl.BlockSpec((1, 1, 1, bq), own_row),
            pl.BlockSpec((1, 1, 1, bq), own_row),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dqk), own),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dqk), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=params,
        interpret=sp.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, di)
    return dq, dk, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, sp: _Spec):
    return _forward(q, k, v, sp)[0]


def _flash_fwd(q, k, v, sp: _Spec):
    o, lse = _forward(q, k, v, sp)
    return o, (q, k, v, o, lse)


def _flash_bwd(sp: _Spec, res, do):
    return _backward(*res, do, sp)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B,H,S,Dqk); k: (B,KV,Sk,Dqk); v: (B,KV,Sk,Dv); H % KV == 0.
    Returns (B,H,S,Dv).  Blocks default to :func:`block_size` of each
    length; lengths off the block are padded and masked."""
    sq, dqk = q.shape[2], q.shape[3]
    sk = k.shape[2]
    block_q = min(block_q or block_size(sq), sq)
    block_k = min(block_k or block_size(sk), sk)
    sp = _Spec(causal=causal, window=window, logit_cap=logit_cap,
               scale=dqk ** -0.5 if scale is None else scale,
               block_q=block_q, block_k=block_k, seq_k=sk,
               mxu_dtype=jnp.dtype(q.dtype if interpret else jnp.bfloat16),
               interpret=interpret)

    pq, pk = (-sq) % block_q, (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    return _flash(q, k, v, sp)[:, :, :sq]
