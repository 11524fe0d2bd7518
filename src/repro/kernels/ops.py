"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the kernels execute (and are
tested) on CPU; on a TPU backend pass ``interpret=False`` (or rely on the
default, which detects the backend) to run the compiled kernels.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ce_loss as _ce
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "logit_cap", "scale",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               logit_cap=logit_cap, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bmat, cmat, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ssd.ssd_scan(x, dt, a, bmat, cmat, chunk=chunk,
                         interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rms_norm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
             interpret: Optional[bool] = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rn.rms_norm(x, scale, eps=eps, block_rows=block_rows,
                        interpret=interpret)


@partial(jax.jit, static_argnames=("block_rows", "block_v", "interpret"))
def ce_loss(x, table, labels, *, block_rows: int = 256, block_v: int = 2048,
            interpret: Optional[bool] = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ce.ce_loss(x, table, labels, block_rows=block_rows,
                       block_v=block_v, interpret=interpret)
