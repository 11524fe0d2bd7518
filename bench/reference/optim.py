"""Plain AdamW, as the configuration's ``train`` block states it.

Per step ``t`` (counting from 1): the gradient is clipped to global norm
``grad_clip``; ``mu = b1 mu + (1 - b1) g``, ``nu = b2 nu + (1 - b2) g^2``;
the update is ``(mu / (1 - b1^t)) / (sqrt(nu / (1 - b2^t)) + eps)`` plus
``weight_decay * p``, applied to every leaf, times the learning rate.  The
learning rate is ``lr * n_nodes`` (linear scaling of the per-node batch)
times a schedule of the step count before the update: linear warm-up over
``warmup_steps`` from 0, then a cosine from 1 to 0.1 at ``total_steps``.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def schedule(step: jax.Array, t: Dict) -> jax.Array:
    s = step.astype(jnp.float32)
    warm, total = t["warmup_steps"], t["total_steps"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(math.pi * prog))
    return jnp.where(s < warm, s / max(warm, 1), cos)


def update(t: Dict, grads, params, mu, nu, step, n_nodes):
    """One AdamW step over dicts of leaves.  Returns
    ``(params, mu, nu, clipped_grads)``."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    clip = jnp.minimum(1.0, t["grad_clip"] / (gnorm + 1e-9))
    grads = {k: g * clip for k, g in grads.items()}
    b1, b2 = t["b1"], t["b2"]
    count = (step + 1).astype(jnp.float32)
    lr = t["lr"] * n_nodes * schedule(step, t)
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    new_p, new_mu, new_nu = {}, {}, {}
    for k, g in grads.items():
        m = b1 * mu[k] + (1 - b1) * g
        v = b2 * nu[k] + (1 - b2) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + t["eps"]) \
            + t["weight_decay"] * params[k]
        new_p[k], new_mu[k], new_nu[k] = params[k] - lr * u, m, v
    return new_p, new_mu, new_nu, grads
