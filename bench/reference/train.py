"""The reference's first training steps, and what the check reads of them.

``train`` starts from the weights the seed gives (``init`` of the
configuration's reference module, ``bench.reference.module_for``), takes one
AdamW step per entry of ``node_counts`` on the next ``n * per_node_batch``
rows of the stream, and returns the numbers the check compares:

* ``losses``: each step's loss;
* ``grad``: per leaf, the norm of the first step's gradient as the
  optimizer gets it (after clipping);
* ``grad_sample``: per leaf, that gradient at the elements
  ``bench.check.sample_indices`` draws for the seed;
* ``change``: per leaf, the norm of the parameters' change over all the
  steps.

``rows_of(tokens, n)`` plants a fault in the rows a step sees (the
controls of ``bench/readings.py`` and the tests use it); ``dt``/``prec``
choose the arithmetic (``lm`` describes both).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.reference import data, lm, module_for, optim


def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_step(cfg: Dict, dt=jnp.float32, prec=lm.HIGHEST):
    """The jitted step ``(params, mu, nu, step, tokens, n_nodes, idx) ->
    (params, mu, nu, loss, clipped gradient norms, clipped gradient at
    the flat indices idx)``; params and moments are donated."""
    a, t = cfg["arch"], cfg["train"]
    ref = module_for(cfg)

    def one(params, mu, nu, step, tokens, n_nodes, idx):
        value, grads = jax.value_and_grad(
            lambda p: ref.loss(a, p, tokens, dt=dt, prec=prec))(params)
        params, mu, nu, clipped = optim.update(t, grads, params, mu, nu,
                                               step, n_nodes)
        sample = {k: clipped[k][jnp.unravel_index(i, clipped[k].shape)]
                  for k, i in idx.items()}
        return params, mu, nu, value, leaf_norms(clipped), sample

    return jax.jit(one, donate_argnums=(0, 1, 2))


def train(cfg: Dict, seed: int, node_counts: Sequence[int], *,
          dt=jnp.float32, prec=lm.HIGHEST,
          rows_of: Optional[Callable] = None) -> Dict:
    a, t = cfg["arch"], cfg["train"]
    params = module_for(cfg).init(a, seed)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    idx = check.sample_indices(seed, {k: v.shape for k, v in params.items()})
    step_fn = make_step(cfg, dt, prec)
    losses, grad, sample, start = [], None, None, 0
    for i, n in enumerate(node_counts):
        count = n * t["per_node_batch"]
        tokens = data.rows(seed, a["vocab_size"], t["seq_len"], start, count)
        start += count
        if rows_of is not None:
            tokens = rows_of(tokens, n)
        params, mu, nu, value, gn, gs = step_fn(
            params, mu, nu, jnp.int32(i), jnp.asarray(tokens),
            jnp.float32(n), idx)
        losses.append(float(value))
        if grad is None:
            grad = {k: float(v) for k, v in gn.items()}
            sample = {k: np.asarray(v) for k, v in gs.items()}
    del mu, nu
    change = change_norms(cfg, seed, params)
    return {"losses": losses, "grad": grad, "grad_sample": sample,
            "change": change}


def change_norms(cfg: Dict, seed: int, params: Dict) -> Dict[str, float]:
    """Per leaf, ``|params - init(seed)|``."""
    start = module_for(cfg).init(cfg["arch"], seed)
    out = jax.jit(lambda p, s: leaf_norms(
        {k: p[k] - s[k] for k in p}))(params, start)
    return {k: float(v) for k, v in out.items()}
