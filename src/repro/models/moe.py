"""Mixture-of-Experts MLP with top-k routing, shared experts and a
load-balance auxiliary loss.

The router scores ``cfg.n_routed`` experts, and the layer holds
``moe.n_experts`` of them, ids ``routing.first_held`` onwards
(``cfg.routing``): every routed expert, or one chip's share of an
expert-parallel deployment.  The softmax and its top-k run over all
routed experts; the top-k gates are renormalised to sum to 1 only where
``routing.norm_topk`` (DeepSeek-V2 keeps the softmax's own).  A held
share computes its own experts' part of the output alone: what the other
shares' experts would add is left to them, while the router, the shared
experts and the aux loss are the same on every share.  The aux loss
covers all routed experts, over the batch (Switch: ``coef * E * sum_e
f_e P_e``) or, where ``routing.seq_aux``, per sequence and averaged
(DeepSeek's ``MoEGate``: ``coef * sum_e f_e P_e``, f_e scaled by E / k).

Three execution strategies (``moe_strategy``; see EXPERIMENTS.md §Perf):

* ``dropless`` — the default.  The T·k token→expert assignments are sorted
  by expert, each expert weight runs as one grouped matmul
  (``jax.lax.ragged_dot``) over its contiguous block of rows, and the rows
  are un-sorted and combined with the top-k gates.  No token is dropped,
  shapes are static whatever the routing, and the routed FLOPs are the
  active-expert count.  The expert set is ``w_gate.shape[0]``; on a held
  share, assignments to experts not held are sorted past the last group
  (room for all T·k rows stays, since every assignment may be held) and
  the grouped matmuls leave them out.  Under a context mesh that splits
  the batch over ``"data"`` alone (the elastic trainer's step), each
  shard sorts and multiplies its own rows.
* ``dense``    — every expert processes every token; outputs are combined
  with the (sparse) gate weights.  The same function as ``dropless`` at
  ``E/k`` times the routed FLOPs; the tests' oracle and the decode path
  (one token per sequence, where sorting buys nothing).
* ``capacity`` — classic dispatch/combine einsum formulation with a token
  capacity per expert (drops overflow tokens, so a different function).

Expert weights are sharded over the ``model`` axis on the expert dimension
when ``n_experts % model_shards == 0`` (expert parallelism), otherwise on
the per-expert hidden dimension (tensor parallelism inside each expert —
e.g. granite's 40 experts on a 16-way axis).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig, RoutingConfig
from repro.models.layers import ACTIVATIONS, ParamDef, mlp_apply, mlp_defs


def moe_defs(cfg: ArchConfig, model_shards: int = 1, dtype=jnp.float32) -> dict:
    moe = cfg.moe
    assert moe is not None
    d, e, de = cfg.d_model, moe.n_experts, moe.d_expert
    if e % model_shards == 0:
        w_in_spec = P("model", None, None)       # expert-parallel
        w_out_spec = P("model", None, None)
    else:
        w_in_spec = P(None, None, "model")       # TP inside experts
        w_out_spec = P(None, "model", None)
    defs = {
        "router": ParamDef((d, cfg.n_routed), spec=P(None, None),
                           scale=d ** -0.5, dtype=jnp.float32),  # fp32
        "w_gate": ParamDef((e, d, de), spec=w_in_spec, scale=d ** -0.5,
                           dtype=dtype),
        "w_up": ParamDef((e, d, de), spec=w_in_spec, scale=d ** -0.5,
                         dtype=dtype),
        "w_down": ParamDef((e, de, d), spec=w_out_spec, scale=de ** -0.5,
                           dtype=dtype),
    }
    if moe.n_shared:
        defs["shared"] = mlp_defs(d, de * moe.n_shared, dtype=dtype)
    return defs


def _route(p: dict, x2d: jax.Array, moe: MoEConfig,
           routing: RoutingConfig = RoutingConfig(), n_seq: int = 1):
    """Returns (top_idx (T,k) over every routed expert, top_vals (T,k),
    aux_loss); ``x2d`` holds ``n_seq`` sequences of equal length."""
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    top_vals, top_idx = jax.lax.top_k(probs, moe.top_k)     # (T, k)
    if routing.norm_topk:
        top_vals = top_vals / jnp.maximum(
            top_vals.sum(-1, keepdims=True), 1e-9
        )

    e = probs.shape[-1]
    if routing.seq_aux:
        # DeepSeek's: per sequence, f_e = its assignments to e over S·k/E
        # and P_e its mean probability; coef * sum_e f_e P_e, averaged
        seq_len = x2d.shape[0] // n_seq
        counts = jax.nn.one_hot(top_idx, e).reshape(n_seq, -1, e).sum(1)
        f = counts * (e / (seq_len * moe.top_k))
        p_seq = probs.reshape(n_seq, -1, e).mean(1)
        aux = moe.router_aux_coef * jnp.mean(jnp.sum(f * p_seq, axis=-1))
        return top_idx, top_vals, aux
    # Switch-style load-balance loss: E * sum_e f_e * P_e, f_e the share
    # of tokens with a nonzero gate on e (exact integer counts / T)
    routed = jax.nn.one_hot(top_idx, e) * (top_vals > 0)[..., None]
    frac_tokens = routed.sum((0, 1)) / x2d.shape[0] * (e / moe.top_k)
    frac_probs = probs.mean(0)
    aux = moe.router_aux_coef * e * jnp.sum(frac_tokens * frac_probs)
    return top_idx, top_vals, aux


def _gates(top_idx: jax.Array, top_vals: jax.Array, e: int) -> jax.Array:
    """The (T,E) gate matrix: top_vals at top_idx, zero elsewhere."""
    t = top_idx.shape[0]
    return jnp.zeros((t, e), top_vals.dtype).at[
        jnp.arange(t)[:, None], top_idx
    ].set(top_vals)


def _experts_dense(p: dict, x2d: jax.Array, gates: jax.Array,
                   activation: str) -> jax.Array:
    act = ACTIVATIONS[activation]
    # (T,d) x (E,d,de) -> (E,T,de); combine with gates -> (T,d)
    h = act(jnp.einsum("td,edf->etf", x2d, p["w_gate"]),
            jnp.einsum("td,edf->etf", x2d, p["w_up"]))
    y = jnp.einsum("etf,efd->etd", h, p["w_down"])
    return jnp.einsum("etd,te->td", y, gates.astype(y.dtype))


def _experts_capacity(p: dict, x2d: jax.Array, gates: jax.Array,
                      moe: MoEConfig, activation: str,
                      group_size: int = 512) -> jax.Array:
    """Dispatch/combine einsum with per-expert capacity (overflow dropped).

    Tokens are processed in groups of ``group_size`` with a per-group
    capacity ``C = g·k/E·cf`` (the t5x/MaxText formulation): the dispatch
    tensor is (G, g, E, C), i.e. O(T·g·k·cf) elements instead of the
    O(T²·k·cf) a global-capacity formulation would need.  Groups inherit
    the token (data) sharding, experts the expert sharding.
    """
    act = ACTIVATIONS[activation]
    t, d = x2d.shape
    e = moe.n_experts
    g = min(group_size, t)
    pad = (-t) % g
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        gates = jnp.pad(gates, ((0, pad), (0, 0)))
    n_groups = x2d.shape[0] // g
    xg = x2d.reshape(n_groups, g, d)
    gg = gates.reshape(n_groups, g, e)
    cap = int(max(1, round(g * moe.top_k / e * moe.capacity_factor)))

    sel = gg > 0                                             # (G,g,E)
    pos = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1      # slot in expert
    keep = sel & (pos < cap)
    disp = (keep[..., None]
            & (pos[..., None] == jnp.arange(cap)[None, None, None, :]))
    disp_f = disp.astype(x2d.dtype)                          # (G,g,E,C)
    xe = jnp.einsum("gsec,gsd->gecd", disp_f, xg)            # (G,E,C,d)
    h = act(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"]),
            jnp.einsum("gecd,edf->gecf", xe, p["w_up"]))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])        # (G,E,C,d)
    comb = disp_f * gg.astype(x2d.dtype)[..., None]          # (G,g,E,C)
    y = jnp.einsum("gsec,gecd->gsd", comb, ye)
    return y.reshape(n_groups * g, d)[:t]


def _grouped(rows: jax.Array, w: jax.Array, group_sizes: jax.Array):
    """(N,a) rows, sorted into groups of ``group_sizes``, times their
    group's (a,b) slice of ``w`` (G,a,b): one ragged dot, float32 out."""
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x: jax.Array, order: jax.Array, inv: jax.Array,
              k: int) -> jax.Array:
    """Rows of ``x`` (T,d) repeated for each of their ``k`` assignments and
    put in ``order`` (T·k,), whose inverse permutation is ``inv``.  Backward
    gathers with ``inv`` and sums each token's ``k`` rows: no scatter-add."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], inv


def _dispatch_bwd(k, inv, g):
    return g[inv].reshape(-1, k, g.shape[-1]).sum(1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _down_combine(h: jax.Array, w_down: jax.Array, group_sizes: jax.Array,
                  top_vals: jax.Array, order: jax.Array, inv: jax.Array,
                  rows: Optional[jax.Array] = None) -> jax.Array:
    """The down projection of the sorted rows ``h`` (T·k,f), then the
    inverse of :func:`_dispatch`, weighted: ``y[t] = sum_j top_vals[t, j]
    · ys[inv[t·k + j]]``.  Backward gets the gates' gradient from ``h``
    and the down projection's input gradient, so it needs neither the
    expert outputs nor a token-major copy of them.  ``rows`` (a held
    share) marks the rows inside the groups; the others are zero both
    ways."""
    t, k = top_vals.shape
    ys = _grouped(h, w_down, group_sizes)
    if rows is not None:
        ys = jnp.where(rows[:, None], ys, 0.0)
    return jnp.sum(ys[inv].reshape(t, k, -1) * top_vals[..., None], axis=1)


def _down_combine_fwd(h, w_down, group_sizes, top_vals, order, inv,
                      rows=None):
    return (_down_combine(h, w_down, group_sizes, top_vals, order, inv,
                          rows),
            (h, w_down, group_sizes, top_vals, order, inv, rows))


def _down_combine_bwd(res, g):
    h, w_down, group_sizes, top_vals, order, inv, rows = res
    gs = g[order // top_vals.shape[1]]                       # (T·k, d)
    vals = top_vals.reshape(-1)[order][:, None]              # (T·k, 1)
    u = _grouped(gs, jnp.swapaxes(w_down, 1, 2), group_sizes)
    cot = gs * vals
    if rows is not None:
        u = jnp.where(rows[:, None], u, 0.0)
        cot = jnp.where(rows[:, None], cot, 0.0)
    d_vals = jnp.sum(u * h, axis=-1)[inv].reshape(top_vals.shape)
    _, w_vjp = jax.vjp(lambda w: _grouped(h, w, group_sizes), w_down)
    (d_w,) = w_vjp(cot)
    return u * vals, d_w, None, d_vals, None, None, None


_down_combine.defvjp(_down_combine_fwd, _down_combine_bwd)


def _experts_dropless(p: dict, x2d: jax.Array, top_idx: jax.Array,
                      top_vals: jax.Array, activation: str,
                      first_held: Optional[int] = None) -> jax.Array:
    """Each token through its own top-k experts only: the T·k assignments
    sorted by expert, one grouped matmul per weight, un-sorted and
    combined.  With ``first_held`` (a held share) only the assignments to
    experts ``first_held .. first_held + E - 1`` join the groups; the
    others sort after them, and their rows, outside every group, are held
    at zero.  Returns y (T,d) in ``x2d``'s dtype."""
    act = ACTIVATIONS[activation]
    k = top_idx.shape[1]
    e = p["w_gate"].shape[0]
    flat = top_idx.reshape(-1)                               # (T·k,)
    if first_held is not None:
        with jax.named_scope("moe/held"):
            flat = flat - first_held
            flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    with jax.named_scope("moe/dispatch"):
        order = jnp.argsort(flat, stable=True)               # sorted by expert
        inv = jnp.argsort(order)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(e, dtype=flat.dtype), axis=0,
            dtype=jnp.int32)                                 # (E,)
        xs = _dispatch(x2d, order, inv, k)                   # (T·k, d)
    rows = None
    if first_held is not None:
        # XLA's ragged dot on the TPU leaves the rows past the last group
        # undefined, forward and in their gradient: keep them zero
        with jax.named_scope("moe/held"):
            rows = jnp.arange(flat.shape[0]) < jnp.sum(group_sizes)
            xs = jnp.where(rows[:, None], xs, 0.0)
    with jax.named_scope("moe/experts"):
        gate = _grouped(xs, p["w_gate"], group_sizes)        # (T·k, f)
        up = _grouped(xs, p["w_up"], group_sizes)
        if rows is not None:
            gate = jnp.where(rows[:, None], gate, 0.0)
            up = jnp.where(rows[:, None], up, 0.0)
        h = act(gate, up)
    with jax.named_scope("moe/combine"):
        y = _down_combine(h, p["w_down"], group_sizes, top_vals, order, inv,
                          rows)
    return y.astype(x2d.dtype)


def _data_mesh():
    """The context mesh when it splits the batch over ``"data"`` and over
    no other axis (the elastic trainer's data-parallel step), else None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("data", 1) == 1:
        return None
    return mesh if mesh.size == mesh.shape["data"] else None


def _experts_dropless_sharded(p: dict, x: jax.Array, top_idx: jax.Array,
                              top_vals: jax.Array, activation: str,
                              first_held: Optional[int] = None) -> jax.Array:
    """:func:`_experts_dropless` on (B,S,·) inputs.  Under a data-parallel
    context mesh each shard sorts, gathers and multiplies its own rows
    (``shard_map``): a sort over the whole sharded T·k axis would be
    replicated, and every chip would run the global batch's expert work.
    The replicated weights' gradients are summed over ``"data"``."""
    b, s, d = x.shape
    k = top_idx.shape[-1]
    experts = {n: p[n] for n in ("w_gate", "w_up", "w_down")}

    def local(w, x, top_idx, top_vals):
        bl = x.shape[0]
        return _experts_dropless(
            w, x.reshape(bl * s, d), top_idx.reshape(bl * s, k),
            top_vals.reshape(bl * s, k), activation,
            first_held).reshape(bl, s, d)

    mesh = _data_mesh()
    if mesh is not None:
        local = jax.shard_map(local, mesh=mesh,
                              in_specs=(P(), P("data"), P("data"),
                                        P("data")),
                              out_specs=P("data"))
    return local(experts, x, top_idx.reshape(b, s, k),
                 top_vals.reshape(b, s, k))


def moe_apply(p: dict, x: jax.Array, cfg: ArchConfig, *,
              strategy: str = "dropless") -> tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y, aux_loss)."""
    moe = cfg.moe
    assert moe is not None
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    with jax.named_scope("moe/route"):
        top_idx, top_vals, aux = _route(p, x2d, moe, cfg.routing, b)
    # a held share: the router is wider than the experts held (static, so
    # a layer that holds every expert traces as before)
    share = cfg.n_routed > moe.n_experts
    first = cfg.routing.first_held if share else None

    def gates():
        g = _gates(top_idx, top_vals, cfg.n_routed)
        return g[:, first:first + moe.n_experts] if share else g

    if strategy == "dropless":
        y = _experts_dropless_sharded(p, x, top_idx, top_vals,
                                      cfg.mlp_activation,
                                      first).reshape(b * s, d)
    elif strategy == "dense":
        y = _experts_dense(p, x2d, gates(), cfg.mlp_activation)
    elif strategy == "capacity":
        y = _experts_capacity(p, x2d, gates(), moe, cfg.mlp_activation)
    else:
        raise ValueError(f"unknown moe strategy {strategy!r}")
    if moe.n_shared:
        y = y + mlp_apply(p["shared"], x2d, cfg.mlp_activation)
    return y.reshape(b, s, d), aux
