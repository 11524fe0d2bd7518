"""ElasticTrainer: JAX-native elastic data-parallel training.

The JAX analogue of Elastic Horovod (paper §4.3): a Trainer can be
rescaled to any node count in [n_min, n_max] at runtime.  Rescale =
host-snapshot params/optimizer state → build a mesh over the new node set
→ re-shard (device_put with new NamedShardings) → re-jit the train step.
No durable-storage round trip.  The measured rescale wall time is exposed
so the MILP can be driven by real ``R^up/R^dw`` values.

The step donates params and optimizer state (``make_train_step``), so the
old and new state are never on the device together: at yi-6b widths with
one layer that is what lets a float32 AdamW step fit one 16 GB chip.

With a live telemetry hub (``repro.obs``) the trainer opens a region
around each part of its host work (DESIGN.md §13): ``trainer.park.copy``;
``trainer.resume`` / ``trainer.reshard`` ⊃ ``trainer.build``,
``trainer.put_state``; ``trainer.step`` ⊃
``trainer.state_wait``, ``trainer.next_batch``, ``trainer.put_batch``,
``trainer.dispatch`` (``trainer.first_dispatch`` at a new node count),
``trainer.loss_wait``.  A rescale to n > 0 only enqueues the state's copy
to the device; with the hub live the next step waits for it first, in
``trainer.state_wait``, so the copy has a span of its own.  With the
null hub nothing waits and each region is one no-op context.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager, Snapshot
from repro.data import DataConfig, TokenPipeline
from repro.models import Model
from repro.obs.telemetry import NULL_TELEMETRY, Telemetry
from repro.optim import AdamW, linear_scaling, warmup_cosine

Pytree = Any


def loss_and_grads(model: Model, mesh: Mesh, params, batch):
    """``model.loss`` and its gradient, traced under ``mesh`` as the context
    mesh, so layers that keep work on each shard's own rows (the dropless
    MoE) can see the data axis."""
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        return jax.value_and_grad(model.loss)(params, batch)


def make_train_step(model: Model, optimizer: AdamW, mesh: Mesh, *,
                    warmup_steps: int, total_steps: int) -> Callable:
    """The jitted data-parallel train step on ``mesh``.

    ``(params, opt_state, batch, lr_scale) -> (params, opt_state, loss)``
    with params and AdamW state replicated and the batch split over
    ``"data"``.  Params and optimizer state are donated: the step updates
    them in place, and the arrays passed in are deleted, so a caller
    keeps only the returned trees."""
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P("data"))

    def step(params, opt_state, batch, lr_scale):
        loss, grads = loss_and_grads(model, mesh, params, batch)
        sched = warmup_cosine(opt_state.step, warmup_steps=warmup_steps,
                              total_steps=total_steps)
        new_params, new_opt = optimizer.update(
            grads, opt_state, params, lr_scale=lr_scale * sched)
        return new_params, new_opt, loss

    return jax.jit(step, in_shardings=(repl, repl, batch_sh, repl),
                   out_shardings=repl, donate_argnums=(0, 1))


@dataclass
class TrainMetrics:
    step: int
    n_nodes: int
    loss: float
    samples: int
    step_time_s: float


class ElasticTrainer:
    """One Trainer: a model + optimizer + data pipeline that can run at any
    node count (devices_per_node devices each) and be rescaled cheaply."""

    def __init__(self, model: Model, *, optimizer: Optional[AdamW] = None,
                 per_node_batch: int = 8, devices_per_node: int = 1,
                 base_lr_nodes: int = 1, seed: int = 0,
                 warmup_steps: int = 20, total_steps: int = 10_000,
                 telemetry=None):
        self.model = model
        self.optimizer = optimizer or AdamW()
        self.per_node_batch = per_node_batch
        self.devices_per_node = devices_per_node
        self.base_lr_nodes = base_lr_nodes
        self.warmup_steps = warmup_steps
        self.total_steps = total_steps
        self.pipeline = TokenPipeline(DataConfig(
            vocab_size=model.cfg.vocab_size, seq_len=256,
            per_node_batch=per_node_batch, seed=seed))

        self.params = model.init(jax.random.key(seed))
        self.opt_state = self.optimizer.init(self.params)
        self.step_count = 0
        self.n_nodes = 0
        self.mesh: Optional[Mesh] = None
        self._jitted: Dict[int, Callable] = {}
        # node counts whose step a live hub has seen dispatched
        self._stepped: set[int] = set()
        # a rescale enqueued the state's copy and no step has waited for it
        self._landing = False
        self.rescale_history: list[tuple[int, int, float]] = []
        # observation sink (repro.obs); LiveBackend hands the loop's hub
        # to a trainer still carrying the null default
        self.telemetry = (telemetry if isinstance(telemetry, Telemetry)
                          else NULL_TELEMETRY)

    # ------------------------------------------------------------------

    def seq_len(self, seq_len: int) -> None:
        self.pipeline.cfg.seq_len = seq_len

    def _build(self, n_nodes: int):
        n_dev = n_nodes * self.devices_per_node
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("data",))
        return mesh, make_train_step(
            self.model, self.optimizer, mesh,
            warmup_steps=self.warmup_steps, total_steps=self.total_steps)

    # ------------------------------------------------------------------

    def rescale(self, n_nodes: int) -> float:
        """Rescale to ``n_nodes`` (0 = waiting).  Returns wall seconds:
        for a park the copy to the host; for a resume or reshard the
        enqueue of the copy to the device, which lands later."""
        t0 = time.perf_counter()
        old = self.n_nodes
        if n_nodes == old:
            return 0.0
        tel = self.telemetry
        if n_nodes == 0:
            # hold state on host; release device mesh
            with tel.region("trainer.park.copy"):
                self.params = Snapshot.take(self.params,
                                            self.step_count).tree
                self.opt_state = Snapshot.take(self.opt_state,
                                               self.step_count).tree
            self.mesh = None
            self.n_nodes = 0
            if tel:
                tel.count("trainer.park_bytes", sum(
                    x.nbytes for x in jax.tree.leaves((self.params,
                                                       self.opt_state))))
            dt = time.perf_counter() - t0
            self.rescale_history.append((old, 0, dt))
            return dt
        n_dev = n_nodes * self.devices_per_node
        if n_dev > len(jax.devices()):
            raise ValueError(
                f"rescale to {n_nodes} nodes needs {n_dev} devices, "
                f"only {len(jax.devices())} available")
        with tel.region("trainer.resume" if old == 0 else "trainer.reshard"):
            if n_nodes not in self._jitted:
                with tel.region("trainer.build"):
                    self._jitted[n_nodes] = self._build(n_nodes)
            self.mesh, _ = self._jitted[n_nodes]
            repl = NamedSharding(self.mesh, P())
            with tel.region("trainer.put_state"):
                self.params = jax.tree.map(
                    lambda x: jax.device_put(x, repl), self.params)
                self.opt_state = jax.tree.map(
                    lambda x: jax.device_put(x, repl), self.opt_state)
            self.n_nodes = n_nodes
            self._landing = True
        dt = time.perf_counter() - t0
        self.rescale_history.append((old, n_nodes, dt))
        return dt

    def train_step(self) -> TrainMetrics:
        assert self.n_nodes > 0, "Trainer is waiting (0 nodes)"
        tel = self.telemetry
        n = self.n_nodes
        mesh, fn = self._jitted[n]
        with tel.region("trainer.step"):
            if tel and self._landing:
                # the rescale only enqueued the state's copy; the step
                # could not start before it lands, so wait for it here,
                # where the wait has a span of its own
                with tel.region("trainer.state_wait"):
                    jax.block_until_ready((self.params, self.opt_state))
            self._landing = False
            with tel.region("trainer.next_batch"):
                batch_np = self.pipeline.next_batch(n)
            with tel.region("trainer.put_batch"):
                batch_sh = NamedSharding(mesh, P("data"))
                batch = {k: jax.device_put(v, batch_sh)
                         for k, v in batch_np.items()}
                lr_scale = jnp.float32(linear_scaling(n, self.base_lr_nodes))
            dispatch = "trainer.dispatch"
            if tel and n not in self._stepped:
                # the first call at a node count traces and compiles
                self._stepped.add(n)
                dispatch = "trainer.first_dispatch"
            t0 = time.perf_counter()
            with tel.region(dispatch):
                self.params, self.opt_state, loss = fn(
                    self.params, self.opt_state, batch, lr_scale)
            with tel.region("trainer.loss_wait"):
                loss = float(loss)
            dt = time.perf_counter() - t0
        self.step_count += 1
        return TrainMetrics(step=self.step_count, n_nodes=n, loss=loss,
                            samples=batch_np["tokens"].shape[0],
                            step_time_s=dt)

    # ------------------------------------------------------------------

    def save_checkpoint(self, manager: CheckpointManager,
                        meta: Optional[Dict] = None) -> str:
        """Write a durable, integrity-checked checkpoint of params +
        optimizer state at the current step.  Returns the npz path."""
        tree = {
            "params": Snapshot.take(self.params).tree,
            "opt_state": Snapshot.take(self.opt_state).tree,
        }
        return manager.save(tree, step=self.step_count, meta=meta)

    def restore_checkpoint(self, manager: CheckpointManager) -> int:
        """Restore from the newest checkpoint that passes verification.

        A corrupt latest checkpoint silently falls back to the previous
        good one (``CheckpointManager.load_latest_good``) — the trainer
        resumes from an older step rather than failing, which is the
        restore-from-last-good semantics the chaos fault model assumes
        (``ChaosBackend.on_fail``).  Returns the restored step count;
        raises ``CorruptCheckpointError`` if no checkpoint survives."""
        like = {"params": self.params, "opt_state": self.opt_state}
        tree, meta, step = manager.load_latest_good(like)
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        if self.n_nodes > 0:
            # re-shard the restored host arrays onto the live mesh
            repl = NamedSharding(self.mesh, P())
            self.params = jax.tree.map(
                lambda x: jax.device_put(x, repl), self.params)
            self.opt_state = jax.tree.map(
                lambda x: jax.device_put(x, repl), self.opt_state)
            self._landing = True
        self.step_count = int(meta.get("step", step))
        return self.step_count

    # ------------------------------------------------------------------

    def measured_rescale_costs(self) -> tuple[float, float]:
        """(r_up, r_dw) estimates from observed rescales.

        A transition to 0 nodes is a *kill/park* (state snapshots to
        host and the device mesh is released), not a scale-down of a
        running mesh — its wall time is dominated by the host transfer
        and would contaminate the ``r_dw`` fed back into the MILP's
        Eqn-16 cost term, so it is excluded from the estimate.
        """
        ups = [dt for a, b, dt in self.rescale_history if b > a > 0]
        dws = [dt for a, b, dt in self.rescale_history if 0 < b < a]
        r_up = float(np.mean(ups)) if ups else 0.5
        r_dw = float(np.mean(dws)) if dws else 0.1
        return r_up, r_dw
