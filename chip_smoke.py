"""On-chip smoke test of BFTrainer's elastic training path.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # rescale across 1, 2 and 4 chips
    python chip_smoke.py --moe-grads   # dropless MoE gradients, all chips

One chip: yi-6b at its published widths, cut to one layer, trains as a
BFTrainer job.  ``BFTrainerRuntime`` replays a seeded Summit-like hole
trace on a one-node pool through ``ControlLoop`` -> ``LiveBackend`` ->
``ElasticTrainer``.  Each hole ends with the node leaving, so the job is
parked on the host (``rescale(0)``) and resumed onto the chip with the
next hole.  The run passes when every loss is finite, the first is
within ``FIRST_LOSS_BAND`` of ln(vocab), the mean of the last three is
below the first, the state checksum taken before each park equals the
one taken after the resume bit for bit, and the first placement on the
chip copied at most one leaf of the state.

``--four-chips``: the same trainer is rescaled 1 -> 2 -> 4 -> 2 -> 1 chips
with two steps at each layout.  Every device of each mesh must hold the
same replicated params, and each step's loss must match ``model.loss`` of
the same global batch on one chip with the same params within
``LOSS_ATOL``.

``--moe-grads``: granite-moe-3b-a800m at its published widths, cut to
four layers, at 2 x 2048 tokens per chip on a data-parallel mesh of every
chip JAX finds.  The loss gradient of the trainer's own path
(``loss_and_grads``) with the dropless MoE and with the dense one, both
at the default precision, is compared leaf by leaf with the dense
gradient at ``HIGHEST``.  Dropless must be no further from it than
``MOE_GRAD_RATIO`` times dense's gap plus ``MOE_GRAD_SLACK`` on every
leaf: both compute the same function with one bfloat16 pass a product.

The numbers of each phase go to earlier lines.  The last line is
``{"ok": true, "device": {...}}`` only when every check passed; on any
failure, and when JAX finds no TPU, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core import (AllocationEngine, amdahl_curve,  # noqa: E402
                        fragments_to_events, generate_summit_like)
from repro.elastic import (BFTrainerRuntime, ElasticTrainer,  # noqa: E402
                           ManagedTrainer)
from repro.elastic.trainer import loss_and_grads  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import AdamW  # noqa: E402

SEED = 0
# Adam moves each weight by about lr a step, so the logits move by about
# lr * d_model a step.  In CPU trials of this model at d_model 2048, lr
# 1e-3 lowered the loss steadily over 16 steps and 3e-3 diverged; 5e-4 at
# d_model 4096 is the same move of the logits as the first.
LR = 5e-4
WARMUP_STEPS = 2
# one-node pool: every hole grants STEPS_PER_HOLE steps, then the node
# leaves and the job parks, so TARGET_STEPS takes four holes, three
# resumes after a park, and a final park when the job finishes
TARGET_STEPS = 16
STEPS_PER_HOLE = 4
TRACE_DAYS = 2.0
# random init gives logits of about unit variance, which puts the first
# loss near ln(V) + 1/2; a band of 1 admits that and nothing far off
FIRST_LOSS_BAND = 1.0
# The sharded step and the one-chip reference compute the same float32
# loss and differ only in reduction order (the 64000-way logsumexp, the
# 4096- and 11008-wide contractions tiled for 8 or 8n rows, the mean
# across devices): about 1e-5 of a loss near 11.  1e-3 leaves room for
# that and is far below the gap between two different batches.
LOSS_ATOL = 1e-3
BYTES_PER_PARAM = 16        # float32 params, AdamW mu and nu, gradient
# --moe-grads: granite's per-chip batch in the benchmark, and the bar for
# dropless.  On one v5e the dense path's leaves sit 0.02-0.08 (relative
# norm) from the HIGHEST gradient and dropless's within 0.0006 of dense's;
# a fault seen once (ragged-dot outputs saved through the rematerialised
# layer scan) put dropless's at 0.15-0.98.
MOE_BATCH = (2, 2048)
MOE_GRAD_RATIO = 1.5
MOE_GRAD_SLACK = 0.005


def moe_config():
    """granite-moe-3b-a800m at its published widths, cut to four layers as
    the benchmark's granite cell is."""
    return dataclasses.replace(get_arch("granite-moe-3b-a800m"), n_layers=4)


def smoke_config():
    """yi-6b at its published widths, cut to one layer: one whole period
    of its one-entry ``layer_pattern``."""
    return dataclasses.replace(get_arch("yi-6b"), n_layers=1)


def require_tpu() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")


def memory_stats(device) -> dict:
    stats = device.memory_stats()
    if stats is None:
        raise SystemExit(f"chip_smoke: {device} reports no memory stats")
    return stats


@jax.jit
def state_checksum(tree):
    """One uint32 per leaf: the sum, mod 2**32, of each element's bits
    times an odd weight set by its position, so any one changed bit
    changes it."""
    def leaf(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
        weight = jnp.arange(bits.size, dtype=jnp.uint32) * 2 + 1
        return jnp.sum(bits * weight, dtype=jnp.uint32)
    return jnp.stack([leaf(x) for x in jax.tree.leaves(tree)])


def shards_on(tree, device):
    """The single-device arrays ``device`` holds of a replicated tree."""
    return jax.tree.map(
        lambda x: next(s.data for s in x.addressable_shards
                       if s.device == device), tree)


def describe_model(model) -> None:
    cfg = model.cfg
    n = model.n_params()
    per_layer = (n - 2 * cfg.vocab_size * cfg.d_model) // cfg.n_layers
    print(f"model {cfg.name}: published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} query / {cfg.n_kv_heads} kv heads of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"untied head); n_layers cut from "
          f"{get_arch(cfg.name).n_layers} to {cfg.n_layers}: {n:,} params. "
          f"Why: one 16 GB chip, and float32 AdamW needs "
          f"{BYTES_PER_PARAM} B/param ({n * BYTES_PER_PARAM / 1e9:.2f} GB "
          f"here); with params and optimizer state donated the step "
          f"compiles to 11.2 GB, and each further layer adds "
          f"{per_layer * BYTES_PER_PARAM / 1e9:.2f} GB before temporaries.",
          flush=True)


class SmokeTrainer(ElasticTrainer):
    """``ElasticTrainer`` that records, for the checks, what the runtime
    did with it: each step's metrics, the state checksum before each park
    and after each resume, and the device memory around its first
    placement on the chip."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.metrics = []
        self.parked = []
        self.resumed = []
        self.placement = None

    def _checksum(self):
        return np.asarray(state_checksum((self.params, self.opt_state)))

    def rescale(self, n_nodes: int) -> float:
        old = self.n_nodes
        if n_nodes == 0 and old > 0:
            self.parked.append(self._checksum())
        first = self.placement is None and n_nodes > 0
        if first:
            before = memory_stats(jax.devices()[0])
        dt = super().rescale(n_nodes)
        if first:
            self.placement = (before, memory_stats(jax.devices()[0]))
        if old == 0 and n_nodes > 0 and self.parked:
            self.resumed.append(self._checksum())
        return dt

    def train_step(self):
        m = super().train_step()
        self.metrics.append(m)
        return m


def one_chip() -> dict:
    cfg = smoke_config()
    model = build_model(cfg)
    describe_model(model)
    trainer = SmokeTrainer(model, optimizer=AdamW(lr=LR), seed=SEED,
                           warmup_steps=WARMUP_STEPS)
    leaves = jax.tree.leaves((trainer.params, trainer.opt_state))
    state_bytes = sum(x.nbytes for x in leaves)
    leaf_bytes = max(x.nbytes for x in leaves)
    print(f"state: {state_bytes:,} B in {len(leaves)} leaves, largest "
          f"{leaf_bytes:,} B; batch {trainer.per_node_batch} x "
          f"{trainer.pipeline.cfg.seq_len} per chip", flush=True)

    events = fragments_to_events(generate_summit_like(
        n_nodes=1, duration=TRACE_DAYS * 86400.0, seed=SEED))
    managed = [ManagedTrainer(id=0, trainer=trainer,
                              curve=amdahl_curve(cfg.name, 100.0, 0.2),
                              n_min=1, n_max=1, target_steps=TARGET_STEPS)]
    rep = BFTrainerRuntime(managed, AllocationEngine(), pj_max=1).run(
        events, max_steps_per_interval=STEPS_PER_HOLE)

    losses = rep.losses[0]
    step_s = [m.step_time_s for m in trainer.metrics]
    hist = trainer.rescale_history
    parks = [dt for a, b, dt in hist if b == 0]
    resumes = [dt for i, (a, b, dt) in enumerate(hist) if a == 0 and i > 0]
    before, after = trainer.placement
    peak = memory_stats(jax.devices()[0])["peak_bytes_in_use"]
    print(f"loop: {rep.events} allocation events, {rep.steps[0]} steps, "
          f"rescales {[(a, b) for a, b, _ in hist]}", flush=True)
    print(f"first step (compile + run): {step_s[0]:.3f} s; "
          f"later steps: {[round(s, 4) for s in step_s[1:]]} s")
    print(f"losses: {[round(x, 4) for x in losses]}")
    print(f"placement 0->1: {hist[0][2]:.3f} s; parks 1->0: "
          f"{[round(s, 3) for s in parks]} s; resumes 0->1: "
          f"{[round(s, 3) for s in resumes]} s")
    print(f"device memory around the first placement: in use "
          f"{before['bytes_in_use']:,} -> {after['bytes_in_use']:,} B, "
          f"peak {before['peak_bytes_in_use']:,} -> "
          f"{after['peak_bytes_in_use']:,} B; peak over the run "
          f"{peak:,} B", flush=True)

    finite = bool(losses) and all(math.isfinite(x) for x in losses)
    return {
        "job ran its steps": rep.steps[0] == TARGET_STEPS,
        "parked and resumed": len(trainer.resumed) >= 1,
        "losses finite": finite,
        "first loss near ln(vocab)": finite and abs(
            losses[0] - math.log(cfg.vocab_size)) < FIRST_LOSS_BAND,
        "loss falls": finite and float(np.mean(losses[-3:])) < losses[0],
        "state unchanged by park and resume": all(
            np.array_equal(p, r)
            for p, r in zip(trainer.parked, trainer.resumed)),
        "first placement copies at most one leaf": after[
            "peak_bytes_in_use"] <= max(before["peak_bytes_in_use"],
                                        before["bytes_in_use"] + leaf_bytes),
    }


def four_chips() -> dict:
    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 chips; JAX "
                         f"found {len(jax.devices())}")
    cfg = smoke_config()
    model = build_model(cfg)
    describe_model(model)
    trainer = ElasticTrainer(model, optimizer=AdamW(lr=LR), seed=SEED,
                             warmup_steps=WARMUP_STEPS)
    dev0 = jax.devices()[0]
    reference = jax.jit(model.loss)
    checks = {}
    for n in (1, 2, 4, 2, 1):
        dt = trainer.rescale(n)
        mesh = set(trainer.mesh.devices.flat)
        placed = all(
            {s.device for s in x.addressable_shards} == mesh
            and all(s.data.shape == x.shape for s in x.addressable_shards)
            for x in jax.tree.leaves(trainer.params))
        sums = [np.asarray(state_checksum(shards_on(trainer.params, d)))
                for d in sorted(mesh, key=lambda d: d.id)]
        same = all(np.array_equal(sums[0], s) for s in sums[1:])
        checks[f"{n} chips: params replicated on every device"] = \
            placed and same
        diffs = []
        for _ in range(2):
            saved = trainer.pipeline.state()
            batch = trainer.pipeline.next_batch(n)
            trainer.pipeline.restore(saved)
            want = float(reference(shards_on(trainer.params, dev0),
                                   jax.device_put(batch, dev0)))
            m = trainer.train_step()
            diffs.append((m.loss, want, abs(m.loss - want)))
        checks[f"{n} chips: loss matches one chip"] = all(
            math.isfinite(got) and d <= LOSS_ATOL for got, _, d in diffs)
        print(f"{n} chips: rescale {dt:.3f} s, devices "
              f"{sorted(d.id for d in mesh)}, replicated {placed and same}; "
              f"(step loss, one-chip loss, |diff|): {diffs}", flush=True)
    return checks


def moe_grads() -> dict:
    cfg = moe_config()
    n = len(jax.devices())
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    rows = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("data"))
    b, s = MOE_BATCH
    rs = np.random.RandomState(SEED)
    batch = jax.device_put(
        {k: rs.randint(0, cfg.vocab_size, (b * n, s)).astype(np.int32)
         for k in ("tokens", "labels")}, rows)

    def grads(strategy, precision="default"):
        model = build_model(cfg, moe_strategy=strategy)
        params = jax.jit(model.init, out_shardings=repl)(
            jax.random.key(SEED))
        f = jax.jit(lambda p, x: loss_and_grads(model, mesh, p, x)[1],
                    in_shardings=(repl, rows), out_shardings=repl)
        with jax.default_matmul_precision(precision):
            g = jax.block_until_ready(f(params, batch))
            walls = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(f(params, batch))
                walls.append(time.perf_counter() - t0)
        return g, sorted(walls)[2] * 1e3

    def gaps(g, ref):
        out = {}
        for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(g),
                                jax.tree.leaves(ref)):
            a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
            out[jax.tree_util.keystr(path)] = float(
                np.linalg.norm(a - r) / np.linalg.norm(r))
        return out

    ref, _ = grads("dense", "highest")
    dense, dense_ms = grads("dense")
    dense_gaps = gaps(dense, ref)
    del dense
    dropless, dropless_ms = grads("dropless")
    dropless_gaps = gaps(dropless, ref)
    print(f"moe grads: {cfg.name} {cfg.n_layers} layers, {n} chips x "
          f"{b} x {s} tokens; gradient wall (median of 5) dense "
          f"{dense_ms:.2f} ms, dropless {dropless_ms:.2f} ms", flush=True)
    print("moe grads: leaf gaps to dense at HIGHEST (dense, dropless): "
          + json.dumps({k: [round(dense_gaps[k], 5), round(v, 5)]
                        for k, v in dropless_gaps.items()}), flush=True)
    return {f"{n} chips: dropless gradient as close as dense's": all(
        v <= MOE_GRAD_RATIO * dense_gaps[k] + MOE_GRAD_SLACK
        for k, v in dropless_gaps.items())}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="rescale across 1, 2 and 4 chips instead of the "
                         "one-chip hole-trace run")
    ap.add_argument("--moe-grads", action="store_true",
                    help="compare granite's dropless MoE gradient with the "
                         "dense one on every chip found")
    args = ap.parse_args(argv)
    require_tpu()
    enable_compile_cache()
    checks = (moe_grads() if args.moe_grads else
              four_chips() if args.four_chips else one_chip())
    for name, ok in checks.items():
        print(f"check {name}: {'pass' if ok else 'FAIL'}")
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SystemExit(f"chip_smoke: failed: {', '.join(failed)}")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
