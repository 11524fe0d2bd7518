"""End-to-end driver: REAL elastic JAX training under BFTrainer control.

Two Trainers (reduced gemma-2b and mamba2 architectures) are trained with
genuine train steps while the AllocationEngine (memoized greedy/MILP
portfolio, DESIGN.md §3) rescales them across a replayed idle-node trace.
The runtime is the same ControlLoop the simulator uses (DESIGN.md §9), so
the live path is policy-complete.  Demonstrates:
  * state carry across rescale (no restart, no durable checkpoint),
  * per-node fixed minibatch => global batch tracks the allocation,
  * measured (not assumed) R_up / R_dw fed back into the MILP,
  * FCFS admission under pj_max, event coalescing, and rescale/preemption
    stall accounting — live, not just simulated.

Run:  PYTHONPATH=src python examples/elastic_train.py [--steps 200]
"""
import argparse

import numpy as np

from repro.configs import get_arch
from repro.core import AllocationEngine, amdahl_curve, fragments_to_events, \
    generate_summit_like
from repro.elastic import BFTrainerRuntime, ElasticTrainer, ManagedTrainer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import AdamW


def make_trainer(arch: str, seed: int, seq: int = 128) -> ElasticTrainer:
    cfg = get_arch(arch).reduced()
    model = build_model(cfg, remat=False)
    tr = ElasticTrainer(model, per_node_batch=4, seed=seed,
                        optimizer=AdamW(lr=1e-3), warmup_steps=10)
    tr.pipeline.cfg.seq_len = seq
    return tr


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60,
                    help="target train steps per Trainer")
    ap.add_argument("--hours", type=float, default=48.0)
    args = ap.parse_args()
    enable_compile_cache()

    frags = generate_summit_like(n_nodes=6, duration=args.hours * 3600,
                                 seed=13)
    events = fragments_to_events(frags)
    print(f"trace: {len(events)} events over {args.hours:.0f}h")

    managed = [
        ManagedTrainer(id=0, trainer=make_trainer("gemma-2b", 1),
                       curve=amdahl_curve("gemma-2b", 100.0, 0.2),
                       n_min=1, n_max=1, target_steps=args.steps),
        ManagedTrainer(id=1, trainer=make_trainer("mamba2-2.7b", 2),
                       curve=amdahl_curve("mamba2", 120.0, 0.15),
                       n_min=1, n_max=1, target_steps=args.steps),
    ]
    engine = AllocationEngine()
    rt = BFTrainerRuntime(managed, engine, t_fwd=120.0, pj_max=2,
                          coalesce_window=30.0)
    rep = rt.run(events, time_scale=1.0, max_steps_per_interval=8)

    st = engine.stats
    print(f"\nallocation events: {rep.events} "
          f"(solver {rep.solver_wall_s:.2f}s), wall {rep.wall_time_s:.1f}s")
    ls = rep.stats
    print(f"policy (shared ControlLoop): rescale stalls {ls.rescale_cost_s:.1f}s, "
          f"preemption {ls.preempt_cost_s:.1f}s of trace time, "
          f"{ls.unfinished} unfinished")
    print(f"engine: {st.cache_hits}/{st.events} cache hits, "
          f"{st.greedy_solves} greedy + {st.fast_milp_solves} fast-MILP "
          f"solves, {st.fallbacks} fallbacks")
    for m in managed:
        losses = rep.losses[m.id]
        r_up, r_dw = m.trainer.measured_rescale_costs()
        first = np.mean(losses[:5]) if len(losses) >= 5 else float("nan")
        last = np.mean(losses[-5:]) if len(losses) >= 5 else float("nan")
        print(f"trainer {m.id} ({m.trainer.model.cfg.name}): "
              f"{rep.steps[m.id]} steps, {rep.samples[m.id]} samples, "
              f"{rep.rescales[m.id]} rescales "
              f"(measured r_up={r_up*1e3:.0f}ms r_dw={r_dw*1e3:.0f}ms), "
              f"loss {first:.3f} -> {last:.3f}")


if __name__ == "__main__":
    main()
