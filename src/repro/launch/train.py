"""Training launcher: train any assigned architecture on local devices.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b-smoke \
        --steps 100 --per-node-batch 4 --seq 256 [--nodes 1] [--elastic]

``--elastic`` replays a Summit-calibrated idle-node trace and lets the
MILP allocator rescale the Trainer live (the full BFTrainer loop);
otherwise it is a plain fixed-size run.  Full-size architectures are for
the dry-run (``repro.launch.dryrun``); this entry point expects ``-smoke``
variants (or small customs) that fit local devices.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_arch
from repro.elastic import ElasticTrainer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import AdamW


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--per-node-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="drive node count from a replayed idle-node trace "
                         "via the MILP allocator")
    ap.add_argument("--checkpoint", default="",
                    help="path to save the final params/opt state")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if cfg.n_layers > 16 and not args.arch.endswith("-smoke"):
        print(f"note: {args.arch} is a full-size config; consider "
              f"{args.arch}-smoke for local training")
    model = build_model(cfg, remat=False)
    trainer = ElasticTrainer(model, optimizer=AdamW(lr=args.lr),
                             per_node_batch=args.per_node_batch,
                             seed=args.seed, total_steps=args.steps)
    trainer.pipeline.cfg.seq_len = args.seq
    print(f"arch={cfg.name} params={model.n_params():,} "
          f"devices={len(jax.devices())}")

    if args.elastic:
        from repro.core import MILPAllocator, amdahl_curve, \
            fragments_to_events, generate_summit_like
        from repro.elastic import BFTrainerRuntime, ManagedTrainer
        frags = generate_summit_like(n_nodes=max(4, args.nodes * 4),
                                     duration=48 * 3600.0, seed=args.seed)
        managed = [ManagedTrainer(
            id=0, trainer=trainer, curve=amdahl_curve(cfg.name, 100.0, 0.2),
            n_min=1, n_max=args.nodes, target_steps=args.steps)]
        rep = BFTrainerRuntime(managed, MILPAllocator("fast")).run(
            fragments_to_events(frags), max_steps_per_interval=8)
        losses = rep.losses[0]
        print(f"elastic run: {rep.steps[0]} steps over {rep.events} "
              f"allocation events, {rep.rescales[0]} rescales, "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
              if losses else "no steps ran (trace had no usable fragments)")
    else:
        trainer.rescale(args.nodes)
        t0 = time.perf_counter()
        for i in range(args.steps):
            m = trainer.train_step()
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                print(f"step {m.step:4d} nodes={m.n_nodes} "
                      f"loss={m.loss:.4f} ({m.step_time_s*1e3:.0f} ms)")
        dt = time.perf_counter() - t0
        print(f"{args.steps} steps in {dt:.1f}s "
              f"({args.steps * args.per_node_batch * args.nodes / dt:.1f} "
              f"samples/s)")

    if args.checkpoint:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.checkpoint, trainer.params,
                        meta={"step": trainer.step_count, "arch": cfg.name})
        print("saved", args.checkpoint)


if __name__ == "__main__":
    main()
