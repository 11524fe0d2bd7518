"""The readings a cell's limits are set from, over many seeds in one
process (no measured window: the check reads set-up's steps).

    python bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds N] [--out FILE]

For each seed: the program's checked steps, taken as a run's set-up takes
them, and the plain reference's; then the controls, each the reference put
in the program's place with one thing broken:

``control_fp8``   the control: the same steps with float8 matmul operands,
                  one precision below the program's single-bfloat16-pass
                  matmuls;
``control_bf16``  bfloat16 matmuls and activations: one step below float32
                  on paper, but the program's float32 matmuls already run
                  as one bfloat16 pass, so this reads like the program;
``half_batch``    each step's loss over the first half of its rows only.

A step that returns its state unchanged reads a ``change_gap`` of exactly
1 and needs no run.  One JSON line per seed; the last line is the summary:
per number the largest reading of the program and the smallest of each
control.  ``bench/limits/<cell>.json`` is set between the two.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


CONTROLS = ("control_fp8", "control_bf16", "half_batch")


def controls():
    import jax.numpy as jnp
    from bench.reference import lm
    return {"control_fp8": dict(dt=jnp.bfloat16, prec=lm.FP8),
            "control_bf16": dict(dt=jnp.bfloat16, prec=lm.DEFAULT),
            "half_batch": dict(rows_of=lambda tok, n: tok[:len(tok) // 2])}


def read_seed(cell, seed: int, with_controls: bool = True) -> dict:
    import gc
    from bench import check, harness
    from bench.reference import train as reference
    rec = harness.Recorder(harness.RunData(0.0, cell.chips, 0, 0.0, 0.0),
                           memory=lambda: 0)
    model, trainer = harness.build(cell, seed, rec)
    prog = harness.check_steps(cell, seed, model, trainer)
    trainer.params = trainer.opt_state = None
    trainer._jitted.clear()
    del trainer
    gc.collect()
    nodes = cell.traffic["check_nodes"]
    ref = reference.train(cell.config, seed, nodes)
    out = {"seed": seed, "program": check.numbers(prog, ref),
           "reference_losses": ref["losses"],
           "gaps": {"program": gaps(prog, ref)}}
    for name, kw in (controls() if with_controls else {}).items():
        got = reference.train(cell.config, seed, nodes, **kw)
        out[name] = check.numbers(got, ref)
        out["gaps"][name] = gaps(got, ref)
    return out


def gaps(got: dict, ref: dict) -> dict:
    """Per step and per leaf, the gaps behind the compared numbers."""
    from bench import check
    return {"steps": [abs(a - b) for a, b in zip(got["losses"],
                                                 ref["losses"])],
            "grad": {k: abs(got["grad"][k] - v) / v
                     for k, v in ref["grad"].items()},
            "change": {k: abs(got["change"][k] - v) / max(v, 1e-30)
                       for k, v in ref["change"].items()},
            "grad_sample": check.sample_gaps(got["grad_sample"],
                                             ref["grad_sample"],
                                             sorted(ref["grad"]))}


def summary(lines) -> dict:
    names = [k for k in lines[0] if k == "program" or k in CONTROLS]
    out = {}
    for name in names:
        agg = max if name == "program" else min
        read = [line[name] for line in lines if name in line]
        out[name] = {k: agg(r[k] for r in read) for k in read[0]}
    out["change_gap_state_unchanged"] = 1.0
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the controls on the first N seeds only")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    import jax
    from bench import device, harness
    cell = harness.load_cell(args.workload)
    device.require(jax.devices(), cell.chips)
    lines = []
    seeds = [int(s) for s in args.seeds.split(",")]
    n_ctl = len(seeds) if args.control_seeds is None else args.control_seeds
    for i, seed in enumerate(seeds):
        line = read_seed(cell, seed, i < n_ctl)
        lines.append(line)
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    print(json.dumps({"workload": cell.name, "seeds": len(lines),
                      "summary": summary(lines)}), flush=True)


if __name__ == "__main__":
    main()
