"""Benchmark dispatcher: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [section ...] [--json [DIR]]``
prints ``name,value,derived`` CSV rows.  Set BENCH_FULL=1 for the
paper-scale variants, BENCH_SMOKE=1 (or ``--smoke``) for CI-scale runs.

``--json [DIR]`` additionally persists the perf-trajectory payloads
(``BENCH_week.json`` from the ``week`` section, ``BENCH_allocator.json``
from ``scale``, ``BENCH_chaos.json`` from ``chaos``,
``BENCH_objectives.json`` from ``objectives``,
``BENCH_scalability.json`` from ``scalability``,
``BENCH_serving.json`` from ``serving``,
``BENCH_resilience.json`` from ``resilience``) into DIR (default:
the current directory), validated
against ``benchmarks.schema`` — the artifacts CI uploads per commit
and ``scripts/bench_compare.py`` diffs against the committed baselines
in ``benchmarks/baselines/``.
"""
from __future__ import annotations

import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

SECTIONS = [
    ("milp", "Fig 5: MILP solve time", "benchmarks.bench_milp"),
    ("engine", "Allocation engine portfolio vs per-event MILP (week trace)",
     "benchmarks.bench_engine"),
    ("scale", "Scale sweep: incremental engine vs fresh solve, to 4096 nodes",
     "benchmarks.bench_scale"),
    ("tfwd", "Figs 7-9: forward-looking time", "benchmarks.bench_tfwd"),
    ("week", "Figs 10-11: weekly efficiency engine/MILP vs heuristic",
     "benchmarks.bench_week"),
    ("workloads", "Scenario library: engine efficiency per workload profile",
     "benchmarks.bench_workloads"),
    ("objectives", "Figs 12-13 + Tabs 3-4 + policy portfolio: "
     "throughput-vs-fairness across scenarios",
     "benchmarks.bench_objectives"),
    ("runtime", "Live ControlLoop: real elastic trainers on a replayed trace",
     "benchmarks.bench_runtime"),
    ("chaos", "Chaos resilience: efficiency retention under injected faults",
     "benchmarks.bench_chaos"),
    ("serving", "Elastic serving: SLO attainment on harvested holes vs "
     "dedicated nodes", "benchmarks.bench_serving"),
    ("resilience", "Self-healing control plane: stream corruption repair + "
     "decision-deadline ladder", "benchmarks.bench_resilience"),
    ("pjmax", "Fig 14: max parallel Trainers", "benchmarks.bench_pjmax"),
    ("scalability", "Fig 15: per-DNN scalability", "benchmarks.bench_scalability"),
    ("rescale_cost", "Fig 16: rescale-cost sweep", "benchmarks.bench_rescale_cost"),
    ("throughput", "Tab 2 analog: model-zoo throughput", "benchmarks.bench_throughput"),
    ("kernels", "Pallas kernel micro-bench", "benchmarks.bench_kernels"),
]


def _parse_args(argv):
    want, i = set(), 0
    while i < len(argv):
        a = argv[i]
        if a == "--json":
            nxt = argv[i + 1] if i + 1 < len(argv) else None
            if nxt is not None and not nxt.startswith("-") and \
                    nxt not in {k for k, _, _ in SECTIONS}:
                os.environ["BENCH_JSON_DIR"] = nxt
                i += 1
            else:
                os.environ.setdefault("BENCH_JSON_DIR", ".")
        elif a == "--smoke":
            os.environ["BENCH_SMOKE"] = "1"
        else:
            want.add(a)
        i += 1
    return want


def main() -> None:
    want = _parse_args(sys.argv[1:])
    enable_compile_cache()
    t_start = time.time()
    for key, desc, mod_name in SECTIONS:
        if want and key not in want:
            continue
        print(f"# === {key}: {desc} ===", flush=True)
        t0 = time.time()
        mod = __import__(mod_name, fromlist=["main"])
        mod.main()
        print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
    print(f"# all benchmarks done in {time.time()-t_start:.1f}s")


if __name__ == "__main__":
    main()
