"""The measured runs leave the program's telemetry hub off: the harness
gives the loop a sink that is not a live hub, so ``LiveBackend`` hands
nothing on and the trainer keeps the null hub, with no region, clock read
or wait for a resume's copy added to what is measured."""
import time

import bench_tiny
from bench import harness
from repro.obs import NULL_TELEMETRY

SEED = 2**31 + 41


def test_untraced_run_leaves_the_trainers_hub_null(monkeypatch):
    bench_tiny.on_cpu(monkeypatch)
    built = []
    build = harness.build

    def keep(*args, **kw):
        model, trainer = build(*args, **kw)
        built.append(trainer)
        return model, trainer
    monkeypatch.setattr(harness, "build", keep)
    cell = bench_tiny.tiny_cell("yi-6b-1l.holes-short")
    result = harness.run(cell, SEED, 0.5, False, time.perf_counter())
    assert result["correct"], result["checks"]
    (trainer,) = built
    assert trainer.telemetry is NULL_TELEMETRY
    assert trainer.step_count > len(cell.traffic["check_nodes"])
    assert trainer.telemetry.regions == []
