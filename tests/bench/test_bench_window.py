"""The measured window's rate: every step begun in the window, over the
time from its opening to the end of the last of them, so a run that
loses no time reads the same whatever fraction of a step the close
falls in."""
import json
import time
import types

import pytest

import bench_tiny
from bench import harness

SEED = 2**31 + 41


def _step(seconds):
    def inner():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pass
        return types.SimpleNamespace(loss=1.0, samples=2,
                                     step_time_s=seconds)
    return inner


def test_the_step_over_the_close_counts_for_the_rate_alone():
    run = harness.RunData(seconds=0.05, chips=1, seq_len=8,
                          flops_per_token=1.0, peak_flops=1.0)
    rec = harness.Recorder(run, lambda: 0)
    trainer = types.SimpleNamespace(n_nodes=1)
    rec.start(0.05)
    with pytest.raises(harness.WindowClosed):
        while True:
            rec.train_step(trainer, _step(0.02))
    assert len(run.steps) == 2
    assert rec.overrun is not None and rec.overrun.t0 < rec.deadline
    assert rec.end() == rec.overrun.t1 > rec.deadline
    assert rec.attempted == 3 and rec.failed == 0


def test_a_close_between_steps_ends_the_rate_at_the_close():
    run = harness.RunData(seconds=0.05, chips=1, seq_len=8,
                          flops_per_token=1.0, peak_flops=1.0)
    rec = harness.Recorder(run, lambda: 0)
    rec.start(0.05)
    rec.train_step(types.SimpleNamespace(n_nodes=1), _step(0.01))
    while time.perf_counter() < rec.deadline:
        pass
    with pytest.raises(harness.WindowClosed):
        rec.train_step(types.SimpleNamespace(n_nodes=1), _step(0.01))
    assert rec.overrun is None and rec.end() == rec.deadline
    assert len(run.steps) == 1


def test_run_reports_the_rate_of_every_step_begun(monkeypatch, capsys):
    bench_tiny.on_cpu(monkeypatch)
    cell = bench_tiny.tiny_cell("granite-moe-3b-4l.hole-long")
    seconds = 0.5
    result = harness.run(cell, SEED, seconds, False, time.perf_counter())
    window = json.loads(next(
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("window: "))[len("window: "):])
    rate = result["metrics"]["train_tokens_per_s"]["value"]
    assert rate == window["tokens_per_s"]
    rows = cell.config["train"]["per_node_batch"]
    seq = cell.config["train"]["seq_len"]
    begun = round(rate * window["rate_s"] / (rows * seq))
    assert begun in (window["steps"], window["steps"] + 1)
    assert rate == pytest.approx(begun * rows * seq / window["rate_s"],
                                 rel=1e-12)
    assert window["rate_s"] >= seconds
    assert len(window["slowest_steps"]) == min(3, window["steps"])
