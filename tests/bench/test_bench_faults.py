"""A whole benchmark run on the CPU at a small size, without the look
for a chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false, once for each fault a training
cell can have."""
import time

import jax
import pytest

import bench_tiny
from bench import check, harness
from bench.reference import lm, train as reference

SEED = 2**31 + 29


def _model(cell):
    from repro.models import build_model
    return build_model(harness.arch_config(cell.config))


def unchanged(cell):
    """The step computes the loss but returns its state as it got it."""
    loss = jax.jit(_model(cell).loss)
    return lambda fn, n, mesh: (
        lambda p, o, batch, lr: (p, o, loss(p, batch)))


def half_batch(cell):
    """The step sees only the first half of its rows."""
    return lambda fn, n, mesh: (lambda p, o, batch, lr: fn(
        p, o, {k: v[:v.shape[0] // 2] for k, v in batch.items()}, lr))


@pytest.mark.parametrize("fault", [None, unchanged, half_batch])
@pytest.mark.parametrize("name", ["yi-6b-1l.holes-short",
                                  "granite-moe-3b-4l.hole-long"])
def test_run_is_correct_only_when_sound(name, fault, monkeypatch):
    bench_tiny.on_cpu(monkeypatch)
    cell = bench_tiny.tiny_cell(name)
    result = harness.run(cell, SEED, 0.5, False, time.perf_counter(),
                         None if fault is None else fault(cell))
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    for m in cell.end_to_end:
        assert result["metrics"][m["name"]]["value"] > 0, m["name"]


@pytest.mark.parametrize("name", ["yi-6b-1l.holes-short",
                                  "granite-moe-3b-4l.hole-long"])
def test_control_in_the_programs_place_is_not_correct(name):
    """The control: the reference with float8 matmul operands, one
    precision below the program's single-bfloat16-pass float32 matmuls,
    in the program's place: the cell's limits reject it, by the sampled
    gradient elements."""
    cell = bench_tiny.tiny_cell(name)
    nodes = cell.traffic["check_nodes"]
    ref = reference.train(cell.config, SEED, nodes)
    ctl = reference.train(cell.config, SEED, nodes, dt=jax.numpy.bfloat16,
                          prec=lm.FP8)
    values = check.numbers(ctl, ref)
    correct, table = check.judge(values, {
        k: v for k, v in cell.limits.items() if k in values})
    assert not correct, values
    assert table["grad_sample_gap"]["value"] > \
        table["grad_sample_gap"]["limit"], values
