"""``BENCHMARK.json`` and the files it names: every cell loads its
configuration, traffic, limits and metric readers by name, and states
the program's model as the program builds it."""
import json
import os

import pytest

import bench_tiny
from bench import harness

with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_reports_what_the_contract_asks(name):
    cell = harness.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    assert all(m["moves"] in e2e for m in cell.per_layer)
    for key in ("loss_gap", "grad_gap", "change_gap", "grad_sample_gap"):
        assert key in cell.limits


@pytest.mark.parametrize("name", CELLS)
def test_cell_config_is_the_programs_model(name):
    cell = harness.load_cell(name)
    harness.arch_config(cell.config)          # raises on any mismatch
    entry = {c["name"]: c for c in SPEC["configs"]}[cell.config["name"]]
    assert entry["reduced"] == cell.config["reduced"]


READERS = sorted(f[:-3] for f in os.listdir(
    os.path.join(bench_tiny.ROOT, "bench", "metrics")) if f.endswith(".py"))


def test_every_per_layer_metric_has_a_reader():
    assert {m["name"] for m in SPEC["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_in_an_empty_run(metric):
    run = harness.RunData(seconds=1.0, chips=1, seq_len=8,
                          flops_per_token=1.0, peak_flops=1.0)
    assert harness.load_reader(metric)(run) is None
