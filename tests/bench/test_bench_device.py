"""The device guard: a run needs a TPU whose kind has peaks, and as many
chips as its cell; otherwise it exits non-zero and prints no result."""
import json

import jax
import pytest

import bench_tiny
from bench import device


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peaks_table_has_the_v5e_and_its_source():
    row = device.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(device.DeviceError, match="no peaks"):
        device.require([FakeDevice("tpu", "TPU v99")], 1)


def test_cpu_platform_is_an_error():
    with pytest.raises(device.DeviceError, match="needs a TPU"):
        device.require(jax.devices(), 1)


def test_too_few_chips_is_an_error():
    with pytest.raises(device.DeviceError, match="needs 4 chips"):
        device.require([FakeDevice("tpu", "TPU v5 lite")], 4)


def test_run_on_the_cpu_exits_nonzero_without_a_result(capsys):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_run_entry", os.path.join(bench_tiny.ROOT, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(SystemExit) as exc:
        mod.main(["--workload", "yi-6b-1l.holes-short", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
