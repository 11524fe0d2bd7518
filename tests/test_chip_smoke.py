"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
one-chip path (hole trace -> ControlLoop -> LiveBackend -> ElasticTrainer,
with parks and resumes) and its MoE gradient check pass their own checks
at a ``reduced()`` size."""
import importlib.util
import os

import pytest

from repro.configs import get_arch

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_host_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_one_chip_checks_pass_at_reduced_size(chip_smoke,
                                                         monkeypatch):
    monkeypatch.setattr(chip_smoke, "smoke_config",
                        lambda: get_arch("yi-6b").reduced(n_layers=1))
    # the CPU backend keeps no memory statistics
    monkeypatch.setattr(chip_smoke, "memory_stats", lambda device: {
        "bytes_in_use": 0, "peak_bytes_in_use": 0})
    checks = chip_smoke.one_chip()
    assert checks and all(checks.values()), checks


def test_chip_smoke_moe_grads_check_passes_at_reduced_size(chip_smoke,
                                                           monkeypatch):
    monkeypatch.setattr(chip_smoke, "moe_config",
                        lambda: get_arch("granite-moe-3b-a800m").reduced())
    monkeypatch.setattr(chip_smoke, "MOE_BATCH", (2, 64))
    checks = chip_smoke.moe_grads()
    assert checks and all(checks.values()), checks
