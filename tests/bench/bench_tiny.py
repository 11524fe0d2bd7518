"""Shared helpers of the benchmark's CPU tests: the repository's root on
the import path, and the benchmark's cells cut to a size a test holds."""
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import device, harness  # noqa: E402
from bench.reference import module_for  # noqa: E402


def on_cpu(monkeypatch) -> None:
    """Let ``harness.run`` drive a run on the CPU: no look for a chip, and
    the memory statistics the CPU does not keep (the chip's are read only
    for the release time and the peak)."""
    monkeypatch.setattr(device, "require",
                        lambda devices, chips: {"bf16_flops_per_s": math.nan})
    monkeypatch.setattr(harness, "memory_stats", lambda d: {
        "bytes_in_use": 0, "peak_bytes_in_use": 0})


def tiny_config(cfg: dict) -> dict:
    """A configuration file's dict at the size its reference module's
    ``tiny`` gives, the structure kept."""
    return module_for(cfg).tiny(cfg)


def tiny_cell(name: str) -> "harness.Cell":
    cell = harness.load_cell(name)
    cell.config = tiny_config(cell.config)
    return cell
