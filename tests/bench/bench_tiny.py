"""Shared helpers of the benchmark's CPU tests: the repository's root on
the import path, and the benchmark's cells cut to a size a test holds."""
import copy
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import device, harness  # noqa: E402

SEQ_LEN = 64
WIDTHS = {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
          "d_ff": 256, "vocab_size": 512}
MOE = {"n_experts": 4, "top_k": 2, "d_expert": 64}


def on_cpu(monkeypatch) -> None:
    """Let ``harness.run`` drive a run on the CPU: no look for a chip, and
    the memory statistics the CPU does not keep (the chip's are read only
    for the release time and the peak)."""
    monkeypatch.setattr(device, "require",
                        lambda devices, chips: {"bf16_flops_per_s": math.nan})
    monkeypatch.setattr(harness, "memory_stats", lambda d: {
        "bytes_in_use": 0, "peak_bytes_in_use": 0})


def tiny_config(cfg: dict) -> dict:
    """A configuration file's dict at ``reduced()``-like widths, the
    structure (pattern, MoE routing, untied head) kept."""
    cfg = copy.deepcopy(cfg)
    widths = dict(WIDTHS)
    if cfg["arch"].get("moe"):
        widths["d_ff"] = MOE["d_expert"]
        cfg["changes"]["moe"] = dict(MOE)
        cfg["arch"]["moe"].update(MOE)
        cfg["arch"]["attention_multiplier"] = WIDTHS["head_dim"] ** -0.5
    cfg["changes"].update(widths)
    cfg["arch"].update(widths)
    cfg["train"]["seq_len"] = SEQ_LEN
    return cfg


def tiny_cell(name: str) -> "harness.Cell":
    cell = harness.load_cell(name)
    cell.config = tiny_config(cell.config)
    return cell
