"""The comparison that decides ``correct``.

Set-up drives the trainer the window uses through its first steps (one
per entry of the traffic file's ``check_nodes``).  The plain reference
(``bench/reference``) follows the same steps from the same seed after the
window has closed.  Compared, each against its own limit from
``bench/limits/<cell>.json``:

``loss_gap``
    The largest ``|loss - reference loss|`` over the checked steps (nats).
``grad_gap``
    Worst leaf: the gap between the norms of the first step's gradient as
    AdamW gets it (the program's read from its first moment,
    ``mu / (1 - b1)``), over the larger of the reference leaf's norm and
    the median leaf's.
``change_gap``
    Worst leaf, the same measure, of the parameters' change over the
    checked steps.  Leaves whose reference gradient is under a thousandth
    of the median leaf's are left out: under Adam they move by round-off
    alone.
``grad_sample_gap``
    The median leaf's ``|g[S] - g_ref[S]| / |g_ref[S]|``, of the first
    step's gradient at a fixed sample ``S`` of each leaf's elements (drawn
    from the seed, ``sample_indices``), over the same leaves as
    ``change_gap``.  Norms and means average unbiased rounding away, so
    the three numbers above read a lower precision's arithmetic much as
    they read the program's; the gradient's elements do not.  The median
    and not the worst leaf: a MoE router's top-k near-ties flip under
    rounding, and make that one small leaf swing from seed to seed.
``park_state_changed``, ``parks_left_state``, ``replicas_differ``
    Exact guarantees, limit 0: leaves whose bits differ across set-up's
    park and resume; parks in the window after which the chip did not
    return to the memory it held after set-up's park; leaves whose copies
    differ between the devices of a mesh.
"""
from __future__ import annotations

import math
import zlib
from statistics import median
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

GRAD_FLOOR = 1e-3
SAMPLE = 4096                   # gradient elements compared per leaf


def sample_indices(seed: int, shapes: Dict[str, Tuple[int, ...]]
                   ) -> Dict[str, np.ndarray]:
    """Per leaf, ``SAMPLE`` flat element indices drawn from the seed and
    the leaf's path (with repeats where the leaf is small)."""
    out = {}
    for path, shape in sorted(shapes.items()):
        rng = np.random.default_rng([seed, zlib.crc32(path.encode())])
        out[path] = rng.integers(0, math.prod(shape), SAMPLE,
                                 dtype=np.int64).astype(np.int32)
    return out


def sample_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                leaves: Iterable[str]) -> Dict[str, float]:
    """Per leaf, ``|prog - ref| / |ref|`` over the sampled elements."""
    out = {}
    for k in leaves:
        if k not in prog:
            out[k] = math.inf
            continue
        p = np.asarray(prog[k], np.float64)
        r = np.asarray(ref[k], np.float64)
        num, den = np.linalg.norm(p - r), np.linalg.norm(r)
        out[k] = num / den if den > 0 else (0.0 if num == 0 else math.inf)
    return out


def median_gap(gaps: Dict[str, float]) -> float:
    values = list(gaps.values())
    return median(values) if all(map(math.isfinite, values)) else math.inf


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: Optional[Iterable[str]] = None) -> float:
    keys = sorted(ref) if leaves is None else sorted(leaves)
    if set(prog) != set(ref):
        return math.inf
    med = median(ref[k] for k in keys)
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def moving_leaves(ref_grad: Dict[str, float]) -> Tuple[str, ...]:
    med = median(ref_grad.values())
    return tuple(k for k, v in sorted(ref_grad.items())
                 if v >= GRAD_FLOOR * med)


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers of the program's readings against the
    reference's (both as ``bench.reference.train.train`` returns them)."""
    losses = list(zip(prog["losses"], ref["losses"]))
    loss_gap = (max(abs(p - r) for p, r in losses)
                if len(prog["losses"]) == len(ref["losses"]) else math.inf)
    if not math.isfinite(loss_gap):
        loss_gap = math.inf
    moving = moving_leaves(ref["grad"])
    return {
        "loss_gap": loss_gap,
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"], moving),
        "grad_sample_gap": median_gap(sample_gaps(
            prog["grad_sample"], ref["grad_sample"], moving)),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``(correct, {name: {"value": v, "limit": l}})``.  Every number must
    be finite and at most its limit; a number without a limit fails.  A
    limit of ``None`` marks a number that is read but not compared (one
    that neither the control nor a fault separates from sound runs)."""
    table = {k: {"value": v, "limit": limits.get(k, -math.inf)}
             for k, v in values.items() if limits.get(k, 0) is not None}
    ok = all(math.isfinite(t["value"]) and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
