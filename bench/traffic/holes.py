"""The hole-trace generator that every traffic mix of the benchmark reads.

A traffic file (``bench/traffic/<name>.json``) fixes the idle-pool trace a
cell replays, and how a hole turns into train steps:

``time_scale`` and ``steps_per_second``
    An interval of ``dt`` trace-seconds grants
    ``int(dt * time_scale * steps_per_second)`` steps (the live backend's
    own rule).  Both are constants of the cell, never measured values, so
    the work offered is the same whatever the chip does with it.
``nodes``
    Pool size; each node alternates hole and busy period.
``hole`` / ``busy``
    Lengths in trace-seconds.  ``{"fixed_s": x}`` is one length;
    ``{"median_s": m, "sigma": s, "strata": k}`` is the lognormal of median
    ``m`` and shape ``s`` cut into ``k`` equal-probability strata, each
    represented by its midpoint quantile ``(i + 1/2) / k``.  Each pass over
    a node's cycle takes every stratum once; ``--seed`` only permutes the
    order within each pass, and sets each node's starting phase, so the mix
    inside a window does not depend on the seed.
``passes``
    How many passes to generate: enough to outlast any window.
``start_in_hole``
    Every node's first hole opens the trace, so every seed starts the
    window the same way.

The unstratified generator (``summit_like``) is a copy of the program's
``repro.core.trace.generate_summit_like`` with its constants as arguments,
calibrated to the Summit statistics of arXiv:2106.12091 (Sec. 2.1, Tab. 1,
Fig. 1): 58% of fragments shorter than 10 minutes, carrying about 10% of
the idle node-time.  The benchmark keeps its own copy so that a change to
the program cannot change the traffic it is measured on.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

import numpy as np

# Summit calibration (seconds), as in the program's generator
SHORT_W = 0.58
SHORT_MEDIAN_S, SHORT_SIGMA = 180.0, 0.9
LONG_MEDIAN_S, LONG_SIGMA = 5000.0, 0.8
BUSY_MEDIAN_S, BUSY_SIGMA = 24000.0, 0.7

Fragment = Tuple[int, float, float]          # (node, start, end)


def strata(median_s: float, sigma: float, k: int) -> List[float]:
    """Midpoint quantiles ``(i + 1/2) / k`` of a lognormal."""
    nd = NormalDist()
    return [median_s * math.exp(sigma * nd.inv_cdf((i + 0.5) / k))
            for i in range(k)]


def lengths(spec: Dict) -> List[float]:
    """One pass of lengths (trace-seconds) for a ``hole``/``busy`` entry."""
    if "fixed_s" in spec:
        return [float(spec["fixed_s"])]
    return strata(float(spec["median_s"]), float(spec["sigma"]),
                  int(spec["strata"]))


def node_fragments(node: int, holes: Sequence[float], busy: Sequence[float],
                   passes: int, rng: np.random.Generator,
                   phase: bool = True) -> List[Fragment]:
    """Alternate hole and busy period on one node.  Each pass takes every
    hole and busy length once, in an order drawn from ``rng``.  With
    ``phase`` the node starts at a point drawn from ``rng`` within its
    first cycle; without, its first hole opens at 0."""
    seq: List[Tuple[float, float]] = []
    for _ in range(passes):
        h = rng.permutation(len(holes))
        b = rng.permutation(len(busy))
        n = max(len(holes), len(busy))
        seq += [(holes[h[i % len(holes)]], busy[b[i % len(busy)]])
                for i in range(n)]
    cycle = sum(holes) / len(holes) + sum(busy) / len(busy)
    t = -float(rng.uniform(0.0, cycle)) if phase else 0.0
    out: List[Fragment] = []
    for hole, gap in seq:
        start, end = t, t + hole
        if end > 0.0:
            out.append((node, max(start, 0.0), end))
        t = end + gap
    return out


def fragments(traffic: Dict, seed: int) -> List[Fragment]:
    """All fragments of a traffic file's trace for ``seed``."""
    rng = np.random.default_rng(seed)
    holes = lengths(traffic["hole"])
    busy = lengths(traffic["busy"])
    out: List[Fragment] = []
    for node in range(int(traffic["nodes"])):
        out += node_fragments(node, holes, busy, int(traffic["passes"]),
                              rng, phase=not traffic.get("start_in_hole"))
    return sorted(out, key=lambda f: (f[1], f[0]))


def to_events(frs: Sequence[Fragment], event_type):
    """Join/leave events, one per time point, as ``event_type(time=...,
    joined=(...), left=(...))`` (the program's ``PoolEvent``)."""
    at: Dict[float, Tuple[List[int], List[int]]] = {}
    for node, start, end in frs:
        at.setdefault(start, ([], []))[0].append(node)
        at.setdefault(end, ([], []))[1].append(node)
    return [event_type(time=t, joined=tuple(sorted(j)), left=tuple(sorted(l)))
            for t, (j, l) in sorted(at.items())]


def grants(traffic: Dict, dt: float) -> int:
    """Steps an interval of ``dt`` trace-seconds grants."""
    return int(dt * float(traffic["time_scale"])
               * float(traffic["steps_per_second"]))


# ---------------------------------------------------------------------------
# Unstratified Summit-like generator (copy of the program's, constants as
# arguments)
# ---------------------------------------------------------------------------


def summit_like(n_nodes: int = 1024, duration: float = 7 * 86400.0,
                seed: int = 0, *, short_w: float = SHORT_W,
                short: Tuple[float, float] = (SHORT_MEDIAN_S, SHORT_SIGMA),
                long: Tuple[float, float] = (LONG_MEDIAN_S, LONG_SIGMA),
                busy: Tuple[float, float] = (BUSY_MEDIAN_S, BUSY_SIGMA)
                ) -> List[Fragment]:
    """Per-node alternating busy/idle renewal process."""
    rng = np.random.default_rng(seed)
    short_mu, long_mu, busy_mu = (math.log(short[0]), math.log(long[0]),
                                  math.log(busy[0]))
    out: List[Fragment] = []
    for node in range(n_nodes):
        t = -float(rng.uniform(0, math.exp(busy_mu)))
        while t < duration:
            t += float(rng.lognormal(busy_mu, busy[1]))
            if t >= duration:
                break
            if rng.uniform() < short_w:
                idle = float(rng.lognormal(short_mu, short[1]))
            else:
                idle = float(rng.lognormal(long_mu, long[1]))
            start, end = max(t, 0.0), min(t + idle, duration)
            if end > start:
                out.append((node, start, end))
            t += idle
    return sorted(out, key=lambda f: (f[1], f[0]))
