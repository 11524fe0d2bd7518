"""The reduction from a profiler trace to busy time, idle share and
idle gaps by host span: on small synthetic interval
sets, and on a small trace recorded on the chip (``bench/record_trace.py``:
steps of a matmul chain summed across the chips, and a 0.2 s host wait in
a ``bench.host_wait`` span)."""
import os

import pytest

import bench_tiny
from bench import trace

RECORDED = os.path.join(bench_tiny.ROOT, "bench", "testdata",
                        "small.xplane.pb")
HOST_WAIT_S = 0.2


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_clip_cuts_to_the_window():
    assert trace.clip([(0, 4), (6, 9), (10, 12)], 2, 10) == [(2, 4), (6, 9)]


def test_op_name_is_the_instruction_and_its_shape():
    text = ("%fusion.220 = (f32[2,2047]{1,0:T(2,128)S(1)}, f32[2,2047,64000]"
            "{2,1,0}) fusion(bf16[2,2048,4096]{2,1,0} %x), kind=kOutput")
    assert trace.op_name(text) == "fusion.220 f32[2,2047]"
    assert trace.op_name("%all-reduce.7 = f32[] all-reduce(f32[] %a)") \
        == "all-reduce.7 f32[]"
    assert trace.op_name("copy") == "copy"


def test_gaps_are_split_between_the_spans_inside_them():
    spans = [("bench.window", 0, 100), ("bench.park", 10, 40),
             ("bench.allocate", 20, 30)]
    got = trace.label_gaps([(21, 29), (5, 45), (50, 60)], spans)
    assert got == pytest.approx({"allocate": 18e-9, "park": 20e-9,
                                 "window": 20e-9})


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(RECORDED)


def test_recorded_trace_is_small():
    assert os.path.getsize(RECORDED) < 1 << 20


def test_recorded_busy_is_inside_the_window(recorded):
    assert recorded.busy_s
    for busy in recorded.busy_s.values():
        assert 0.0 < busy < recorded.window_s
    assert recorded.window_s > HOST_WAIT_S


def test_recorded_busy_equals_the_raw_union(recorded):
    """Recompute the first device's busy time straight from the planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(RECORDED)
    window = ops = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.window":
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
        if plane.name == "/device:TPU:0":
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
    import numpy as np
    # a bitmap of the window at 10 ns: no sorting or merging involved
    lo, hi = window
    busy = np.zeros(int((hi - lo) // 10) + 1, bool)
    for s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            busy[int((s - lo) // 10):int((e - lo) // 10)] = True
    n_ops = len(ops)
    assert recorded.busy_s[0] == pytest.approx(busy.sum() * 1e-8,
                                               abs=n_ops * 1e-8)


def test_recorded_host_wait_is_the_longest_idle(recorded):
    name, seconds = recorded.gaps[0]
    assert name == "host_wait"
    assert HOST_WAIT_S * 0.95 <= seconds <= HOST_WAIT_S * 1.5


def test_breakdown_lists_at_most_ten(recorded):
    b = trace.breakdown(recorded)
    assert 0 < len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "host_wait"
