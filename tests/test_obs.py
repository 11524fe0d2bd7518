"""Observability subsystem tests (DESIGN.md §13).

Three invariant families:

* **zero-overhead parity** — replaying every scenario × policy with a
  live ``Telemetry`` hub produces bit-identical ``LoopStats`` /
  ``EngineStats`` to the disabled (``NULL_TELEMETRY``) replay: the hub
  is a passive sink and can never feed back into decisions;
* **trace determinism** — same-seed replays (clean and chaos) emit
  byte-identical JSONL streams (the wall clock is excluded by default);
* unit coverage for the pieces: streaming ``Histogram`` percentiles,
  JSONL round-trip, Chrome-trace export shape, per-job timelines, the
  dataclass-derived ``as_dict`` serialization, and the wall-clock
  ``region`` verb (nothing under a null or foreign sink; nesting,
  profiler names and ``_ms`` histograms on a live hub).
"""
from __future__ import annotations

import dataclasses
import json
import math
import types

import pytest

from repro.chaos import ChaosSpec, run_chaos
from repro.core import AllocationEngine, Simulator, fragments_to_events
from repro.core.backend import LiveBackend
from repro.core.engine import EngineStats
from repro.core.loop import LoopStats, TrainerJob
from repro.core.scaling import tab2_curve
import repro.obs.telemetry as telemetry_mod
from repro.obs import (
    NULL_TELEMETRY,
    Histogram,
    NullTelemetry,
    SpanEvent,
    Telemetry,
    TRACE_EVENT_KEYS,
    TRACE_SCHEMA,
    build_timelines,
    chrome_trace,
    read_jsonl,
    to_jsonl,
)
from repro.obs.report import _demo_jobs, run_summary
from repro.sched import SCENARIOS, build_scenario

POLICIES = ("throughput", "weighted", "maxmin", "deadline", "costcap")


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------


def test_histogram_exact_percentiles():
    h = Histogram()
    for v in range(1, 101):            # 1..100
        h.observe(float(v))
    assert h.count == 100
    assert h.percentile(50) == 50.0
    assert h.percentile(95) == 95.0
    assert h.percentile(99) == 99.0
    assert h.min == 1.0 and h.max == 100.0
    assert h.mean == pytest.approx(50.5)


def test_histogram_empty():
    h = Histogram()
    s = h.summary()
    assert s["count"] == 0
    assert s["p50"] == 0.0 and s["min"] == 0.0 and s["max"] == 0.0


def test_histogram_log_bucket_degradation():
    h = Histogram(exact_cap=64)
    vals = [1.001 ** i for i in range(1000)]   # spread over ~e
    for v in vals:
        h.observe(v)
    assert h._exact is None                     # degraded to buckets
    assert h.count == 1000
    exact = sorted(vals)
    for q in (50, 95, 99):
        approx = h.percentile(q)
        true = exact[max(0, math.ceil(q / 100 * len(exact)) - 1)]
        assert approx == pytest.approx(true, rel=0.08)   # ~7% buckets
    assert h.percentile(100) <= h.max * 1.07


def test_histogram_nonpositive_underflow():
    h = Histogram(exact_cap=2)
    for v in (-1.0, 0.0, 5.0, 7.0):
        h.observe(v)
    assert h.count == 4
    assert h.percentile(25) == 0.0              # underflow bucket
    assert h.percentile(99) == pytest.approx(7.0, rel=0.08)


# ---------------------------------------------------------------------------
# Span serialization + Chrome export
# ---------------------------------------------------------------------------


def _sample_events():
    return [
        SpanEvent("instant", "job", "admit", 5.0, 5.0, job=0,
                  args={"arrival": 1.0, "wait": 4.0}),
        SpanEvent("span", "job", "run", 5.0, 20.0, job=0, args={"n": 4}),
        SpanEvent("span", "job", "stall", 20.0, 25.0, job=0,
                  args={"why": "grow", "cost_s": 5.0}),
        SpanEvent("span", "solver", "greedy", 5.0, 5.0, wall_s=0.002,
                  args={"pool": 8}),
        SpanEvent("counter", "counter", "pool_size", 5.0, 5.0, value=8.0),
    ]


def test_jsonl_round_trip():
    evs = _sample_events()
    text = to_jsonl(evs)
    header = json.loads(text.splitlines()[0])
    assert header == {"schema": TRACE_SCHEMA}
    back = read_jsonl(text)
    assert len(back) == len(evs)
    # wall clock excluded by default: the solver span's wall_s is nulled
    assert back[3].wall_s is None
    assert back[1].args == {"n": 4}
    # include_wall keeps it
    back_w = read_jsonl(to_jsonl(evs, include_wall=True))
    assert back_w[3].wall_s == pytest.approx(0.002)


def test_jsonl_rejects_unknown_schema():
    with pytest.raises(ValueError, match="trace schema"):
        read_jsonl('{"schema": "bftrainer-trace/999"}\n')


def test_span_event_key_set_is_stable():
    d = _sample_events()[0].as_dict()
    assert list(d) == TRACE_EVENT_KEYS


def test_chrome_trace_shape():
    trace = chrome_trace(_sample_events())
    evs = trace["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C"} <= phases
    # every non-metadata event is a complete trace-event record
    for e in evs:
        assert {"ph", "pid", "tid", "name"} <= set(e)
        if e["ph"] in ("X", "i", "C"):
            assert "ts" in e
    # the solver span's rendered duration is its *wall* time in µs
    solver = [e for e in evs if e.get("cat") == "solver"][0]
    assert solver["dur"] == pytest.approx(0.002 * 1e6)
    # stalls render on the job's dedicated stall thread
    stall = [e for e in evs if e["name"] == "stall"][0]
    run = [e for e in evs if e["name"] == "run"][0]
    assert stall["tid"] == run["tid"] + 1


# ---------------------------------------------------------------------------
# Timelines
# ---------------------------------------------------------------------------


def test_build_timelines_folds_lifecycle():
    tel = Telemetry()
    tel.instant("job", "admit", 5.0, job=1, arrival=1.0, wait=4.0)
    tel.span("job", "run", 5.0, 10.0, job=1, n=4)
    tel.span("job", "run", 10.0, 20.0, job=1, n=4)     # merges with prev
    tel.instant("job", "rescale", 20.0, job=1, old=4, new=2, cost_s=5.0)
    tel.span("job", "stall", 20.0, 25.0, job=1, why="shrink", cost_s=5.0)
    tel.span("job", "run", 25.0, 30.0, job=1, n=2)
    tel.instant("job", "preempt", 30.0, job=1, taken=1)
    tel.instant("job", "fail", 31.0, job=1, lost=100.0, penalty_s=60.0)
    tel.instant("job", "finish", 40.0, job=1)
    tel.instant("loop", "pool-event", 5.0)             # ignored: not cat=job
    tls = build_timelines(tel)
    assert set(tls) == {1}
    t = tls[1]
    assert t.arrival == 1.0 and t.admitted_at == 5.0
    assert t.admission_wait == 4.0
    assert t.segments == [(5.0, 20.0, 4), (25.0, 30.0, 2)]
    assert t.node_seconds == pytest.approx(15 * 4 + 5 * 2)
    assert t.stalls == [(20.0, 25.0, "shrink")]
    assert t.rescales == [(20.0, 4, 2)]
    assert t.n_preemptions == 1 and t.n_failures == 1
    assert t.lost_progress == 100.0
    assert t.finished_at == 40.0
    s = t.summary()
    assert s["n_shrinks"] == 1 and s["n_grows"] == 0


# ---------------------------------------------------------------------------
# Null hub
# ---------------------------------------------------------------------------


def test_null_telemetry_is_falsy_noop():
    assert not NULL_TELEMETRY
    assert not NullTelemetry()
    assert Telemetry()
    NULL_TELEMETRY.count("x")
    NULL_TELEMETRY.gauge("x", 1.0)
    NULL_TELEMETRY.observe("x", 1.0)
    NULL_TELEMETRY.span("c", "n", 0.0, 1.0)
    NULL_TELEMETRY.instant("c", "n", 0.0)
    NULL_TELEMETRY.sample("x", 0.0, 1.0)
    assert NULL_TELEMETRY.counters == {}
    assert NULL_TELEMETRY.events == []


# ---------------------------------------------------------------------------
# Regions: wall-clock spans of host code
# ---------------------------------------------------------------------------


class _ForeignSink:
    """A truthy duck-typed sink whose unknown verbs do nothing, as the
    benchmark harness's event clock is."""

    def __bool__(self):
        return True

    def __getattr__(self, name):
        return lambda *args, **kw: None


@pytest.fixture
def profiler(monkeypatch):
    """``names``: the profiler spans opened, in order.  Reading the clock
    from the hub's module raises unless ``clock`` is set."""
    import jax.profiler

    seen = types.SimpleNamespace(names=[], clock=False)

    class Annotation:
        def __init__(self, name):
            seen.names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    ticks = iter(range(0, 10**9, 1000))

    def clock():
        if not seen.clock:
            raise AssertionError("the clock was read")
        return next(ticks)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(telemetry_mod.time, "perf_counter_ns", clock)
    return seen


@pytest.mark.parametrize("sink", [NULL_TELEMETRY, NullTelemetry(),
                                  _ForeignSink()],
                         ids=["null-singleton", "null", "foreign"])
def test_region_on_a_sink_that_records_nothing(sink, profiler):
    if isinstance(sink, Telemetry):
        ctx = sink.region("trainer.step", nodes=1)
        assert ctx is telemetry_mod.NULL_REGION
        with ctx as got:
            with sink.region("trainer.dispatch"):
                pass
        assert got is None
    # a live backend's rescale under this sink opens no region either
    trainer = types.SimpleNamespace(n_nodes=0)
    trainer.rescale = lambda n: setattr(trainer, "n_nodes", n)
    backend = LiveBackend([types.SimpleNamespace(id=0, trainer=trainer)])
    backend.telemetry = sink
    backend._sync(types.SimpleNamespace(id=0, nodes=[4, 7]))
    assert trainer.n_nodes == 2
    assert profiler.names == []
    if isinstance(sink, Telemetry):
        assert sink.regions == [] and sink.histograms == {}


def test_live_region_records_nesting_profiler_spans_and_histograms(
        profiler):
    profiler.clock = True
    tel = Telemetry()
    with tel.region("trainer.step", nodes=2) as outer:
        with tel.region("trainer.next_batch"):
            pass
        with tel.region("trainer.dispatch"):
            with tel.region("inner"):
                pass
    with pytest.raises(ValueError):
        with tel.region("trainer.step"):
            raise ValueError("the region still closes")
    assert outer is tel.regions[0]
    assert [(r.id, r.parent, r.name) for r in tel.regions] == [
        (0, None, "trainer.step"), (1, 0, "trainer.next_batch"),
        (2, 0, "trainer.dispatch"), (3, 2, "inner"),
        (4, None, "trainer.step")]
    assert tel.regions[0].args == {"nodes": 2}
    assert profiler.names == ["bftrainer/" + r.name for r in tel.regions]
    for r in tel.regions:
        assert r.t1_ns is not None and r.t1_ns > r.t0_ns
        if r.parent is not None:
            p = tel.regions[r.parent]
            assert p.t0_ns < r.t0_ns and r.t1_ns < p.t1_ns
    assert tel._open == []
    h = tel.histograms
    assert h["trainer.step_ms"].count == 2
    assert h["inner_ms"].count == 1
    assert h["trainer.dispatch_ms"].max == pytest.approx(
        (tel.regions[2].t1_ns - tel.regions[2].t0_ns) * 1e-6)
    # regions stay out of the deterministic span stream
    assert tel.events == [] and tel.to_jsonl().count("\n") <= 1


# ---------------------------------------------------------------------------
# Dataclass-derived serialization (EngineStats / LoopStats)
# ---------------------------------------------------------------------------


def test_engine_stats_as_dict_matches_fields():
    s = EngineStats()
    assert set(s.as_dict()) == {f.name for f in dataclasses.fields(s)}


def test_loop_stats_as_dict_matches_fields():
    s = LoopStats(total_samples=0.0, makespan=0.0, events_processed=0,
                  allocator="x", per_trainer_runtime={},
                  rescale_cost_samples=0.0, rescale_cost_s=0.0,
                  preempt_cost_s=0.0, solver_wall_total=0.0)
    d = s.as_dict()
    assert set(d) == {f.name for f in dataclasses.fields(s)}
    # and it is JSON-clean for the simple fields
    json.dumps({k: v for k, v in d.items() if k != "event_records"})


# ---------------------------------------------------------------------------
# Zero-overhead parity + trace determinism on real replays
# ---------------------------------------------------------------------------

PARITY_SCALE = 0.04


def _normalized(stats: LoopStats) -> LoopStats:
    recs = [dataclasses.replace(r, solver_wall=0.0)
            for r in stats.event_records]
    return dataclasses.replace(stats, solver_wall_total=0.0,
                               allocator="", event_records=recs)


def _replay(scenario: str, policy, tel):
    sc = build_scenario(scenario, scale=PARITY_SCALE, seed=7)
    events = fragments_to_events(sc.fragments)
    jobs = _demo_jobs(max(4, int(round(sc.stats.eq_nodes / 3))),
                      sc.duration, sc.stats.eq_nodes, seed=7)
    engine = AllocationEngine(time_budget=0.0)   # deterministic portfolio
    if tel is not None:
        engine.telemetry = tel
    stats = Simulator(events, jobs, engine, t_fwd=120.0,
                      horizon=sc.duration, objective=policy,
                      telemetry=tel).run()
    return stats, engine.stats


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("policy", POLICIES)
def test_enabled_disabled_parity(scenario, policy):
    """Enabling telemetry must not change a single decision or stat."""
    off_stats, off_engine = _replay(scenario, policy, None)
    tel = Telemetry()
    on_stats, on_engine = _replay(scenario, policy, tel)
    assert _normalized(on_stats) == _normalized(off_stats)
    assert dataclasses.replace(on_engine, wall_time=0.0) \
        == dataclasses.replace(off_engine, wall_time=0.0)
    assert tel.events                 # the enabled run really observed


def test_engine_stats_from_telemetry_round_trip():
    tel = Telemetry()
    _, engine_stats = _replay("bursty", None, tel)
    assert EngineStats.from_telemetry(tel) == engine_stats


def test_same_seed_trace_jsonl_is_deterministic():
    tel1 = Telemetry()
    tel2 = Telemetry()
    _replay("bursty", "maxmin", tel1)
    _replay("bursty", "maxmin", tel2)
    assert tel1.to_jsonl() == tel2.to_jsonl()


def _chaos_jobs():
    return [TrainerJob(id=i, curve=tab2_curve("ShuffleNet"), work=1e9,
                       n_min=1, n_max=8, r_up=20.0, r_dw=5.0)
            for i in range(3)]


def _chaos_events():
    from repro.core.events import PoolEvent
    return [PoolEvent(time=0.0, joined=tuple(range(8))),
            PoolEvent(time=3600.0, left=(0, 1)),
            PoolEvent(time=7200.0, joined=(0,))]


def _run_chaos(tel):
    spec = ChaosSpec(mtbf=4 * 3600.0, seed=11, ckpt_every=1e8,
                     crash_every=5000.0, corrupt_prob=1.0)
    return run_chaos(_chaos_events(), _chaos_jobs(), spec,
                     engine_factory=lambda: AllocationEngine(time_budget=0.0),
                     horizon=10800.0, telemetry=tel)


def test_chaos_trace_determinism_and_parity():
    rep_off = _run_chaos(None)
    tel1 = Telemetry()
    tel2 = Telemetry()
    rep_on = _run_chaos(tel1)
    _run_chaos(tel2)
    assert tel1.to_jsonl() == tel2.to_jsonl()
    assert _normalized(rep_on.stats) == _normalized(rep_off.stats)
    # the chaos layers observed into the shared hub
    assert any(k.startswith("chaos.") for k in tel1.counters) \
        or not rep_on.schedule.kills
    if rep_on.allocator_restarts:
        assert tel1.counters.get("allocator.restarts") \
            == rep_on.allocator_restarts
    if rep_on.corrupt_restores:
        assert tel1.counters.get("chaos.corrupt_restores") \
            == rep_on.corrupt_restores


def test_run_summary_is_json_ready():
    tel = Telemetry()
    stats, _ = _replay("bursty", None, tel)
    summary = run_summary(tel, stats)
    # dense trace: histograms, counters, gauges, per-job timelines
    assert summary["histograms"]["engine.decision_ms"]["count"] > 0
    assert summary["counters"]["engine.events"] > 0
    assert summary["gauges"]["loop.events_processed"] \
        == stats.events_processed
    assert summary["timelines"]
    json.dumps({k: v for k, v in summary.items() if k != "loop_stats"})
