"""Unified telemetry for the BFTrainer control plane (DESIGN.md §13).

One hub observes everything the control plane does — allocation
decisions per solver arm, loop events, rescale durations, fault
injections, checkpoint restores — as counters, gauges, streaming
histograms (p50/p95/p99) and dual-clock spans (trace clock + wall
clock) — and the live trainer's host work as wall-clock regions, each
also a ``jax.profiler`` span on the device trace's clock.  The default
is ``NULL_TELEMETRY``, a falsy no-op sink, so instrumented code paths
are bit-identical to uninstrumented ones when telemetry is off
(tests/test_obs.py pins this down).

Entry points:

* ``Telemetry()`` — the live hub; pass it as ``telemetry=`` to
  ``AllocationEngine`` / ``ControlLoop`` / ``Simulator`` /
  ``run_scenario`` / ``run_chaos``; ``BFTrainerRuntime`` hands it on
  through ``LiveBackend`` to each ``ElasticTrainer``.
* ``telemetry.write_chrome_trace(path)`` — Chrome trace-event JSON,
  loadable in Perfetto (https://ui.perfetto.dev).
* ``telemetry.write_jsonl(path)`` — deterministic span/event stream
  (wall-clock fields excluded by default).
* ``build_timelines(telemetry)`` — per-job lifecycle timelines.
* ``python -m repro.obs.report`` — text/JSON run summary CLI.
"""
from repro.obs.spans import (
    TRACE_EVENT_KEYS,
    TRACE_SCHEMA,
    SpanEvent,
    chrome_trace,
    read_jsonl,
    to_jsonl,
)
from repro.obs.telemetry import (
    NULL_TELEMETRY,
    Histogram,
    NullTelemetry,
    Telemetry,
)
from repro.obs.timeline import JobTimeline, build_timelines

__all__ = [
    "Telemetry", "NullTelemetry", "NULL_TELEMETRY", "Histogram",
    "SpanEvent", "chrome_trace", "to_jsonl", "read_jsonl",
    "TRACE_SCHEMA", "TRACE_EVENT_KEYS",
    "JobTimeline", "build_timelines",
]
