"""One run of one cell: set-up, the measured window, the check.

The cell's job is the program's own: an ``ElasticTrainer`` driven by
``BFTrainerRuntime.run`` -> ``ControlLoop`` -> ``AllocationEngine`` ->
``LiveBackend`` -> ``ElasticTrainer.rescale`` / ``train_step`` over the
hole trace of the cell's traffic file.  The harness only wraps the calls
into each layer, to time them and to put a profiler span around them:

* ``BenchTrainer``: rescales (park / resume / reshard) and train steps;
  it also ends the loop at the window's close, and gives the loop the
  traffic file's fixed stall costs in place of measured ones, so the work
  offered does not depend on the chip;
* ``TimedAllocator``: each allocation decision;
* ``EventClock``: a telemetry sink that notes when the loop starts to
  handle each pool event.

Set-up builds the trainer, takes the checked steps (``check_nodes``)
and, where the traffic parks, parks and resumes once.  The window then runs the loop
for ``seconds`` of wall time; the loop jumps over busy periods in no wall
time, so the window is the job's elastic life made contiguous.  The step
that runs over the close counts towards the rate, with the time it took
(``Recorder.overrun``); the per-layer readers see the steps that ended
inside.  After it the trainer's state is freed and the reference follows
the checked steps (``bench/check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, device
from bench import trace as tracing
from bench.reference import module_for, train as reference
from bench.traffic import holes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
RELEASE_TIMEOUT_S = 10.0
# a chip counts as released when it holds no more than this share of the
# job's state above its level after set-up's park
RELEASE_SLACK = 0.01


class WindowClosed(Exception):
    """The measured window has closed; unwinds the control loop."""


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    limits: Dict[str, float]


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Cell:
    spec = _read(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read(os.path.join(ROOT, conf["file"])),
        traffic=_read(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer,
        limits=_read(os.path.join(BENCH, "limits", name + ".json")))


def load_reader(metric: str) -> Callable:
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _as_stated(value, stated):
    """The program's value in the form the file states it: a nested
    dataclass as a dict of the keys stated (all where the file states
    none), the layer pattern as a list of whole entries."""
    if dataclasses.is_dataclass(value):
        return {k: getattr(value, k) for k in (stated or
                                               dataclasses.asdict(value))}
    if isinstance(value, tuple):
        return [_as_stated(v, None) for v in value]
    return value


def arch_config(cfg: Dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against every key of the file's ``arch`` (a field of the program's,
    or one of the reference's own ``file_only`` parameters; any other is
    refused), and against what its reference module
    (``bench.reference.module_for``) computes.  ``changes`` may rebuild
    ``moe``, ``mla``, ``ssm`` (dicts) and ``layer_pattern`` (a list of
    ``{"mixer", "mlp"}``)."""
    from repro.configs import (LayerSpec, MLAConfig, MoEConfig, SSMConfig,
                               get_arch)
    base = get_arch(cfg["registry"])
    changes = dict(cfg["changes"])
    for key, kind in (("moe", MoEConfig), ("mla", MLAConfig),
                      ("ssm", SSMConfig)):
        if changes.get(key) is not None:
            old = getattr(base, key)
            changes[key] = (kind(**changes[key]) if old is None else
                            dataclasses.replace(old, **changes[key]))
    if "layer_pattern" in changes:
        changes["layer_pattern"] = tuple(LayerSpec(**s)
                                         for s in changes["layer_pattern"])
    arch = dataclasses.replace(base, **changes)
    a = cfg["arch"]
    ref = module_for(cfg)
    # a key the program has no field for is one of the reference's own
    # parameters (granite's multipliers), at the value the program runs
    own = ref.file_only(arch)
    fields = {f.name for f in dataclasses.fields(arch)}
    unknown = sorted(set(a) - fields - set(own))
    if unknown:
        raise ValueError(f"{cfg['name']}: the file states what neither the "
                         f"program's ArchConfig nor the reference "
                         f"{ref.__name__} has: {', '.join(unknown)}")
    got = {k: _as_stated(getattr(arch, k), v) if k in fields else own[k]
           for k, v in a.items()}
    if got != a:
        diff = {k: (got[k], a[k]) for k in a if got[k] != a[k]}
        raise ValueError(f"{cfg['name']}: the program's {cfg['registry']} "
                         f"is not what the file states: {diff}")
    missing = ref.unmodelled(arch, a)
    if missing:
        raise ValueError(f"{cfg['name']}: the program's {cfg['registry']} "
                         f"has what the reference {ref.__name__} does not "
                         f"compute: {', '.join(missing)}")
    return arch


# ---------------------------------------------------------------------------
# what the window records
# ---------------------------------------------------------------------------


@dataclass
class Step:
    t0: float
    t1: float
    n_nodes: int
    rows: int
    step_time_s: float
    loss: float
    # the first step at a new node count also waits for the rescale's
    # copies, which ``rescale`` enqueues and does not wait for
    after_rescale: bool = False


@dataclass
class RunData:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""
    seconds: float
    chips: int
    seq_len: int
    flops_per_token: float
    peak_flops: float
    steps: List[Step] = field(default_factory=list)
    rescales: List[Tuple[str, int, int, float, float]] = field(
        default_factory=list)
    decisions: List[Tuple[float, float]] = field(default_factory=list)
    releases: List[float] = field(default_factory=list)
    trace: Optional[tracing.TraceSummary] = None


class Recorder:
    def __init__(self, run: RunData, memory: Callable[[], int]):
        self.run = run
        self.memory = memory               # bytes in use on the first chip
        self.deadline: Optional[float] = None
        self.opened = 0.0
        self.attempted = 0
        self.failed = 0
        self.parks_left_state = 0
        self.pool_events = 0
        self.joins = 0
        self.last_event = -math.inf
        self.last_decision = -math.inf
        self.pending: Optional[int] = None     # node count not yet stepped
        self.baseline: Optional[int] = None
        self.slack = 0
        self.compiles = 0
        self.traces = 0
        self.interval_steps: List[int] = []
        # the step begun in the window that ended after its close
        self.overrun: Optional[Step] = None

    @property
    def open(self) -> bool:
        return self.deadline is not None

    def start(self, seconds: float) -> None:
        self.opened = time.perf_counter()
        self.deadline = self.opened + seconds

    def stop(self) -> None:
        self.deadline = None

    def end(self) -> float:
        """The end of the window's work: the close, or the end of the
        step that ran over it."""
        return self.overrun.t1 if self.overrun else self.deadline

    def check_deadline(self) -> None:
        if self.open and time.perf_counter() >= self.deadline:
            raise WindowClosed

    def pool_event(self, joined: int) -> None:
        if self.open:
            self.last_event = time.perf_counter()
            self.pool_events += 1
            self.joins += joined > 0
            self.interval_steps.append(0)

    def rescale(self, trainer, n: int, inner: Callable[[int], float]):
        self.check_deadline()
        old = trainer.n_nodes
        if n == old:
            return inner(n)
        kind = "park" if n == 0 else "resume" if old == 0 else "reshard"
        start = max(self.last_event, self.last_decision)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + kind):
            dt = inner(n)
        t1 = time.perf_counter()
        if self.open:
            self.attempted += 1
            self.run.rescales.append((kind, old, n, t0, t1))
            self.pending = n if n > 0 else None
            if kind == "park":
                self._release(start)
        return dt

    def _release(self, start: float) -> None:
        limit = self.baseline + self.slack
        give_up = time.perf_counter() + RELEASE_TIMEOUT_S
        while self.memory() > limit:
            if time.perf_counter() > give_up:
                self.parks_left_state += 1
                return
            time.sleep(0.0005)
        t = time.perf_counter()
        if t <= self.deadline:
            self.run.releases.append(t - start)

    def train_step(self, trainer, inner: Callable):
        self.check_deadline()
        n = trainer.n_nodes
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                m = inner()
        except Exception:
            if self.open:
                self.attempted += 1
                self.failed += 1
            raise
        t1 = time.perf_counter()
        if not self.open:
            return m
        self.attempted += 1
        if not math.isfinite(m.loss):
            self.failed += 1
        step = Step(t0, t1, n, m.samples, m.step_time_s, m.loss,
                    self.pending == n)
        if t1 > self.deadline:
            self.overrun = step
            raise WindowClosed
        self.run.steps.append(step)
        if self.interval_steps:
            self.interval_steps[-1] += 1
        self.pending = None
        return m


def bench_trainer_class():
    """``ElasticTrainer`` with the harness's wrappers (built lazily so that
    importing the harness does not import the program)."""
    from jax.profiler import TraceAnnotation
    from repro.elastic import ElasticTrainer

    class BenchTrainer(ElasticTrainer):
        def __init__(self, model, *, recorder: Recorder,
                     costs: Tuple[float, float],
                     step_wrap: Optional[Callable] = None, **kw):
            self.recorder = recorder
            self.costs = costs
            self.step_wrap = step_wrap
            super().__init__(model, **kw)
            next_batch = self.pipeline.next_batch

            def annotated(n_nodes):
                with TraceAnnotation("bench.next_batch"):
                    return next_batch(n_nodes)
            self.pipeline.next_batch = annotated

        def _build(self, n_nodes):
            mesh, fn = super()._build(n_nodes)
            if self.step_wrap is not None:
                fn = self.step_wrap(fn, n_nodes, mesh)

            def step(*args):
                with TraceAnnotation("bench.step"):
                    return fn(*args)
            return mesh, step

        def measured_rescale_costs(self):
            return self.costs

        def rescale(self, n_nodes):
            return self.recorder.rescale(
                self, n_nodes, lambda n: ElasticTrainer.rescale(self, n))

        def train_step(self):
            return self.recorder.train_step(
                self, lambda: ElasticTrainer.train_step(self))

    return BenchTrainer


class TimedAllocator:
    """The allocator the loop calls, timed and spanned."""

    def __init__(self, inner, recorder: Recorder):
        self.inner = inner
        self.recorder = recorder
        self.name = inner.name

    def allocate(self, prob):
        rec = self.recorder
        rec.check_deadline()
        t0 = time.perf_counter()
        rec.last_decision = t0
        with jax.profiler.TraceAnnotation("bench.allocate"):
            res = self.inner.allocate(prob)
        if rec.open:
            rec.run.decisions.append((t0, time.perf_counter()))
        return res


class EventClock:
    """Telemetry sink: notes the start of the loop's handling of each pool
    event and drops everything else."""

    def __init__(self, recorder: Recorder):
        self.recorder = recorder

    def __bool__(self):
        return True

    def instant(self, cat, name, *args, **kw):
        if name == "pool-event":
            self.recorder.pool_event(kw.get("joined", 0))

    def __getattr__(self, name):
        return lambda *args, **kw: None


# ---------------------------------------------------------------------------
# readings of the program's state
# ---------------------------------------------------------------------------


def _named_leaves(tree) -> Tuple[List[str], List]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ([jax.tree_util.keystr(p) for p, _ in flat],
            [leaf for _, leaf in flat])


def _norm_list(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves]


_norms = jax.jit(_norm_list)


@jax.jit
def _gather(leaves, idx):
    return [x[jnp.unravel_index(i, x.shape)] for x, i in zip(leaves, idx)]


def first_gradient(opt_state, b1: float, seed: int
                   ) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:
    """The first step's gradient as AdamW got it, read from its first
    moment after one step (``mu = (1 - b1) g``): per leaf its norm, and
    its elements at ``check.sample_indices`` for the seed."""
    names, leaves = _named_leaves(opt_state.mu)
    idx = check.sample_indices(seed, dict(zip(names,
                                              (x.shape for x in leaves))))
    norms = {k: float(v) / (1.0 - b1) for k, v in zip(names, _norms(leaves))}
    picked = _gather(leaves, [idx[k] for k in names])
    sample = {k: np.asarray(v, np.float64) / (1.0 - b1)
              for k, v in zip(names, picked)}
    return norms, sample


def change_norms(params, model, seed: int) -> Dict[str, float]:
    """Per leaf, ``|params - the program's initial params for seed|``.
    The key is an argument, so that one program serves every seed."""
    names, _ = _named_leaves(params)
    fn = jax.jit(lambda p, key: _norm_list(jax.tree.leaves(jax.tree.map(
        lambda x, s: x - s, p, model.init(key)))))
    return {k: float(v) for k, v in zip(names,
                                        fn(params, jax.random.key(seed)))}


@jax.jit
def state_checksum(leaves):
    """One uint32 per leaf: the sum, mod 2**32, of each element's bits
    times an odd weight set by its position, so any one changed bit
    changes it."""
    def leaf(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
        weight = jnp.arange(bits.size, dtype=jnp.uint32) * 2 + 1
        return jnp.sum(bits * weight, dtype=jnp.uint32)
    return jnp.stack([leaf(x) for x in leaves])


def state_sums(trainer) -> np.ndarray:
    return np.asarray(state_checksum(
        jax.tree.leaves((trainer.params, trainer.opt_state))))


def memory_stats(d) -> Dict:
    """The chip's memory statistics (``bytes_in_use``, ``peak_bytes_in_use``)."""
    stats = d.memory_stats()
    if stats is None:
        raise device.DeviceError(f"{d} reports no memory statistics")
    return stats


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def build(cell: Cell, seed: int, rec: Recorder,
          step_wrap: Optional[Callable] = None):
    """The program's model and the trainer the window will drive, with its
    weights made from ``seed`` on the device.  ``step_wrap(fn, n_nodes,
    mesh)`` plants a fault in the compiled step (the tests use it)."""
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.optim import AdamW

    enable_compile_cache()
    cfg, job = cell.config, cell.traffic["job"]
    t = cfg["train"]
    model = build_model(arch_config(cfg))
    trainer = bench_trainer_class()(
        model, recorder=rec, costs=(job["r_up_s"], job["r_dw_s"]),
        step_wrap=step_wrap,
        optimizer=AdamW(lr=t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"],
                        weight_decay=t["weight_decay"],
                        grad_clip=t["grad_clip"]),
        per_node_batch=t["per_node_batch"], seed=seed,
        warmup_steps=t["warmup_steps"], total_steps=t["total_steps"])
    trainer.seq_len(t["seq_len"])
    return model, trainer


def check_steps(cell: Cell, seed: int, model, trainer) -> Dict:
    """Set-up's steps, one per ``check_nodes`` entry, read for the check:
    the program's readings as ``bench.reference.train.train`` gives the
    reference's."""
    b1 = cell.config["train"]["b1"]
    prog: Dict = {"losses": []}
    for i, n in enumerate(cell.traffic["check_nodes"]):
        trainer.rescale(n)
        prog["losses"].append(trainer.train_step().loss)
        if i == 0:
            prog["grad"], prog["grad_sample"] = first_gradient(
                trainer.opt_state, b1, seed)
    prog["change"] = change_norms(trainer.params, model, seed)
    return prog


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, step_wrap: Optional[Callable] = None) -> Dict:
    cfg, traffic = cell.config, cell.traffic
    devices = jax.devices()
    peak = device.require(devices, cell.chips)
    devices = devices[:cell.chips]

    from repro.core import AllocationEngine, PoolEvent, amdahl_curve
    from repro.elastic import BFTrainerRuntime, ManagedTrainer

    data = RunData(seconds=seconds, chips=cell.chips,
                   seq_len=cfg["train"]["seq_len"],
                   flops_per_token=module_for(cfg).flops_per_token(
                       cfg["arch"], cfg["train"]["seq_len"]),
                   peak_flops=peak["bf16_flops_per_s"])
    rec = Recorder(data, lambda: memory_stats(devices[0])["bytes_in_use"])
    model, trainer = build(cell, seed, rec, step_wrap)
    job = traffic["job"]
    prog = check_steps(cell, seed, model, trainer)
    guarantees: Dict[str, float] = {}
    if traffic.get("park_check"):
        n = trainer.n_nodes
        before = state_sums(trainer)
        trainer.rescale(0)
        rec.baseline = memory_stats(devices[0])["bytes_in_use"]
        rec.slack = RELEASE_SLACK * sum(
            x.nbytes for x in jax.tree.leaves(
                (trainer.params, trainer.opt_state)))
        trainer.rescale(n)
        guarantees["park_state_changed"] = int(
            np.sum(before != state_sums(trainer)))

    # ---- the window ----
    events = holes.to_events(holes.fragments(traffic, seed), PoolEvent)
    managed = ManagedTrainer(
        id=0, trainer=trainer,
        curve=amdahl_curve(cfg["name"], job["curve"]["thr1"],
                           job["curve"]["comm_frac"]),
        n_min=job["n_min"], n_max=job["n_max"])
    runtime = BFTrainerRuntime(
        [managed], TimedAllocator(AllocationEngine(), rec),
        steps_per_second=traffic["steps_per_second"], pj_max=job["pj_max"],
        telemetry=EventClock(rec))
    counting = [False]

    def on_compile(key, *args, **kw):
        if counting[0]:
            if key.endswith("backend_compile_duration") or \
                    "cache_retrieval" in key:
                rec.compiles += 1
            elif key.endswith("jaxpr_trace_duration"):
                rec.traces += 1
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
    setup_s = time.perf_counter() - t_process
    counting[0] = True
    with jax.profiler.TraceAnnotation("bench.window"):
        rec.start(seconds)
        try:
            runtime.run(events, time_scale=traffic["time_scale"],
                        max_steps_per_interval=1 << 30,
                        measure_rescale_costs=False)
        except WindowClosed:
            pass
        else:
            raise RuntimeError("the hole trace ended before the window "
                               "closed; raise the traffic's 'passes'")
        closed = time.perf_counter()
        end = rec.end()
        rec.stop()
    counting[0] = False
    if trace:
        jax.profiler.stop_trace()
    peak_bytes = max(memory_stats(d)["peak_bytes_in_use"] for d in devices)

    # ---- after the window: free the job, then the reference ----
    trainer.params = trainer.opt_state = None
    trainer._jitted.clear()
    del runtime, managed, trainer
    gc.collect()
    if trace:
        try:
            data.trace = tracing.reduce(tracing.find_xplane(log_dir),
                                        [d.id for d in devices])
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    ref = reference.train(cfg, seed, traffic["check_nodes"])
    values = check.numbers(prog, ref)
    values.update(guarantees)
    if traffic.get("park_check"):
        values["parks_left_state"] = rec.parks_left_state
    correct, table = check.judge(values, cell.limits)
    correct = correct and rec.failed == 0

    # ---- the result ----
    steps = data.steps
    # the rate: every step begun in the window, over the time from its
    # opening to the end of the last of them (the close, where none ran
    # over it), so it moves by less than a whole step
    done = steps + ([rec.overrun] if rec.overrun else [])
    tokens_per_s = sum(s.rows for s in done) * data.seq_len / (
        end - rec.opened)
    walls = sorted(range(len(steps)), key=lambda i: steps[i].t0 - steps[i].t1)
    gaps = [steps[i + 1].t0 - steps[i].t1 for i in range(len(steps) - 1)]
    counts = {
        "window_s": closed - rec.opened,
        "steps": len(steps), "pool_events": rec.pool_events,
        "holes_opened": rec.joins,
        "parks": sum(k == "park" for k, *_ in data.rescales),
        "resumes": sum(k == "resume" for k, *_ in data.rescales),
        "grows": sum(k == "reshard" and b > o for k, o, b, *_ in
                     data.rescales),
        "shrinks": sum(k == "reshard" and b < o for k, o, b, *_ in
                       data.rescales),
        "compilations_in_window": rec.compiles,
        "traces_in_window": rec.traces,
        "steps_per_interval": rec.interval_steps[:40],
        "tokens_per_s": tokens_per_s,
        "rate_s": end - rec.opened,
        # where a slow run lost its time: the three longest steps as
        # [index, start in the window s, wall ms, step_time_s ms], and
        # the longest gap between two steps
        "slowest_steps": [[i, steps[i].t0 - rec.opened,
                           1e3 * (steps[i].t1 - steps[i].t0),
                           1e3 * steps[i].step_time_s] for i in walls[:3]],
        "longest_gap_ms": 1e3 * max(gaps, default=0.0),
        "releases_s": data.releases,
        "not_compared": {k: v for k, v in values.items()
                         if k not in table},
        "checked_losses": prog["losses"],
        "reference_losses": ref["losses"],
    }
    print("window: " + json.dumps(counts), flush=True)
    metrics: Dict[str, Dict] = {}
    if not trace:
        e2e = {"train_tokens_per_s": tokens_per_s,
               "release_s": (float(np.mean(data.releases))
                             if data.releases else None),
               "setup_s": setup_s}
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = load_reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = int(peak_bytes)
    result = {"correct": bool(correct), "attempted": rec.attempted,
              "failed": rec.failed, "metrics": metrics, "device": dev}
    if trace and data.trace is not None:
        dev["busy_s"] = data.trace.mean_busy_s
        dev["window_s"] = data.trace.window_s
        result["breakdown"] = tracing.breakdown(data.trace)
    result["checks"] = {k: {"value": _finite(float(v["value"])),
                            "limit": _finite(float(v["limit"]))}
                        for k, v in table.items()}
    return result


def _finite(x: float) -> float:
    return x if math.isfinite(x) else (1e300 if x > 0 else -1e300)
