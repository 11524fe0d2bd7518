"""The trainer's wall-clock regions (DESIGN.md §13): the region tree of a
park and resume cycle, the wait for a resume's or a restore's copy on the
first step after it only, the park's bytes counted, the same numbers with
the hub on and off, and the hub's handoff from the loop through
``LiveBackend``."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.core import AllocationEngine, PoolEvent, amdahl_curve
from repro.elastic import BFTrainerRuntime, ElasticTrainer, ManagedTrainer
from repro.models import build_model
from repro.obs import NULL_TELEMETRY, Telemetry
from repro.optim import AdamW


def tiny_trainer(telemetry=None) -> ElasticTrainer:
    model = build_model(get_arch("yi-6b").reduced(n_layers=1), remat=False)
    tr = ElasticTrainer(model, per_node_batch=2, seed=5,
                        optimizer=AdamW(lr=3e-3), warmup_steps=2,
                        telemetry=telemetry)
    tr.seq_len(16)
    return tr


def cycle(tr: ElasticTrainer):
    """rescale(1) -> 2 steps -> rescale(0) -> rescale(1) -> 1 step: the
    losses and the final state's leaves, on the host."""
    tr.rescale(1)
    losses = [tr.train_step().loss, tr.train_step().loss]
    tr.rescale(0)
    tr.rescale(1)
    losses.append(tr.train_step().loss)
    return losses, [np.asarray(x) for x in
                    jax.tree.leaves((tr.params, tr.opt_state))]


def tree(tel: Telemetry):
    """``[(name, [child names])]`` for each top-level region."""
    kids = {}
    for r in tel.regions:
        kids.setdefault(r.parent, []).append(r)
    return [(r.name, [c.name for c in kids.get(r.id, [])])
            for r in kids[None]]


STEP = ["trainer.next_batch", "trainer.put_batch", "trainer.dispatch",
        "trainer.loss_wait"]


@pytest.fixture(scope="module")
def both():
    tel = Telemetry()
    on = tiny_trainer(tel)
    return tel, on, cycle(on), cycle(tiny_trainer())


def test_region_tree_of_a_park_and_resume_cycle(both):
    tel, *_ = both
    first = STEP[:2] + ["trainer.first_dispatch"] + STEP[3:]
    assert tree(tel) == [
        ("trainer.resume", ["trainer.build", "trainer.put_state"]),
        ("trainer.step", ["trainer.state_wait"] + first),
        ("trainer.step", STEP),
        ("trainer.park.copy", []),
        ("trainer.resume", ["trainer.put_state"]),
        ("trainer.step", ["trainer.state_wait"] + STEP),
    ]
    assert all(r.t1_ns is not None for r in tel.regions)
    h = tel.histograms
    assert h["trainer.state_wait_ms"].count == 2
    assert h["trainer.step_ms"].count == 3
    assert h["trainer.build_ms"].count == 1


def test_park_bytes_are_the_states(both):
    tel, on, *_ = both
    nbytes = sum(x.nbytes for x in jax.tree.leaves((on.params,
                                                    on.opt_state)))
    assert nbytes > 0
    assert tel.counters == {"trainer.park_bytes": nbytes}


def test_first_step_after_a_restore_waits_for_its_copy(tmp_path):
    tel = Telemetry()
    tr = tiny_trainer(tel)
    tr.rescale(1)
    tr.train_step()
    manager = CheckpointManager(str(tmp_path))
    tr.save_checkpoint(manager)
    tr.train_step()
    assert tr.restore_checkpoint(manager) == 1
    tr.train_step()
    steps = [r for r in tel.regions if r.name == "trainer.step"]
    waited = [any(c.parent == s.id and c.name == "trainer.state_wait"
                  for c in tel.regions) for s in steps]
    assert waited == [True, False, True]


def test_hub_changes_no_loss_and_no_bit_of_state(both):
    _, _, (losses_on, state_on), (losses_off, state_off) = both
    assert losses_on == losses_off
    assert len(state_on) == len(state_off)
    for a, b in zip(state_on, state_off):
        np.testing.assert_array_equal(a, b)


class _ForeignSink:
    """Truthy, drops every verb: the benchmark harness's event clock."""

    def __bool__(self):
        return True

    def __getattr__(self, name):
        return lambda *args, **kw: None


def _runtime_run(tr: ElasticTrainer, telemetry) -> None:
    managed = ManagedTrainer(id=0, trainer=tr,
                             curve=amdahl_curve("t", 100.0, 0.2),
                             n_min=1, n_max=1)
    events = [PoolEvent(0.0, joined=(0,)), PoolEvent(2.0, left=(0,)),
              PoolEvent(3.0, joined=(0,)), PoolEvent(5.0, left=(0,))]
    BFTrainerRuntime([managed], AllocationEngine(time_budget=0.0),
                     telemetry=telemetry).run(
        events, max_steps_per_interval=1, horizon=6.0,
        measure_rescale_costs=False)


def test_live_backend_hands_a_live_hub_to_the_trainer():
    tel = Telemetry()
    tr = tiny_trainer()
    _runtime_run(tr, tel)
    assert tr.telemetry is tel
    tops = tree(tel)
    assert [name for name, _ in tops if name != "trainer.step"] == \
        ["backend.rescale"] * 4
    assert [kids for name, kids in tops if name == "backend.rescale"] == \
        [["trainer.resume"], ["trainer.park.copy"]] * 2
    assert tel.histograms["trainer.step_ms"].count >= 2
    assert tel.histograms["backend.rescale_ms"].count == 4
    assert tel.regions[0].args == {"job": 0, "old": 0, "new": 1}
    assert not any(ev.cat == "backend" for ev in tel.events)


@pytest.mark.parametrize("sink", [None, _ForeignSink()],
                         ids=["null", "foreign"])
def test_live_backend_keeps_the_null_hub_without_a_live_one(sink):
    tr = tiny_trainer(sink)
    assert tr.telemetry is NULL_TELEMETRY
    _runtime_run(tr, sink)
    assert tr.telemetry is NULL_TELEMETRY
    assert tr.step_count >= 2
