"""Integration tests: ElasticTrainer rescale semantics, the full
BFTrainerRuntime (scheduler driving real JAX training), and the serving
engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import (
    MILPAllocator,
    amdahl_curve,
    fragments_to_events,
    generate_summit_like,
)
from repro.elastic import BFTrainerRuntime, ElasticTrainer, ManagedTrainer
from repro.models import build_model
from repro.serving import ServeEngine


def small_trainer(arch="gemma-2b", seed=0, seq=48, lr=3e-3):
    from repro.optim import AdamW
    cfg = get_arch(arch).reduced()
    model = build_model(cfg, remat=False)
    tr = ElasticTrainer(model, per_node_batch=2, seed=seed,
                        optimizer=AdamW(lr=lr), warmup_steps=2)
    tr.pipeline.cfg.seq_len = seq
    return tr


def test_elastic_trainer_trains_and_rescales():
    tr = small_trainer()
    tr.rescale(1)
    losses = [tr.train_step().loss for _ in range(6)]
    # rescale preserves state: params identical before/after
    before = jax.tree.leaves(tr.params)[0].copy()
    tr.rescale(0)          # waiting (host snapshot)
    assert tr.n_nodes == 0
    tr.rescale(1)
    after = jax.tree.leaves(tr.params)[0]
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))
    # continues training from where it left off (step count preserved)
    m = tr.train_step()
    assert m.step == 7
    assert np.isfinite(m.loss)
    # loss should broadly decrease over continued training
    more = [tr.train_step().loss for _ in range(25)]
    assert np.mean(more[-5:]) < np.mean(losses[:3])


def test_train_step_donates_params_and_optimizer_state():
    """The step updates the state in place: the arrays passed in are
    deleted, so old and new state are never live together."""
    tr = small_trainer(seed=2)
    tr.rescale(1)
    tr.train_step()
    old = jax.tree.leaves((tr.params, tr.opt_state))
    m = tr.train_step()
    assert np.isfinite(m.loss)
    assert all(x.is_deleted() for x in old)
    assert not any(x.is_deleted()
                   for x in jax.tree.leaves((tr.params, tr.opt_state)))


def test_elastic_trainer_measures_rescale_costs():
    tr = small_trainer(seed=1)
    tr.rescale(1)
    tr.train_step()
    tr.rescale(0)
    tr.rescale(1)
    r_up, r_dw = tr.measured_rescale_costs()
    assert r_up > 0 and r_dw >= 0
    # 0->1, 1->0, 0->1 (no-op rescale(1)->1 is not recorded)
    assert len(tr.rescale_history) == 3


def test_measured_rescale_costs_exclude_kills():
    """Regression: transitions to/from 0 nodes (kill/park and unpark)
    are host-transfer events, not mesh rescales — they must not
    contaminate the r_dw/r_up estimates fed back into the MILP.  The old
    filter ``0 <= b < a`` averaged kill walls into r_dw."""
    tr = object.__new__(ElasticTrainer)   # only rescale_history is read
    tr.rescale_history = [
        (4, 2, 0.2), (2, 1, 0.4),   # true downscales
        (3, 0, 50.0),               # kill: must be excluded from r_dw
        (1, 2, 0.6), (2, 4, 1.0),   # true upscales
        (0, 2, 40.0),               # unpark: must be excluded from r_up
    ]
    r_up, r_dw = tr.measured_rescale_costs()
    assert r_dw == pytest.approx(0.3)     # mean(0.2, 0.4), no 50.0
    assert r_up == pytest.approx(0.8)     # mean(0.6, 1.0), no 40.0


def test_measured_rescale_costs_defaults_without_history():
    tr = object.__new__(ElasticTrainer)
    tr.rescale_history = [(0, 1, 12.0), (1, 0, 9.0)]   # only park/unpark
    r_up, r_dw = tr.measured_rescale_costs()
    assert (r_up, r_dw) == (0.5, 0.1)     # pre-measurement defaults


def test_elastic_rescale_rejects_oversubscription():
    tr = small_trainer(seed=2)
    with pytest.raises(ValueError):
        tr.rescale(len(jax.devices()) + 1)


def test_bftrainer_runtime_end_to_end():
    """The paper's full loop at miniature scale: MILP allocates single-node
    pools to two real Trainers over a replayed trace."""
    frags = generate_summit_like(n_nodes=6, duration=24 * 3600.0, seed=5)
    events = fragments_to_events(frags)
    managed = [
        ManagedTrainer(id=i, trainer=small_trainer(seed=10 + i),
                       curve=amdahl_curve(f"t{i}", 100.0, 0.2),
                       n_min=1, n_max=1, target_steps=3)
        for i in range(2)
    ]
    rt = BFTrainerRuntime(managed, MILPAllocator("fast"), t_fwd=120.0)
    rep = rt.run(events, time_scale=1.0, max_steps_per_interval=2)
    assert rep.events > 0
    assert sum(rep.steps.values()) > 0
    for mid, ls in rep.losses.items():
        assert all(np.isfinite(v) for v in ls)


def test_serve_engine_greedy_matches_forward_argmax():
    cfg = get_arch("yi-6b").reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(3))
    eng = ServeEngine(model, params, max_len=64)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 12)), jnp.int32)
    res = eng.generate({"tokens": prompt}, 5)
    assert res.tokens.shape == (2, 5)

    # replicate greedily with repeated full forwards
    toks = prompt
    for i in range(5):
        logits, _ = jax.jit(model.forward)(params, {"tokens": toks})
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        assert np.array_equal(np.asarray(nxt[:, 0]), res.tokens[:, i]), i
        toks = jnp.concatenate([toks, nxt], axis=1)


def test_serve_engine_ssm():
    cfg = get_arch("mamba2-2.7b").reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(4))
    eng = ServeEngine(model, params, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    res = eng.generate({"tokens": prompt}, 4)
    assert res.tokens.shape == (1, 4)
