"""The benchmark's plain reference agrees with the program at small
widths on the CPU: the same initial weights, the same loss, the same
AdamW steps (``make_train_step``), for every cell of ``BENCHMARK.json``,
each against the reference module its configuration names."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import harness
from bench.reference import data, module_for, train as reference

with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
SEED = 2**31 + 17


def _program(cfg):
    from repro.models import build_model
    return build_model(harness.arch_config(cfg))


def _flat(tree):
    return {jax.tree_util.keystr(p): np.asarray(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", CELLS)
def test_reference_init_equals_the_programs(name):
    cfg = bench_tiny.tiny_cell(name).config
    model = _program(cfg)
    prog = _flat(model.init(jax.random.key(SEED)))
    ref = {k: np.asarray(v)
           for k, v in module_for(cfg).init(cfg["arch"], SEED).items()}
    assert prog.keys() == ref.keys()
    # the same draws of the same generator; the reference makes them in
    # one jitted call, where the multiply by the scale may round once
    # differently from the program's op-by-op init: one float32 ulp
    for k in ref:
        np.testing.assert_allclose(prog[k], ref[k], rtol=2.4e-7, atol=0,
                                   err_msg=k)


@pytest.mark.parametrize("name", CELLS)
def test_reference_loss_matches_model_loss(name):
    cfg = bench_tiny.tiny_cell(name).config
    a, t = cfg["arch"], cfg["train"]
    model = _program(cfg)
    params = model.init(jax.random.key(SEED))
    tokens = data.rows(SEED, a["vocab_size"], t["seq_len"], 0, 3)
    want = float(model.loss(params, {"tokens": jnp.asarray(tokens),
                                     "labels": jnp.asarray(tokens)}))
    ref = module_for(cfg)
    got = float(ref.loss(a, ref.init(a, SEED), jnp.asarray(tokens)))
    # float32 sums of ~200 terms in another order: a few ulp of a loss
    # near ln(512) = 6.2, far below the 1e-3 a wrong mask or routing moves
    assert got == pytest.approx(want, abs=1e-5)


@pytest.mark.parametrize("name", CELLS)
def test_reference_rows_are_the_pipelines(name):
    from repro.data import DataConfig, TokenPipeline
    cfg = bench_tiny.tiny_cell(name).config
    a, t = cfg["arch"], cfg["train"]
    pipe = TokenPipeline(DataConfig(vocab_size=a["vocab_size"],
                                    seq_len=t["seq_len"], per_node_batch=2,
                                    seed=SEED))
    first, second = pipe.next_batch(1), pipe.next_batch(2)
    np.testing.assert_array_equal(
        first["tokens"], data.rows(SEED, a["vocab_size"], t["seq_len"], 0, 2))
    np.testing.assert_array_equal(
        second["tokens"], data.rows(SEED, a["vocab_size"], t["seq_len"], 2, 4))


@pytest.mark.parametrize("name", CELLS)
def test_reference_steps_match_make_train_step(name):
    """Three AdamW steps of the program's jitted step against the
    reference's, at one node: losses, the first clipped gradient's leaf
    norms and the parameters after the three steps."""
    from jax.sharding import Mesh
    from repro.elastic.trainer import make_train_step
    from repro.optim import AdamW
    cfg = bench_tiny.tiny_cell(name).config
    a, t = cfg["arch"], cfg["train"]
    model = _program(cfg)
    opt = AdamW(lr=t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"],
                weight_decay=t["weight_decay"], grad_clip=t["grad_clip"])
    step = make_train_step(model, opt, Mesh(np.asarray(jax.devices()[:1]),
                                            ("data",)),
                           warmup_steps=t["warmup_steps"],
                           total_steps=t["total_steps"])
    params = model.init(jax.random.key(SEED))
    state = opt.init(params)
    losses = []
    for i in range(3):
        tok = jnp.asarray(data.rows(SEED, a["vocab_size"], t["seq_len"],
                                    2 * i, 2))
        params, state, loss = step(params, state,
                                   {"tokens": tok, "labels": tok},
                                   jnp.float32(1.0))
        losses.append(float(loss))
        if i == 0:
            grad = {k: float(np.linalg.norm(v)) / (1 - t["b1"])
                    for k, v in _flat(state.mu).items()}
    ref = reference.train(cfg, SEED, [1, 1, 1])
    # float32 on the CPU in another summation order: relative 1e-5 covers
    # it; a schedule, clipping or bias-correction fault moves these by far
    # more than 1e-3
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    for k, v in ref["grad"].items():
        assert grad[k] == pytest.approx(v, rel=1e-4), k
    start = _flat(model.init(jax.random.key(SEED)))
    for k, v in _flat(params).items():
        change = float(np.linalg.norm(v - start[k]))
        assert change == pytest.approx(ref["change"][k], rel=1e-4), k
    assert all(math.isfinite(x) for x in losses)
