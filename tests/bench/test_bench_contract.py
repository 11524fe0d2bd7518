"""The reference contract: a configuration file names its reference module
(``bench.reference.module_for``), and the harness takes from that module
the gate on what the reference computes and the FLOP count ``step_mfu``
reads.  A model that ``lm`` does not compute (latent attention, shared
experts, a mixed layer pattern) is refused under ``lm`` by name, and is
accepted under a module that says it computes it."""
import copy
import dataclasses
import json
import os
import sys
import time
import types

import pytest

import bench_tiny
from bench import harness
from bench.reference import lm, module_for

EXISTING = {"yi-6b-1l": "yi-6b-1l.holes-short",
            "granite-moe-3b-4l": "granite-moe-3b-4l.hole-long"}
STUB = "contract_stub"
MARKER = 123456789.0

# the parent's counts (bench/flops.py's hand counts, test_bench_flops.py)
FLOPS_PER_TOKEN = {"yi-6b-1l": 2_711_617_536, "granite-moe-3b-4l": 1_209_461_760}
# the fields of the program's model the file states, as they were run
ARCH = {
    "yi-6b-1l": dict(
        n_layers=1, d_model=4096, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=11008, vocab_size=64000, rope_theta=5_000_000.0, norm_eps=1e-6,
        tie_embeddings=False, mlp_activation="swiglu", moe=None),
    "granite-moe-3b-4l": dict(
        n_layers=4, d_model=1536, n_heads=24, n_kv_heads=8, head_dim=64,
        d_ff=512, vocab_size=49155, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=False, mlp_activation="swiglu",
        moe=dict(n_experts=40, top_k=8, d_expert=512, n_shared=0,
                 router_aux_coef=0.01)),
}
PATTERN = {"yi-6b-1l": [("attn", "dense")],
           "granite-moe-3b-4l": [("attn", "moe")]}

TINY_MLA = {"kv_lora_rank": 64, "q_lora_rank": 0, "qk_nope_head_dim": 32,
            "qk_rope_head_dim": 16, "v_head_dim": 32}
TINY_MOE = {"n_experts": 4, "top_k": 2, "d_expert": 64, "n_shared": 2}
TINY_PATTERN = [{"mixer": "attn", "mlp": "dense"},
                {"mixer": "attn", "mlp": "moe"}]


def _config(name):
    with open(os.path.join(bench_tiny.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _deepseek_file(tmp_path, reference=None):
    """A DeepSeek-V2-Lite-shaped configuration file at tiny widths: one
    dense and one MoE layer, latent attention, two shared experts."""
    widths = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
              "head_dim": 48, "d_ff": 64, "dense_d_ff": 256,
              "vocab_size": 512}
    cfg = {
        "name": "deepseek-v2-lite-tiny",
        "registry": "deepseek-v2-lite-16b",
        "changes": dict(widths, layer_pattern=TINY_PATTERN, moe=TINY_MOE,
                        mla=TINY_MLA),
        "arch": dict(widths, layer_pattern=TINY_PATTERN, moe=TINY_MOE,
                     mla=TINY_MLA, rope_theta=10000.0, norm_eps=1e-6,
                     tie_embeddings=False, mlp_activation="swiglu"),
        "train": dict(_config("granite-moe-3b-4l")["train"], seq_len=64),
    }
    if reference is not None:
        cfg["reference"] = reference
    path = tmp_path / "deepseek-v2-lite-tiny.json"
    path.write_text(json.dumps(cfg))
    return json.loads(path.read_text())


@pytest.fixture
def stub(monkeypatch):
    """A reference module that says it computes every feature, and counts
    ``MARKER`` operations a token."""
    mod = types.ModuleType(f"bench.reference.{STUB}")
    mod.file_only = lambda arch: {}
    mod.unmodelled = lambda arch, a: []
    mod.flops_per_token = lambda a, seq_len: MARKER
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


class _Built(Exception):
    def __init__(self, run):
        self.run = run


def _run_data(monkeypatch, cell):
    """The ``RunData`` ``harness.run`` makes for the cell, caught where it
    would build the program's model."""
    bench_tiny.on_cpu(monkeypatch)

    def build(cell, seed, rec, step_wrap=None):
        raise _Built(rec.run)
    monkeypatch.setattr(harness, "build", build)
    with pytest.raises(_Built) as caught:
        harness.run(cell, 2**31 + 3, 1.0, False, time.perf_counter())
    return caught.value.run


@pytest.mark.parametrize("name", sorted(EXISTING))
def test_existing_configs_resolve_to_lm(name):
    cfg = _config(name)
    assert "reference" not in cfg
    assert module_for(cfg) is lm
    assert module_for(bench_tiny.tiny_config(cfg)) is lm


@pytest.mark.parametrize("tiny", [False, True])
@pytest.mark.parametrize("name", sorted(EXISTING))
def test_lm_computes_all_of_the_existing_models(name, tiny):
    cfg = _config(name)
    if tiny:
        cfg = lm.tiny(cfg)
    assert lm.unmodelled(harness.arch_config(cfg), cfg["arch"]) == []


@pytest.mark.parametrize("name", sorted(EXISTING))
def test_arch_config_is_the_parents(name):
    """What the file states, as the program builds it: the registry's
    model with the file's ``changes``, and the fields pinned."""
    from repro.configs import get_arch
    cfg = _config(name)
    arch = harness.arch_config(cfg)
    assert arch == dataclasses.replace(get_arch(cfg["registry"]),
                                       **cfg["changes"])
    want = dict(ARCH[name])
    moe = want.pop("moe")
    assert {k: getattr(arch, k) for k in want} == want
    assert (None if arch.moe is None else dataclasses.asdict(arch.moe)
            ) == (None if moe is None else dict(moe, capacity_factor=1.25))
    assert [(s.mixer, s.mlp) for s in arch.layer_pattern] == PATTERN[name]
    assert (arch.mla, arch.ssm, arch.query_scale) == (None, None, 0.0)


@pytest.mark.parametrize("name", sorted(EXISTING))
def test_run_counts_the_parents_flops(name, monkeypatch):
    cfg = _config(name)
    assert module_for(cfg).flops_per_token(cfg["arch"], 2048) == \
        FLOPS_PER_TOKEN[name]
    run = _run_data(monkeypatch, harness.load_cell(EXISTING[name]))
    assert run.flops_per_token == FLOPS_PER_TOKEN[name]
    assert run.seq_len == 2048


def test_deepseek_shaped_file_is_refused_under_lm(tmp_path):
    cfg = _deepseek_file(tmp_path)
    assert module_for(cfg) is lm
    with pytest.raises(ValueError, match="bench.reference.lm") as e:
        harness.arch_config(cfg)
    named = str(e.value).split("compute: ")[1].split(", ")
    assert {"mla", "layer_pattern", "moe.n_shared"} <= set(named)


def test_deepseek_shaped_file_is_accepted_under_its_own_reference(
        tmp_path, stub):
    from repro.configs import LayerSpec, MLAConfig
    cfg = _deepseek_file(tmp_path, reference=STUB)
    assert module_for(cfg) is stub
    arch = harness.arch_config(cfg)
    assert arch.layer_pattern == (LayerSpec("attn", "dense"),
                                  LayerSpec("attn", "moe"))
    assert arch.mla == MLAConfig(**TINY_MLA)
    assert (arch.moe.n_shared, arch.moe.n_experts) == (2, 4)
    assert (arch.n_layers, arch.dense_d_ff) == (2, 256)


@pytest.mark.parametrize("key, sub, value", [
    ("mla", "kv_lora_rank", 32),
    ("moe", "n_shared", 1),
    ("layer_pattern", 1, {"mixer": "attn", "mlp": "dense"}),
])
def test_every_stated_key_is_compared(tmp_path, stub, key, sub, value):
    """A nested key or a pattern entry that the file states otherwise
    than the program builds it is refused, and named."""
    cfg = _deepseek_file(tmp_path, reference=STUB)
    cfg["arch"] = copy.deepcopy(cfg["arch"])
    cfg["arch"][key][sub] = value
    with pytest.raises(ValueError, match=f"not what the file states.*{key}"):
        harness.arch_config(cfg)


YARN = {"type": "yarn", "factor": 40.0, "mscale": 0.707}


@pytest.mark.parametrize("reference, key, value", [
    (None, "rope_scaling", YARN),
    (STUB, "rope_scaling", YARN),
    (STUB, "embedding_multiplier", 1.0),
])
def test_a_key_neither_the_program_nor_the_reference_has_is_refused(
        tmp_path, stub, reference, key, value):
    """A file that states what the program has no field for, and its
    reference does not apply, is refused by name whatever the value:
    ``lm``'s multipliers are not the stub's."""
    cfg = _deepseek_file(tmp_path, reference=reference)
    cfg["arch"] = dict(cfg["arch"], **{key: value})
    with pytest.raises(ValueError, match=f"neither the program.*: {key}$"):
        harness.arch_config(cfg)


@pytest.mark.parametrize("key, published", [
    ("embedding_multiplier", 12.0), ("attention_multiplier", 0.015625),
    ("residual_multiplier", 0.22), ("logits_scaling", 6.0)])
def test_a_multiplier_the_program_does_not_run_is_refused(key, published):
    """granite's published multipliers, which the program's
    ``ArchConfig`` cannot state, are refused where the file states them."""
    cfg = _config("granite-moe-3b-4l")
    cfg["arch"] = dict(cfg["arch"], **{key: published})
    with pytest.raises(ValueError, match=f"not what the file states.*{key}"):
        harness.arch_config(cfg)


def test_run_takes_its_flops_from_the_module_the_file_names(
        tmp_path, stub, monkeypatch):
    cfg = _deepseek_file(tmp_path, reference=STUB)
    cell = harness.load_cell("granite-moe-3b-4l.hole-long")
    cell = dataclasses.replace(cell, name="deepseek-v2-lite-tiny.hole-long",
                               config=cfg)
    run = _run_data(monkeypatch, cell)
    assert run.flops_per_token == MARKER
    assert run.seq_len == 64


@pytest.mark.parametrize("name", ["../lm", "lm.py", "bench.reference.lm"])
def test_module_for_refuses_what_is_not_a_module_name(name):
    with pytest.raises(ValueError, match="not a module name"):
        module_for({"name": "x", "reference": name})
