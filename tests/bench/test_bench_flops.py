"""Required training FLOPs from the configurations' shapes, against hand
counts."""
import json
import os

import pytest

import bench_tiny
from bench import flops


def _arch(name):
    with open(os.path.join(bench_tiny.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)["arch"]


def test_yi_hand_count():
    # attention 4096*4096*2 + 2*4096*512 = 37.75M; SwiGLU 3*4096*11008 =
    # 135.27M; untied head 64000*4096 = 262.14M; attention scores
    # 12 * 1 * 2048 * 4096 per token
    per_token = 6 * (37_748_736 + 135_266_304 + 262_144_000) \
        + 12 * 2048 * 4096
    assert flops.flops_per_token(_arch("yi-6b-1l"), 2048) == per_token
    step = flops.step_flops(_arch("yi-6b-1l"), 2048, 2)
    assert step == pytest.approx(11.1e12, rel=0.005)


def test_granite_hand_count_counts_only_routed_experts():
    # per layer: attention 2*1536*1536 + 2*1536*512 = 6.29M, router
    # 1536*40, 8 of 40 experts 8*3*1536*512; 4 layers; head 49155*1536
    layer = 6_291_456 + 61_440 + 18_874_368
    per_token = 6 * (4 * layer + 75_502_080) + 12 * 4 * 2048 * 1536
    a = _arch("granite-moe-3b-4l")
    assert flops.flops_per_token(a, 2048) == per_token
    assert flops.step_flops(a, 2048, 2) == pytest.approx(4.95e12, rel=0.005)
