"""DeepSeek-V2-Lite 16B — MoE with multi-head latent attention (MLA).
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

MLA with kv_lora_rank=512, no query LoRA, 128 + 64 (rope) QK and 128 V
head dims; the rope is YaRN-scaled (factor 40 over an original 4096
positions, beta 32 / 1, mscale = mscale_all_dim = 0.707), and the softmax
scale is ``192 ** -0.5 * m ** 2`` with ``m = 0.1 * 0.707 * ln 40 + 1``.
The first layer has a dense SwiGLU MLP of width 10944; the other 26 are
MoE: a softmax router over 64 routed experts (``routing.n_routed``), the
top-6 gates taken as the softmax gives them (``norm_topk=False``), 2
shared experts (one SwiGLU of width 2 x 1408), and a sequence-wise
load-balance loss (``seq_aux``, coefficient 0.001).  All 64 experts are
held here; a configuration that holds one chip's share of them sets
``moe.n_experts`` to the experts held and ``routing.first_held`` to the
first one's id.
"""
from repro.configs.base import (ArchConfig, LayerSpec, MLAConfig, MoEConfig,
                                RoutingConfig, YarnConfig)

_PATTERN = (LayerSpec(mixer="attn", mlp="dense"),) + tuple(
    LayerSpec(mixer="attn", mlp="moe") for _ in range(26)
)

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    source="arXiv:2405.04434 (DeepSeek-V2)",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,              # MLA: all heads share one latent KV
    head_dim=192,               # qk_nope (128) + qk_rope (64)
    d_ff=1408,                  # routed-expert hidden size (assignment)
    dense_d_ff=10944,           # dense first-layer MLP hidden size
    vocab_size=102400,
    layer_pattern=_PATTERN,
    mlp_activation="swiglu",
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  router_aux_coef=0.001),
    routing=RoutingConfig(n_routed=64, norm_topk=False, seq_aux=True),
    mla=MLAConfig(
        kv_lora_rank=512, q_lora_rank=0,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    ),
    supports_long_context=False,  # MLA is still full-context attention
)
