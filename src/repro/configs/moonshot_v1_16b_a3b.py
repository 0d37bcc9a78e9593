"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, config.json]: the
DeepSeek-V3 block (`model_type` deepseek_v3).  27 layers, the first dense
(intermediate 11264), then 26 MoE layers of 64 routed experts (1408 wide,
top-6, sigmoid scores normalized over the top-6 and scaled by 2.446) beside
2 shared experts; MLA with 16 heads over a 512-wide latent plus a 64-wide
rotary key (q_lora_rank null); untied embeddings; context 8192.

The LM stack (`repro.models`) implements neither MLA nor shared experts, so
`build_model` refuses this config; the workload zoo and `models/flops.py`
use it, and `SMOKE_CONFIG` keeps the LM smoke tests on plain MoE.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,            # per-expert FFN width (moe_intermediate_size)
    vocab_size=163840,
    block_pattern=("moe",),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    top_k=6,
    num_shared_experts=2,
    dense_layers=1,
    dense_d_ff=11264,
    tie_embeddings=False,
)

SMOKE_CONFIG = ModelConfig(
    name="moonshot-smoke",
    family="moe",
    num_layers=2,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=64,
    vocab_size=256,
    block_pattern=("moe",),
    num_experts=8,
    top_k=2,
)
