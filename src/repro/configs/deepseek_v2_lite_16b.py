"""deepseek-v2-lite-16b: 27L d2048 16H V=102400; MLA (kv_lora 512, qk_nope 128,
qk_rope 64, v 128, no q_lora) with YaRN rope (factor 40); layer 0 a dense
SwiGLU of 10944, layers 1-26 MoE: 64 routed experts of 1408, softmax router,
greedy top-6 with unnormalised gates, plus 2 shared experts.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]"""
from .base import ArchConfig, MLASpec, MoESpec, YarnSpec

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400,
    head_dim=128,
    mla=MLASpec(kv_lora=512, qk_nope=128, qk_rope=64, v_dim=128),
    moe=MoESpec(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                norm_topk=False),
    n_dense_layers=1,
    yarn=YarnSpec(factor=40.0, original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    notes="MLA kv_lora=512 + YaRN; 1 dense + 26 MoE layers, 2 shared + 64 "
          "routed top-6 [arXiv:2405.04434]",
)
