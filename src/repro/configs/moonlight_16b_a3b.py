"""moonlight-16b-a3b [moe] — 27L d_model=2048 16H MLA vocab=163840 (untied):
one leading dense layer (d_ff=11264), then 26 MoE layers of 64 routed
experts (top-6, per-expert d_ff=1408) plus 2 shared experts.
[hf:moonshotai/Moonlight-16B-A3B, config.json (model_type deepseek_v3):
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]

Published values: num_hidden_layers 27, first_k_dense_replace 1,
intermediate_size 11264, n_routed_experts 64, num_experts_per_tok 6,
n_shared_experts 2, moe_intermediate_size 1408, kv_lora_rank 512,
q_lora_rank null, qk_nope_head_dim 128, qk_rope_head_dim 64,
v_head_dim 128, num_attention_heads 16, rope_theta 50000, rms_norm_eps
1e-5, vocab_size 163840, tie_word_embeddings false. 15.96 B parameters,
2.91 B active per token (``param_count`` / ``active_param_count``).

The NoC traffic model (``repro.workloads``) uses all of it: MoE dispatch
on the 26 MoE layers only, the dense layer's MLP all-reduce, the shared
experts' weights, the MLA latent KV cache. The LM substrate
(``repro.models``) is not taught MLA, shared experts or the dense layer —
no NoC path runs it: built from this config it is 27 MoE layers of MHA at
head_dim 128 (the per-head value width), the routed experts only.
"""

from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840, head_dim=128,
    n_experts=64, top_k=6, moe_d_ff=1408,
    n_dense_layers=1, n_shared_experts=2,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=50000.0, norm_eps=1e-5,
)

SMOKE = CONFIG.scaled(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      head_dim=16, d_ff=128, vocab=256, n_experts=8, top_k=2,
                      moe_d_ff=32, kv_lora_rank=16, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16)
