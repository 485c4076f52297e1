"""deepseek-v2-lite — multi-head latent attention and 64 fine-grained
experts, served as one chip's share of an expert-parallel deployment.

[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]
27L d_model=2048 16H, MLA (no q-LoRA, kv_lora_rank 512, qk nope 128 +
rope 64, v 128, YaRN factor 40 over 4096), vocab 102400; layer 0 a dense
SwiGLU of 10944, layers 1-26 MoE: 64 routed experts of 1408, top-6 by
softmax with no renormalisation, plus 2 shared experts.

The share: expert parallelism 4 with data-parallel attention over one
v5e-4 host; each chip holds every layer's attention, the dense layer,
the shared experts, the embedding and LM head, and routed experts 0-15.
The router keeps all 64 outputs.
"""
from repro.configs.base import MLA, ModelConfig, register

CONFIG = register(
    ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=1,           # one latent row per token serves all heads
        d_ff=10944,
        vocab_size=102400,
        head_dim=192,             # qk_nope_head_dim + qk_rope_head_dim
        pattern_unit=(MLA,),
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_mscale_all_dim=0.707,
        num_experts=64,
        experts_per_token=6,
        moe_d_ff=1408,
        n_shared_experts=2,
        held_experts=tuple(range(16)),
        first_dense_layers=1,
        source="arXiv:2405.04434; hf",
    )
)
