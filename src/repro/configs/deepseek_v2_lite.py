"""DeepSeek-V2-Lite — 27L d_model=2048 16H MLA (kv_lora 512, no q-LoRA,
qk-nope 128 + decoupled rope 64, v 128), vocab 102400; layer 0 dense
(d_ff 10944), layers 1–26 MoE: 2 shared + 64 routed experts of width 1408,
softmax router, greedy top-6, gates not renormalised, scale 1; RoPE θ 1e4
with YaRN (factor 40 over 4096 positions, β 32/1, mscale = mscale_all_dim
= 0.707); RMSNorm ε 1e-6.  [huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json; arXiv:2405.04434]

Served with the dropless expert layer (``moe_impl="dropless"``).  RoPE
rotates the two halves of each rope head (``layers.apply_rope``) where the
published code rotates interleaved pairs: a published checkpoint's rope
projection columns are loaded gathered even then odd, which makes the two
the same function."""

from repro.configs.registry import ArchSpec, default_skips
from repro.models.layers import YaRN
from repro.models.transformer import ModelConfig

ROPE_SCALING = YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,               # qk-nope and value dims per head
    d_ff=10944,               # the leading dense layer's SwiGLU
    vocab_size=102400,
    n_experts=64,
    experts_per_token=6,
    n_shared_experts=2,
    d_ff_expert=1408,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    moe_impl="dropless",
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,
    d_rope=64,
    norm_eps=1e-6,
    rope_theta=1e4,
    rope_scaling=ROPE_SCALING,
    param_dtype="bfloat16",
    remat=False,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    n_layers=3,
    n_dense_layers=1,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_head=16,
    d_ff=96,
    vocab_size=256,
    n_experts=8,
    experts_per_token=3,
    n_shared_experts=2,
    d_ff_expert=32,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    moe_impl="dropless",
    use_mla=True,
    kv_lora_rank=32,
    q_lora_rank=0,
    d_rope=8,
    norm_eps=1e-6,
    rope_scaling=YaRN(factor=40.0, original_max_position=64, beta_fast=32.0,
                      beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    act_dtype="float32",
    kv_chunk=32,
    remat=False,
)

SPEC = ArchSpec(
    arch_id="deepseek-v2-lite",
    source="[hf deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]",
    model=CONFIG,
    smoke=SMOKE,
    skip_cells=default_skips("moe"),
)
