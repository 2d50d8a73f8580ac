"""moonlight-16b-a3b [hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3;
arXiv:2502.16982, block of arXiv:2412.19437]: 27L d=2048, the first layer
dense (ff=11264), then 26 MoE layers of 64 routed experts (ff=1408, top-6,
sigmoid scores, noaux_tc with one group, normalized, x2.446) and 2 shared
experts (one SwiGLU of 2816); MLA with 16 heads, kv_lora_rank 512, q/k heads
128 + 64 (rope), v heads 128, rope_theta 5e4; vocab 163840, untied head.

The expert layer holds a chip's share of the routed experts
(``experts_held``): the registry's config holds all 64, a deployment that
divides each layer over several chips gives each its ``(first, count)``.
The bias that picks experts (``noaux_tc``'s correction bias) is zero and
not updated here; the sequence-wise balance loss has alpha 1e-4 (the
DeepSeek-V3 report's; the config does not give it).  Training only, at
tp 1: no prefill or decode cells."""
import jax.numpy as jnp

from repro.configs.registry import ArchDef, ShapeCell
from repro.models.moe import MoEConfig
from repro.models.transformer import TransformerConfig

MOE = MoEConfig(
    n_experts=64,
    top_k=6,
    d_ff_expert=1408,
    shared_d_ff=2816,
    score_func="sigmoid",
    norm_topk=True,
    routed_scale=2.446,
    seq_aux_coef=1e-4,
    experts_held=(0, 64),
)

CONFIG = TransformerConfig(
    name="moonlight-16b-a3b",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    vocab=163840,
    rope_theta=5e4,
    moe=MOE,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense=1,
    eps=1e-5,
    dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16,
)

SMOKE = TransformerConfig(
    name="moonlight-smoke",
    n_layers=3,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    head_dim=24,
    d_ff=256,
    vocab=512,
    rope_theta=5e4,
    moe=MoEConfig(n_experts=16, top_k=4, d_ff_expert=128, shared_d_ff=256,
                  score_func="sigmoid", norm_topk=True, routed_scale=2.446,
                  seq_aux_coef=1e-4, experts_held=(0, 16)),
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    first_k_dense=1,
    eps=1e-5,
    dtype=jnp.float32,
    param_dtype=jnp.float32,
    attn_chunk=8,
)

ARCH = ArchDef(
    arch_id="moonlight-16b-a3b",
    family="lm",
    config=CONFIG,
    smoke_config=SMOKE,
    cells=(ShapeCell("train_8k", "train", {"seq_len": 8192, "global_batch": 256}),),
    notes="DeepSeek-V3 block: MLA, 1 dense + 26 MoE layers of 64 experts "
          "(6 routed, 2 shared); trains at tp 1, experts held per chip",
)
