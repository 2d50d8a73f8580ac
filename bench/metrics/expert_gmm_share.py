"""The held experts' grouped matmuls against the bf16 peak, in %: their
FLOPs per step at the expected load (``expert_gmm_flops`` of the Moonlight
reference: 3 matmuls of 2 R d f, R = B_mb S k held / E rows per microbatch
and MoE layer, each counted 4 times) over the device time per step under
``moe_experts`` (the grouped matmuls and their SwiGLU) and one chip's peak."""
from bench import named_scopes

CONFIG, TRAFFIC = "moonlight_16b_a3b_5l", "s8k.b4.1chip"


def read(ctx):
    sizes, mix, ref = named_scopes.cell_counts(CONFIG, TRAFFIC)
    return named_scopes.share_of_peak(ctx, "moe_experts",
                                      ref.expert_gmm_flops(sizes, mix))
