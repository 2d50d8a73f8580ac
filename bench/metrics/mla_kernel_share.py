"""Latent attention's own work against the bf16 peak, in %: causal MLA's
FLOPs per step (``mla_attention_flops`` of the Moonlight reference, at the
true head sizes 192 and 128, whatever implements them) over the device time
per step under ``attn_kernel`` and one chip's peak. The kernel's padding of
the head sizes to 256 is work that lowers this share."""
from bench import named_scopes

CONFIG, TRAFFIC = "moonlight_16b_a3b_5l", "s8k.b4.1chip"


def read(ctx):
    sizes, mix, ref = named_scopes.cell_counts(CONFIG, TRAFFIC)
    return named_scopes.share_of_peak(ctx, "attn_kernel",
                                      ref.mla_attention_flops(sizes, mix))
