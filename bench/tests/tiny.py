"""Small cells for the CPU tests: the benchmark's own configurations and
mixes with their depth, widths and batch cut to what a test can run.

At this size the gaps between the program and the reference are far from
those at the cells' own size (smaller for ResNet, whose float32 rounding
cancels less; larger for the LM, whose bfloat16 rounding weighs more at
small widths), so a small cell carries limits of its own, set as the
cells' are: between the largest reading of the program over 8 seeds and
the smallest of the control or the half-batch fault over 3 (CPU, this
size). ``None``: no upper reading, printed and not compared."""
from __future__ import annotations

import dataclasses

from bench import harness

RESNET = dict(blocks=[1, 1, 1, 1], widths=[32, 64, 128, 256], groups=8,
              n_classes=10, image_size=32)
LM = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
          d_ff=128, vocab=512, attn_chunk=32)
MIXES = {"resnet50": dict(global_batch=8), "internlm2_1_8b_3l": dict(seq_len=64)}
# program (max) / control (min) / half batch (min), in the order of
# bench.check.NUMBERS:
# resnet50: 1.7e-7 2.2e-7 4.0e-7 1.8e-6 2.1e-6 2.2e-7 2.5e-7 /
#   1.5e-6 3.1e-6 1.3e-5 3.2e-5 2.1e-5 2.4e-6 4.9e-6 / 1.0e-2 and up
# internlm2_1_8b_3l: 5.0e-5 6.1e-5 6.1e-5 3.0e-3 3.1e-3 6.3e-4 8.0e-4 /
#   9.1e-6 1.3e-4 1.5e-4 1.4e-2 3.3e-3 3.0e-3 1.1e-3 /
#   8.2e-5 3.2e-4 1.6e-3 0.43 0.23 0.32 6.5e-3
LIMITS = {
    "resnet50": dict(loss1=6e-7, loss2=1e-6, loss3=3e-6, grad1=1e-5,
                     dparam3=8e-6, grad1_median=1e-6, dparam3_median=1.5e-6),
    "internlm2_1_8b_3l": dict(loss1=None, loss2=None, loss3=4e-4,
                              grad1=7e-3, dparam3=3e-2, grad1_median=1.5e-3,
                              dparam3_median=None),
}


def cell(workload: str, chips: int | None = None) -> harness.Cell:
    """``workload`` of BENCHMARK.json at a test's size."""
    c = harness.resolve(workload)
    name = c.sizes["name"]
    sizes = {**c.sizes, **(RESNET if c.ref.INPUT == "images" else LM)}
    mix = {**c.mix, **MIXES[name]}
    return dataclasses.replace(c, sizes=sizes, mix=mix, limits=LIMITS[name],
                               chips=chips if chips is not None else c.chips)
