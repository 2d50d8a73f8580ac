"""Model FLOP utilisation of the whole step, in %: the configuration's
model FLOPs per step (``model_flops`` of its reference module, recomputed
operations not counted) times the steps of the traced window, over the
window's length, the chips and one chip's bf16 peak."""


def read(ctx):
    return (100.0 * ctx.model_flops * ctx.steps
            / (ctx.window_s * ctx.chips * ctx.peak_flops))
