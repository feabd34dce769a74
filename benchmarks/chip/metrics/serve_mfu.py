"""Share of the chip's bf16 peak that the whole window's required work
reaches: the model FLOPs of every token processed (prefill chunks' valid
tokens and every decode row, attention included, packed weights counted
as their dense matmuls) over the window's seconds times the peak.  Layer:
whole step.  Moves ``tokens_per_s``."""


def read(ctx):
    return 100.0 * ctx.window_flops / (ctx.window_s * ctx.peak.bf16_flops)
