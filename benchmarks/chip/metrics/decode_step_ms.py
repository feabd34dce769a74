"""Device time of one decode step: the decode executable and the on-device
sampler, summed over the trace, over the number of decode executions.
Layer: decode.  Moves ``tokens_per_s``."""


def read(ctx):
    dec = ctx.tracemod.modules(ctx.trace, ctx.modules["decode"])
    if not dec:
        return None
    smp = ctx.tracemod.modules(ctx.trace, ctx.modules["sampler"])
    return 1e3 * sum(e - s for s, e in dec + smp) / 1e9 / len(dec)
