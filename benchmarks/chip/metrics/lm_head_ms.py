"""Device time of the head in one decode step: the operations under the
model's ``lm_head`` scope that run inside the decode executions, summed,
over the number of decode executions.  Layer: decode.  Moves
``tokens_per_s``."""


def read(ctx):
    dec = ctx.tracemod.modules(ctx.trace, ctx.modules["decode"])
    if not dec or not any(n == "lm_head" for n, _, _ in ctx.trace.get("scoped", ())):
        return None
    return 1e3 * ctx.spans.scoped_within(ctx.trace, dec, "lm_head") / len(dec)
