"""Device time of the serving embedding's fake-quant in one decode step:
the operations under the model's ``embed_quant`` scope that run inside the
decode executions, summed, over the number of decode executions.  Layer:
decode.  Moves ``tokens_per_s``."""


def read(ctx):
    dec = ctx.tracemod.modules(ctx.trace, ctx.modules["decode"])
    if not dec or not any(n == "embed_quant" for n, _, _ in ctx.trace.get("scoped", ())):
        return None
    return 1e3 * ctx.spans.scoped_within(ctx.trace, dec, "embed_quant") / len(dec)
