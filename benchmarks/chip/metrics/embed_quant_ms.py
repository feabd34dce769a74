"""Device time of the serving embedding's fake-quant in one decode step:
the operations under the model's ``embed_quant`` scope that run inside the
decode executions, summed, over the number of decode executions.  Layer:
decode.  Moves ``tokens_per_s``."""
import importlib


def read(ctx):
    spans = importlib.import_module(ctx.tracemod.__package__ + ".spans")
    trace = spans.attach(ctx.trace)
    dec = ctx.tracemod.modules(trace, ctx.modules["decode"])
    if not dec or not any(n == "embed_quant" for n, _, _ in trace.get("scoped", ())):
        return None
    return 1e3 * spans.scoped_within(trace, dec, "embed_quant") / len(dec)
