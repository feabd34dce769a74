"""Device time of one prefill-chunk launch: the executions of the prefill
executable in the trace, their summed time over their number.  Layer:
prefill.  Moves ``ttft_p50_ms``."""


def read(ctx):
    spans = ctx.tracemod.modules(ctx.trace, ctx.modules["prefill"])
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / 1e9 / len(spans)
