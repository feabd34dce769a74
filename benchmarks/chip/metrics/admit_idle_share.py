"""Share of the traced window in which no operation ran on the device while
the engine admitted requests: one of its ``admit.schedule`` spans (the
scheduler's pop of queued requests, with the prefix trie's lookups,
allocations and evictions) or ``admit`` spans (each admission's prefill
dispatches, logits fetch and first-token choice) was open.  The spans are
the program's own annotations, on the profiler's clock.  Layer: engine.
Moves ``itl_p95_ms``."""


def read(ctx):
    # a program that opens no ``serve.step`` span around its steps has
    # none of the engine's spans: nothing to read
    if not any(n == "serve.step" for n, _, _ in ctx.trace.get("spans", ())):
        return None
    return (100.0 * ctx.spans.idle_under(ctx.trace, *ctx.spans.ADMIT_SPANS)
            / ctx.tracemod.window_s(ctx.trace))
