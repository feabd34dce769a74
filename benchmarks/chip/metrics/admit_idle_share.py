"""Share of the traced window in which no operation ran on the device while
the engine admitted requests: one of its ``admit.schedule`` spans (the
scheduler's pop of queued requests, with the prefix trie's lookups,
allocations and evictions) or ``admit`` spans (each admission's prefill
dispatches, logits fetch and first-token choice) was open.  The spans are
the program's own annotations, on the profiler's clock.  Layer: engine.
Moves ``itl_p95_ms``."""
import importlib


def read(ctx):
    spans = importlib.import_module(ctx.tracemod.__package__ + ".spans")
    trace = spans.attach(ctx.trace)
    if not trace.get("spans"):
        return None
    return (100.0 * spans.idle_under(trace, *spans.ADMIT_SPANS)
            / ctx.tracemod.window_s(trace))
