"""Share of the window's decode steps that the lookahead pipeline could not
chain and ran synchronously (``pipeline.bubbles`` / ``serve.decode_steps``,
counted by the engine).  Layer: engine.  Moves ``itl_p95_ms``."""


def read(ctx):
    steps = ctx.counts["decode_steps"]
    return 100.0 * ctx.counts["bubbles"] / steps if steps else None
