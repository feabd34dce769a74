"""The FLOPs that the traced prefill chunks' valid tokens require, over
their device time times the chip's bf16 peak.  Padding rows of a chunk
count nothing.  Layer: prefill.  Moves ``ttft_p50_ms``."""


def read(ctx):
    spans = ctx.tracemod.modules(ctx.trace, ctx.modules["prefill"])
    if not spans or not ctx.traced_prefill:
        return None
    flops = sum(ctx.costs.prefill_chunk_flops(ctx.cfg, s, v)
                for s, v in ctx.traced_prefill)
    # launches and executions pair up one to one: the trace starts and
    # stops with the device idle
    flops *= len(spans) / len(ctx.traced_prefill)
    seconds = sum(e - s for s, e in spans) / 1e9
    return 100.0 * flops / (seconds * ctx.peak.bf16_flops)
