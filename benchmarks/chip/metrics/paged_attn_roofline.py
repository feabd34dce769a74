"""Share of its roofline that decode attention over the paged pool
reaches: the live KV codes and scales each row holds at that step (from
its length), with the rows' queries and outputs, over peak bandwidth (or
the FLOPs over peak, where larger), over the summed time of the
``paged_decode_attention*`` and ``fused_qkv_paged_decode*`` operations
inside the decode executions.  Layer: kernels.  Moves ``tokens_per_s``."""


def read(ctx):
    tm, costs, peak = ctx.tracemod, ctx.costs, ctx.peak
    dec = tm.modules(ctx.trace, ctx.modules["decode"])
    seconds = tm.ops_within(ctx.trace, dec, ("paged_decode_attention",
                                             "fused_qkv_paged_decode"))
    if not seconds or not ctx.traced_decode:
        return None
    least = sum(max(costs.decode_attn_bytes(ctx.cfg, ls) / peak.hbm_bytes_s,
                    costs.decode_attn_flops(ctx.cfg, ls) / peak.bf16_flops)
                for ls in ctx.traced_decode)
    least *= len(dec) / len(ctx.traced_decode)
    return 100.0 * least / seconds
