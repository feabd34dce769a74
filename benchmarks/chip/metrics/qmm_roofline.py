"""Share of its roofline that the bit-packed matmul kernel reaches: the
least time the traced calls' shapes allow (each call the larger of
2·M·K·N over peak FLOP/s and planes + scales + activations + output over
peak bandwidth), over the summed time of the ``qmm_*`` operations inside
the decode and prefill executions.  Layer: kernels.  Moves
``tokens_per_s``."""


def read(ctx):
    tm, costs, peak = ctx.tracemod, ctx.costs, ctx.peak
    dec = tm.modules(ctx.trace, ctx.modules["decode"])
    pre = tm.modules(ctx.trace, ctx.modules["prefill"])
    seconds = tm.ops_within(ctx.trace, dec + pre, ("qmm_",))
    if not seconds:
        return None
    least = (len(dec) * sum(costs.qmm_least_seconds(m, lin, peak)
                            for m, lin in costs.decode_qmm_calls(ctx.cfg))
             + len(pre) * sum(costs.qmm_least_seconds(m, lin, peak)
                              for m, lin in costs.prefill_qmm_calls(ctx.cfg)))
    return 100.0 * least / seconds
