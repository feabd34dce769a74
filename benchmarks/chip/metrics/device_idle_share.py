"""Share of the traced window in which no operation ran on the device:
1 - (union of the operations' intervals) / (the window).  Layer: device.
Moves ``tokens_per_s``."""


def read(ctx):
    return 100.0 * (1.0 - ctx.tracemod.busy_s(ctx.trace)
                    / ctx.tracemod.window_s(ctx.trace))
