"""Device: the share of the traced slice in which no operation ran on the
device (1 - busy union / slice), averaged over the chips, in %."""

import trace_reduce


def read(ctx):
    if ctx.red is None:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(ctx.red)
                    / trace_reduce.window_s(ctx.red))
