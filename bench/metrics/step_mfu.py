"""Model step (the jitted paged decode program): the whole decode step's
share of the chip's bf16 peak, in %. FLOPs of one step: the window's
decode tokens (every output token after a request's first), each 2 x packed
matmul parameters plus attention over its context (bench/flops.py), over
the decode steps the engine ran in the window; its time: the decode
program's mean device time in the traced slice. It bounds what the kernel
rooflines beside it can claim."""

import flops
import readers
import trace_reduce


def read(ctx):
    if ctx.red is None or ctx.peaks is None:
        return None
    ex = trace_reduce.executions(ctx.red, readers.DECODE)
    steps = sum(1 for o in ctx.run.occupancy if o > 0)
    run = ctx.run
    work = sum(flops.token_flops(ctx.model, r.n_prompt + i)
               for r in run.recs for i, t in enumerate(r.times)
               if i > 0 and run.t_open <= t < run.t_close)
    if not ex or not steps or not work:
        return None
    step_s = sum(e["dur"] for e in ex) / len(ex) / 1e9
    return 100.0 * work / steps / (step_s * ctx.peaks["bf16_flops"])
