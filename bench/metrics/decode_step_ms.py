"""Model step (models/, the jitted paged decode program): device time of
one decode program execution in the traced slice, mean in ms."""

import readers
import trace_reduce


def read(ctx):
    if ctx.red is None:
        return None
    ex = trace_reduce.executions(ctx.red, readers.DECODE)
    return sum(e["dur"] for e in ex) / len(ex) / 1e6 if ex else None
