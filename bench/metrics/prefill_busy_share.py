"""Model step (the jitted prefill-chunk programs): their device time over
the device's busy time in the traced slice, in %."""

import readers
import trace_reduce


def read(ctx):
    if ctx.red is None:
        return None
    busy = readers.busy_ns(ctx)
    ex = trace_reduce.executions(ctx.red, readers.CHUNK)
    return 100.0 * sum(e["dur"] for e in ex) / busy if busy else None
