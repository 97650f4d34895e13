"""Kernels (kernels/paged_attention.py): the decode attention kernel's
device time over the device's busy time in the traced slice, in %."""

import readers
import trace_reduce

OPS = r"^paged_attention\."


def read(ctx):
    if ctx.red is None:
        return None
    busy = readers.busy_ns(ctx)
    ns = trace_reduce.kernel_ns(ctx.red, OPS)
    return 100.0 * ns / busy if busy and ns else None
