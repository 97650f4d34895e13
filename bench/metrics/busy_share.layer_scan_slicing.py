"""Model step (models/model.py, ``scan_layers``): device time of the ops
the layer scan itself adds, slicing each layer's page pool and parameters
out of their stacks and writing the pool back, over the device's busy time
in the traced slice, in %. Those ops carry the ``layer_scan`` scope and not
the ``layer`` scope of the scan's body (bench/trace_marks.py)."""

import readers
import trace_marks


def read(ctx):
    if ctx.red is None:
        return None
    scopes = trace_marks.scopes(ctx)
    ns = sum(t for dev in ctx.red["devices"]
             for name, t in dev["op_ns"].items()
             if trace_marks.scan_only(scopes.get(name, "")))
    busy = readers.busy_ns(ctx)
    return 100.0 * ns / busy if busy and ns else None
