"""Engine (serve/engine.py, on the host): the host's own time per decode
step in the traced slice, in ms. Each ``engine.step`` span's duration less
the parts its ``engine.*.sync`` children spent waiting for the device,
summed over the slice's steps, over the steps that ran a decode."""

import bisect

import trace_marks
import trace_reduce

SYNC = ("engine.prefill.sync", "engine.decode.sync")


def children(spans, parent):
    """Spans of ``spans`` (sorted by start) that start inside ``parent``
    and are not it."""
    starts = [h[1] for h in spans]
    a = bisect.bisect_left(starts, parent[1])
    b = bisect.bisect_left(starts, parent[1] + parent[2])
    return [h for h in spans[a:b] if h is not parent]


def read(ctx):
    if ctx.red is None:
        return None
    spans = trace_marks.spans(ctx)
    self_ns, decodes = 0, 0
    for step in (h for h in spans or [] if h[0] == "engine.step"):
        kids = children(spans, step)
        waits = trace_reduce.union([(s, min(s + d, step[1] + step[2]))
                                    for n, s, d, _ in kids if n in SYNC])
        self_ns += step[2] - sum(e - s for s, e in waits)
        decodes += any(k[0] == "engine.decode.dispatch" for k in kids)
    return self_ns / decodes / 1e6 if decodes else None
