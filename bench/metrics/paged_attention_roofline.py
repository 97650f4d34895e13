"""Kernels (kernels/paged_attention.py): roofline share of the decode
attention kernel in the traced slice, in %.

Each decode program execution is paired with the ``engine.decode.prep``
span that dispatched it, whose ``kv_tokens`` and ``live`` arguments give
the step's least work (``bench/attn_bytes.py``): the last one to start
before the execution ended. Host spans and device ops lie on clocks that
agree to about a millisecond (a v5e trace shows executions starting up to
0.7 ms before the span that dispatched them began), while the host waits
for an execution's tokens and starts the next step's span 3.6 ms or more
after it ends. Only whole pairs count: a span and an execution both inside
the slice, and no other execution claiming the span. The share is
the least time of the paired steps (the larger of FLOPs over the bf16 peak
and bytes over HBM bandwidth; bytes bound it) over the time their
``paged_attention`` calls took. A program whose spans carry no
``kv_tokens`` gives None."""

import bisect
import re

import attn_bytes
import readers
import trace_marks
import trace_reduce

OPS = re.compile(r"^paged_attention\.")


def read(ctx):
    if ctx.red is None:
        return None
    preps = [h for h in trace_marks.spans(ctx) or []
             if h[0] == "engine.decode.prep" and "kv_tokens" in h[3]]
    starts = [h[1] for h in preps]
    claimed = {}
    for ex in trace_reduce.executions(ctx.red, readers.DECODE):
        i = bisect.bisect_right(starts, ex["start"] + ex["dur"]) - 1
        if i >= 0:
            claimed.setdefault(i, []).append(ex)
    pk = ctx.peaks
    least, spent = 0.0, 0
    for i, exs in claimed.items():
        ns = sum(d for n, d in exs[0]["ops"] if OPS.search(n))
        if len(exs) > 1 or not ns:
            continue
        args = preps[i][3]
        f, b = attn_bytes.decode_step(ctx.model, ctx.config["kv_dtype"],
                                      int(args["kv_tokens"]),
                                      int(args["live"]))
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
        spent += ns
    return 100.0 * least / (spent / 1e9) if spent else None
