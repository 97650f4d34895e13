"""Helpers the per-layer metric readers share (``bench/metrics/*.py``).

A reader takes the run's context and returns a number, or None when the
run gave it nothing to read; the harness then leaves the metric out.
"""

from __future__ import annotations

import re
from typing import List, Optional

import flops
import harness
import trace_reduce

# compiled programs, by the name of the jitted engine method
DECODE = r"decode_paged_impl"
CHUNK = r"prefill_chunk_impl"


def p95(values: List[float]) -> Optional[float]:
    return harness.pct(values, 95)


def due_in_window(ctx):
    """Requests due in the window, less those due while the profiler was
    being started or stopped (the drive loop could not submit them)."""
    run = ctx.run
    return [r for r in run.recs if run.t_open <= r.due < run.t_close
            and not any(a <= r.due <= b for a, b in run.stalls)]


def busy_ns(ctx) -> int:
    return sum(d["busy_ns"] for d in ctx.red["devices"])


def roofline(ctx, kernel: str, op_pattern: str) -> Optional[float]:
    """Least time the chip could take for the ``kernel`` calls of every
    decode and chunk execution in the traced slice (the larger of FLOPs
    over the bf16 peak and bytes over HBM bandwidth, from each call's
    shapes) over the time those calls took, in %."""
    if ctx.red is None:
        return None
    rx = re.compile(op_pattern)
    pk = ctx.peaks
    eng = ctx.mix["engine"]
    least, spent = 0.0, 0
    for ex in trace_reduce.executions(ctx.red, f"{DECODE}|{CHUNK}"):
        ops = [d for n, d in ex["ops"] if rx.search(n)]
        if not ops:
            continue
        if re.search(DECODE, ex["name"]):
            options = [flops.program_calls(ctx.model, ctx.quantize, "decode",
                                           eng["slots"])]
        else:
            options = [flops.program_calls(ctx.model, ctx.quantize, p,
                                           eng["prefill_chunk"])
                       for p in ("chunk", "chunk_final")]
        calls = next((c[kernel] for c in options
                      if len(c[kernel]) == len(ops)), None)
        if calls is None:
            continue          # not the program this reader can account for
        least += sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
                     for f, b in calls)
        spent += sum(ops)
    return 100.0 * least / (spent / 1e9) if spent else None
