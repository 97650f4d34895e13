"""Engine and scheduler (serve/engine.py, serve/scheduler.py): submit to
admission into a slot, from the program's own RequestMetrics, p95 in ms
over the requests due in the window."""

import readers


def read(ctx):
    waits = [ctx.run.queue_wait.get(r.id) for r in readers.due_in_window(ctx)]
    return readers.p95([w * 1e3 for w in waits if w is not None])
