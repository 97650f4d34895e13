"""Load generator (bench/gen.py, the drive loop): how late requests due in
the window were submitted, p95 in ms. A late generator is not a fast
server."""

import readers


def read(ctx):
    late = [(r.submit - r.due) * 1e3 for r in readers.due_in_window(ctx)
            if r.submit is not None]
    return readers.p95(late)
