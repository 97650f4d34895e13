"""Model step: executables JAX built (compiled, or loaded from the
persistent cache) while the window was open, from the program's own
compile times (``repro.serve.metrics.COMPILE_TIMES``). Every one stalls the
steps behind it."""


def read(ctx):
    try:
        from repro.serve.metrics import COMPILE_TIMES
    except ImportError:
        return None
    run = ctx.run
    lo, hi = run.t0 + run.t_open, run.t0 + run.t_close
    return float(sum(1 for t in COMPILE_TIMES if lo <= t < hi))
