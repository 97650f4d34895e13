"""Engine and scheduler: the share of slots live in each engine step of the
window (ServeMetrics' per-step occupancy), mean in %."""


def read(ctx):
    occ = ctx.run.occupancy
    return 100.0 * sum(occ) / len(occ) if occ else None
