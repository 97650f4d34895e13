"""Whole step: model FLOPs of the window's work over the window times the
chip's bf16 peak, in %. Work: every prompt token the engine computed for a
request whose first token came in the window, and every output token that
decode produced in the window, each as 2 x packed matmul parameters plus
attention over its context (bench/flops.py)."""

import flops


def read(ctx):
    run, model = ctx.run, ctx.model
    per_key = 4.0 * model["n_layers"] * model["d_model"]
    dense = 2.0 * flops.matmul_params(model)
    work = 0.0
    for r in run.recs:
        for i, t in enumerate(r.times):
            if not run.t_open <= t < run.t_close:
                continue
            if i == 0:
                a = run.objs[r.id].n_matched
                n = r.n_prompt - a
                keys = (a + 1 + r.n_prompt) * n / 2.0   # sum of contexts
                work += n * dense + per_key * keys
            else:
                work += flops.token_flops(model, r.n_prompt + i)
    if ctx.peaks is None or work == 0.0:
        return None
    return 100.0 * work / (ctx.seconds * ctx.peaks["bf16_flops"])
