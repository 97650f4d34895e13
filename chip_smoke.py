#!/usr/bin/env python3
"""Serve OLMo-1B at its published width on a TPU through the Pallas kernels.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: --tp 4 vs --tp 1, and
                                       # --replicas 4, one per chip

Drives the functions ``python -m repro.launch.serve`` runs
(``_load_model``, ``_build_serving``, ``make_requests``, ``serve_stream``)
on ``olmo-1b`` (16 layers, d_model 2048, 16 heads, d_ff 8192, vocab 50304,
bf16, packed ``mpd_c=8``) with random weights from ``PRNGKey(0)``:

* phase A — packed bf16, ``--paged --page-size 16 --slots 8``;
* phase B — the same traffic with ``--mpd-fuse --quantize int8`` (the fused
  FFN and bdmm kernels on int8 weights).

Traffic: 16 Poisson requests, prompts of 64-256 tokens (64-96 in the
four-chip phase; 4 of them share a 64-token prefix), 32 new tokens each.
Each phase compiles the engine's program ladder in ``warmup()`` first,
checks that the decode step's compiled program holds the Pallas kernels
(``tpu_custom_call``), requires every request to finish normally with no
step fault or quarantine, and compares one prompt's last-token prefill
logits with the ``jnp`` route in float32 at ``precision="highest"`` on the
same chip.

Exits non-zero, printing no result, unless JAX runs on a TPU with the Pallas
kernel route. The last line of a run that passed is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def traffic(prompt_len: int) -> list:
    return ["--arch", "olmo-1b", "--paged", "--page-size", "16",
            "--slots", "8", "--requests", "16", "--rate", "8",
            "--prompt-len", str(prompt_len), "--gen", "32",
            "--shared-prefix", "64"]


TRAFFIC = traffic(256)
# The four-chip phase compiles its warm-up ladder for the tp-4 mesh and
# once per replica device. Prompts of at most 96 tokens (max_len 128, 8
# pages) cut that ladder from 14 programs to 8.
FOUR_CHIP_TRAFFIC = traffic(96)
MIN_PROMPT, N_SHARED = 64, 4
PHASES = {"A": [], "B": ["--mpd-fuse", "--quantize", "int8"]}

# Logit tolerance, as max|served - reference| / max|reference| over the
# vocabulary. The served model keeps its weights, activations and KV pages
# in bf16 (unit roundoff 2^-8 = 3.9e-3), the reference the same weights in
# float32; every layer rounds the residual stream and each projection
# output once more, and 16 layers of such roundings add up to a few 1e-2
# of the logit range. Kernel faults (a wrong tile, a dropped block, a
# mis-indexed page) move logits by the order of the range itself.
LOGIT_TOL = 5e-2
# --tp 4 against --tp 1: both bf16 on the same kernels; only the partition
# of the projections and the reduction order differ, which is bounded by
# the same bf16 rounding budget.
TP_LOGIT_TOL = 5e-2


T0 = time.perf_counter()


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def stage(msg: str) -> None:
    """Progress on stderr, with the host's resident memory: a run cut short
    still shows how far it got."""
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    print(f"[{time.perf_counter() - T0:7.1f} s, rss {rss / 2**30:.2f} GiB] "
          f"{msg}", file=sys.stderr, flush=True)


def prefill_logits(model, params, prompt, *, chunk: int, page_size: int,
                   dtype=None):
    """Last-token logits of one prompt's chunked paged prefill: the model
    call the engine's admission makes, chunk by chunk, on a fresh page pool
    (page 0 is the null page)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = len(prompt)
    padded = -(-n // chunk) * chunk
    n_pages = padded // page_size + 1
    caches = model.init_paged_caches(1, n_pages, page_size, dtype)
    bt_row = jnp.arange(1, n_pages, dtype=jnp.int32)
    step = jax.jit(model.prefill_chunk, static_argnames=("final",))
    logits = None
    for start in range(0, n, chunk):
        m = min(chunk, n - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :m] = prompt[start:start + m]
        logits, caches = step(params, jnp.asarray(toks), caches, bt_row, 0,
                              start, m, final=start + chunk >= n)
    return np.asarray(logits[0], np.float32)


def reference_logits(model, params, prompt, *, chunk, page_size):
    """The same prefill on the ``jnp`` route in float32 at
    ``precision="highest"``: weights as stored (int8 stays int8, bf16 is
    widened exactly), activations and KV pages in float32."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import build

    ref_model = build(dataclasses.replace(model.cfg, dtype="float32"))
    ref_params = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    jax.clear_caches()          # routing is read at trace time
    ops.set_backend("jnp")
    try:
        with jax.default_matmul_precision("highest"):
            return prefill_logits(ref_model, ref_params, prompt, chunk=chunk,
                                  page_size=page_size, dtype=jnp.float32)
    finally:
        ops.set_backend("pallas")
        jax.clear_caches()


def rel_err(got, want) -> float:
    import numpy as np
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def requests_for(serve, cfg, args):
    return serve.make_requests(
        cfg, n_requests=args.requests, rate=args.rate,
        prompt_len=args.prompt_len, gen=args.gen, seed=args.seed,
        shared_prefix=args.shared_prefix, n_shared=N_SHARED,
        min_prompt_len=MIN_PROMPT, min_gen=args.gen)


def check_served(engine, reqs, summary, tag: str) -> None:
    bad = [(r.id, r.state.value, r.finish_reason, len(r.generated))
           for r in reqs
           if r.state.value != "done" or r.finish_reason is not None
           or len(r.generated) != r.max_new_tokens]
    if bad:
        fail(f"{tag}: requests not finished normally (id, state, reason, "
             f"tokens): {bad}")
    if summary["n_step_faults"] or summary["n_quarantines"]:
        fail(f"{tag}: {summary['n_step_faults']} step faults, "
             f"{summary['n_quarantines']} quarantines")


def serve_phase(serve, args, tag: str, device):
    """Build, warm, check and serve one configuration; returns the model,
    params, engine, requests and a dict of the phase's numbers."""
    import jax

    stage(f"{tag}: loading the model")
    cfg, model, params = serve._load_model(args)
    stage(f"{tag}: building {args.replicas} engine(s)")
    engine, mode = serve._build_serving(args, model, params)
    stage(f"{tag}: warming up")
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    stage(f"{tag}: warm after {compile_s:.1f} s; checking the decode step")
    eng0 = engine.replicas[0] if hasattr(engine, "replicas") else engine
    text = eng0.lower_decode(eng0.decode_widths()[-1]).compile().as_text()
    if "tpu_custom_call" not in text:
        fail(f"{tag}: the compiled decode step holds no Pallas kernel")
    reqs = requests_for(serve, cfg, args)
    stage(f"{tag}: serving {len(reqs)} requests")
    summary = serve.serve_stream(engine, reqs)
    jax.block_until_ready(eng0.cache.caches)
    stage(f"{tag}: served")
    check_served(engine, reqs, summary, tag)
    # bytes_in_use: what this phase holds on the chip after serving;
    # peak_bytes_in_use: the process's peak so far, earlier phases included
    stats = device.memory_stats() or {}
    out = {"phase": tag, "mode": mode, "compile_s": compile_s,
           "tokens": summary["total_tokens"], "tok_s": summary["agg_tok_s"],
           "ttft_p50_s": summary["ttft_p50_s"],
           "bytes_in_use": stats.get("bytes_in_use"),
           "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    return model, params, engine, reqs, out


def one_chip(serve, device, seed: int) -> None:
    for tag, flags in PHASES.items():
        args = serve.build_parser().parse_args(
            TRAFFIC + flags + ["--seed", str(seed)])
        model, params, engine, reqs, out = serve_phase(serve, args, tag,
                                                       device)
        kw = dict(chunk=engine.chunk_tokens, page_size=args.page_size)
        stage(f"{tag}: prefill logits, Pallas route")
        got = prefill_logits(model, params, reqs[0].prompt, **kw)
        stage(f"{tag}: prefill logits, float32 jnp reference")
        want = reference_logits(model, params, reqs[0].prompt, **kw)
        out["logit_rel_err"] = rel_err(got, want)
        out["logit_tol"] = LOGIT_TOL
        log(json.dumps(out))
        if not out["logit_rel_err"] <= LOGIT_TOL:
            fail(f"{tag}: prefill logits off the float32 reference by "
                 f"{out['logit_rel_err']:.3e} > {LOGIT_TOL}")
        del model, params, engine
        jax_clear()


def jax_clear() -> None:
    import gc

    import jax
    gc.collect()
    jax.clear_caches()


def four_chips(serve, device, seed: int) -> None:
    import jax
    import numpy as np

    base = FOUR_CHIP_TRAFFIC + ["--seed", str(seed)]
    # --tp 4 against --tp 1 on the same prompts
    logits, tokens = {}, {}
    for tp in (1, 4):
        args = serve.build_parser().parse_args(base + ["--tp", str(tp)])
        with serve._mesh_ctx(args):
            model, params, engine, reqs, out = serve_phase(
                serve, args, f"tp{tp}", device)
            logits[tp] = prefill_logits(model, params, reqs[0].prompt,
                                        chunk=engine.chunk_tokens,
                                        page_size=args.page_size)
        tokens[tp] = [list(r.generated) for r in reqs]
        log(json.dumps(out))
        del model, params, engine
        jax_clear()
    err = rel_err(logits[4], logits[1])
    same = [a == b for a, b in zip(tokens[4], tokens[1])]
    agree = float(np.mean([x == y for a, b in zip(tokens[4], tokens[1])
                           for x, y in zip(a, b)]))
    log(json.dumps({"phase": "tp4_vs_tp1", "logit_rel_err": err,
                    "logit_tol": TP_LOGIT_TOL,
                    "greedy_token_agreement": agree,
                    "requests_token_identical": int(sum(same)),
                    "requests": len(same)}))
    if not err <= TP_LOGIT_TOL:
        fail(f"--tp 4 prefill logits off --tp 1 by {err:.3e} > "
             f"{TP_LOGIT_TOL}")

    # --replicas 4: replica i on jax.devices()[i]
    args = serve.build_parser().parse_args(base + ["--replicas", "4"])
    devices = jax.devices()[:4]
    _, _, router, _, out = serve_phase(serve, args, "replicas4", device)
    placed = []
    for i, eng in enumerate(router.replicas):
        held = {d for leaf in jax.tree.leaves((eng.params, eng.cache.caches))
                for d in leaf.devices()}
        placed.append(sorted(d.id for d in held))
        if held != {devices[i]}:
            fail(f"replica {i} holds arrays on {held}, not {devices[i]}")
    if router.n_live != 4:
        fail(f"only {router.n_live} of 4 replicas survived")
    out["replica_device_ids"] = placed
    log(json.dumps(out))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (--tp 4 vs --tp 1, "
                    "--replicas 4)")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script ({e})")
    enable_compile_cache()

    import jax

    from repro.kernels import ops
    from repro.launch import serve

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    if ops.get_backend() != "pallas":
        fail(f"kernel route is {ops.get_backend()!r}, not 'pallas'")
    need = 4 if opts.four_chips else 1
    if len(devices) < need:
        fail(f"needs {need} chips, JAX sees {len(devices)}")
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}")

    if opts.four_chips:
        four_chips(serve, dev, opts.seed)
    else:
        one_chip(serve, dev, opts.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
