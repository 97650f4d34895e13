"""Production serving launcher for --arch <id>.

Default mode drives the ``repro.serve`` continuous-batching engine from a
synthetic Poisson request stream: requests with variable prompt/output
lengths arrive over wall-clock time, are admitted FCFS into cache slots,
and decode as one fixed-shape batch with per-request stop conditions. The
engine keeps K/V in a paged cache (global page pool + block tables +
prefix-reuse trie + chunked prefill; see ``repro.serve.cache.PagedCache``)
and the run reports page-level KV accounting next to the latency
percentiles.

``--spec-draft <dir>`` turns on speculative decoding: the
packed export in ``<dir>`` — typically the target's own MPD-folded int8
artifact — proposes ``--spec-k`` tokens per step against its own page
pool, and the target verifies the whole window in one dispatch. Greedy
output stays token-identical to plain decode; temperature > 0 uses
rejection sampling. Recurrent archs fall back to the plain loop.

``--http`` serves real traffic instead of the synthetic stream: an
asyncio HTTP frontend (``repro.serve.server``) streams tokens over SSE
from ``POST /v1/generate``, honours ``interactive``/``batch`` priority
classes (interactive preempts batch under page pressure), applies
bounded-queue backpressure (``--queue-limit`` -> 429 + Retry-After), and
exposes ``GET /metrics`` (Prometheus text) + ``GET /healthz``. Composes
with ``--spec-draft``.

On a real slice pass a mesh via ``repro.dist`` (the engine's page pools
shard through ``Model.paged_cache_axes()`` + the active rule table).
"""

import argparse
import collections
import contextlib
import logging
import time

import jax
import numpy as np

from repro.configs.common import ARCHS, get_config
from repro.data import SyntheticLM
from repro.models import build

log = logging.getLogger("repro.serve.launch")


def make_requests(cfg, *, n_requests, rate, prompt_len, gen, seed=0,
                  shared_prefix=0, n_shared=None, min_prompt_len=None,
                  min_gen=None):
    """Synthetic Poisson request stream: exponential inter-arrivals at
    ``rate`` req/s, prompt lengths in [min_prompt_len, prompt_len], output
    budgets in [min_gen, gen] (the minima default to half the maxima).
    ``shared_prefix`` forces the first that many prompt tokens identical
    across the first ``n_shared`` requests (all by default; system-prompt
    shape), so the engine's prefix trie gets real hits."""
    from repro.serve import Request, SamplingParams

    if rate <= 0:
        raise ValueError(f"arrival rate must be positive, got {rate}")
    if min_prompt_len is None:
        min_prompt_len = max(prompt_len // 2, 1)
    if min_gen is None:
        min_gen = max(gen // 2, 1)
    rng = np.random.default_rng(seed)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=prompt_len,
                       global_batch=max(n_requests, 1), seed=seed)
    toks = np.asarray(data.next()["inputs"])
    if shared_prefix:
        toks[:n_shared, :shared_prefix] = toks[0, :shared_prefix]
    t = 0.0
    out = []
    for i in range(n_requests):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.integers(min_prompt_len, prompt_len + 1))
        plen = max(plen, min(shared_prefix, prompt_len))
        out.append(Request(
            id=i, prompt=toks[i, :plen],
            max_new_tokens=int(rng.integers(min_gen, gen + 1)),
            sampling=SamplingParams(temperature=0.0, seed=seed * 1000 + i),
            arrival_time=t))
    return out


def serve_stream(engine, requests, *, idle_sleep=0.0005):
    """Wall-clock drive loop: submit each request when its arrival time
    elapses, step the engine whenever it has work. Returns the metrics
    summary."""
    pending = collections.deque(
        sorted(requests, key=lambda r: r.arrival_time or 0.0))
    t0 = time.perf_counter()
    engine.metrics.clock = lambda: time.perf_counter() - t0
    while pending or engine.has_work():
        now = time.perf_counter() - t0
        while pending and (pending[0].arrival_time or 0.0) <= now:
            engine.submit(pending.popleft())
        if engine.has_work():
            engine.step()
        elif pending:
            time.sleep(min(idle_sleep,
                           max((pending[0].arrival_time or 0.0) - now, 0)))
    return engine.metrics.summary()


def _load_spec_draft(args):
    """Deploy the draft model for speculative decoding from a packed
    export directory — typically the target's own MPD-folded (optionally
    int8) artifact, i.e. compression paying a second time as a draft."""
    from repro.checkpoint import checkpoint as ckpt_lib

    if not ckpt_lib.has_packed(args.spec_draft):
        raise SystemExit(
            f"--spec-draft needs a packed export under {args.spec_draft} "
            "(write one with `train --fold-to-packed` or export_packed)")
    draft, draft_params = ckpt_lib.load_packed(args.spec_draft)
    q = getattr(draft, "quant_report", None)
    log.info("spec draft: packed export from %s/packed%s, k=%d",
             args.spec_draft,
             f" (quantized, {q['bits']}-bit)" if q else "", args.spec_k)
    return draft, draft_params


def _build_resilience(args, *, chaos=True):
    """CLI engines always get the degradation ladder (the production
    posture); a fault injector rides along only when ``--chaos-schedule``
    is set (and never in the ``chaos=False`` baseline of --chaos-verify)."""
    from repro.serve import (DegradationLadder, FaultInjector, Resilience,
                             parse_schedule)

    injector = None
    if chaos and args.chaos_schedule:
        schedule = parse_schedule(args.chaos_schedule)
        injector = FaultInjector(schedule, seed=args.chaos_seed)
        log.info("chaos: %d fault specs from %r (seed %d)",
                 len(schedule), args.chaos_schedule, args.chaos_seed)
    return Resilience(injector=injector, ladder=DegradationLadder(),
                      seed=args.chaos_seed)


def _build_engine(args, model, params, *, chaos=True, device=None):
    """Construct the continuous-batching engine from CLI flags. Shared by
    the synthetic-stream driver and the ``--http`` frontend. ``device``
    places a router replica on one chip. Returns ``(engine, mode_label)``."""
    from repro.serve import Engine

    max_len = args.prompt_len + args.gen
    res = _build_resilience(args, chaos=chaos)
    spec_draft = _load_spec_draft(args) if args.spec_draft else None
    try:
        engine = Engine(model, params, n_slots=args.slots, max_len=max_len,
                        page_size=args.page_size, n_pages=args.pages or None,
                        prefill_chunk_tokens=args.prefill_chunk or None,
                        spec_draft=spec_draft, spec_k=args.spec_k,
                        resilience=res, device=device)
    except ValueError as e:
        raise SystemExit(str(e))
    mode = "paged+spec" if engine.spec_active else "paged"
    return engine, mode


def _build_serving(args, model, params, *, chaos=True):
    """One engine, or ``--replicas N`` of them behind a
    :class:`repro.serve.Router` — the facade is Engine-shaped either way,
    so the stream driver and the HTTP frontend don't branch on it.
    Replica ``i`` lives on ``jax.devices()[i]`` (round-robin when there are
    fewer devices than replicas); under ``--tp`` every replica spans the
    one mesh instead."""
    if args.replicas <= 1:
        return _build_engine(args, model, params, chaos=chaos)
    from repro.serve import Router

    devices = jax.devices() if args.tp <= 1 else [None]
    engines = []
    for i in range(args.replicas):
        engine, mode = _build_engine(args, model, params, chaos=chaos,
                                     device=devices[i % len(devices)])
        engines.append(engine)
    try:
        router = Router(engines, disagg=args.disagg,
                        n_prefill=args.n_prefill)
    except ValueError as e:
        raise SystemExit(str(e))
    mode += f" x{args.replicas}"
    if args.disagg:
        mode += f" (disagg: {args.n_prefill} prefill)"
    return router, mode


def _mesh_ctx(args):
    """``--tp M``: install an M-way ``model`` mesh + the tp rule table for
    the whole serving lifetime. Engines capture the active (mesh, rules)
    at construction and re-enter them around every step/warmup, and the
    paged attention ops shard head-parallel under them (bit-identical to
    the single-device path)."""
    if args.tp <= 1:
        return contextlib.nullcontext()
    from repro.dist import sharding as sh
    from repro.dist.mesh import make_mesh

    n_dev = len(jax.devices())
    if n_dev < args.tp:
        raise SystemExit(f"--tp {args.tp} needs {args.tp} devices, "
                         f"have {n_dev} (force host devices with "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count)")
    mesh = make_mesh((args.tp,), ("model",))
    log.info("tensor parallel: %d-way model mesh over %s devices",
             args.tp, mesh.devices.size)
    return sh.use_mesh(mesh)


def _warmup(engine, mode):
    """Compile every program the engine will run before the first request,
    so a lowering error stops startup instead of surfacing as a step fault
    (or a dead replica) inside the serve loop's retry ladder."""
    t0 = time.perf_counter()
    engine.warmup()
    log.info("%s: compiled in %.1f s", mode, time.perf_counter() - t0)


def _continuous_main(args, cfg, model, params):
    from repro.kernels import ops

    engine, mode = _build_serving(args, model, params)
    _warmup(engine, mode)
    # replica-count-agnostic reporting: a Router proxies metrics/summary;
    # per-engine internals (cache, prefill counters) read off replica 0
    eng0 = engine.replicas[0] if hasattr(engine, "replicas") else engine
    requests = make_requests(cfg, n_requests=args.requests, rate=args.rate,
                             prompt_len=args.prompt_len, gen=args.gen,
                             seed=args.seed, shared_prefix=args.shared_prefix)
    summary = serve_stream(engine, requests)
    log.info("%s: %d/%d requests, %d tokens in %.2f s (%.0f tok/s)",
             mode, summary["n_done"], summary["n_requests"],
             summary["total_tokens"], summary["elapsed_s"],
             summary["agg_tok_s"])
    log.info("ttft mean/p50/p95: %.0f/%.0f/%.0f ms; queue-wait p50/p95: "
             "%.0f/%.0f ms; e2e p50/p95: %.0f/%.0f ms; slot occupancy %.0f%%",
             summary["ttft_mean_s"] * 1e3, summary["ttft_p50_s"] * 1e3,
             summary["ttft_p95_s"] * 1e3, summary["queue_wait_p50_s"] * 1e3,
             summary["queue_wait_p95_s"] * 1e3, summary["e2e_p50_s"] * 1e3,
             summary["e2e_p95_s"] * 1e3, summary["occupancy_mean"] * 100)
    c = eng0.cache
    log.info("paged kv: page_size=%d, pool=%d pages/replica; allocated "
             "peak %.2f MB vs dense reservation %.2f MB; prefill tokens "
             "computed %d (+%d reused via prefix cache); prefill kv "
             "read %.2f MB [%s kernel]",
             c.page_size, c.n_pages,
             summary["kv_bytes_allocated_peak"] / 1e6,
             summary["kv_bytes_reserved"] / 1e6,
             eng0.n_prefill_tokens, eng0.n_prefill_tokens_skipped,
             summary["prefill_kv_bytes_read"] / 1e6,
             ops.prefill_backend())
    if eng0.spec_active:
        log.info("spec decode: k=%d, %.2f tokens/step, %.0f%% draft "
                 "acceptance", eng0.spec_k,
                 summary["tokens_per_step_mean"],
                 summary["draft_acceptance_rate"] * 100)
    if hasattr(engine, "replicas"):
        log.info("router: %d replicas (%d live), affinity hit rate %.0f%%, "
                 "%d handoffs, per-replica busy %s s",
                 len(engine.replicas), engine.n_live,
                 engine.metrics.affinity_hit_rate * 100,
                 engine.metrics.n_handoffs,
                 [round(b, 2) for b in engine.busy_s])
    res = eng0.resilience
    if res.injector is not None or summary["degradation_transitions"]:
        log.info("resilience: %s", res.summary())
    if args.chaos_verify:
        _chaos_verify(args, cfg, model, params, requests)


def _chaos_verify(args, cfg, model, params, chaos_requests):
    """Re-run the same request stream on a fault-free engine and demand
    that every request the chaos run completed normally produced the
    identical token sequence. Exits non-zero on any divergence — this is
    the CI proof that quarantine/retry never perturbs surviving traffic."""
    engine, _ = _build_serving(args, model, params, chaos=False)
    baseline = make_requests(cfg, n_requests=args.requests, rate=args.rate,
                             prompt_len=args.prompt_len, gen=args.gen,
                             seed=args.seed, shared_prefix=args.shared_prefix)
    serve_stream(engine, baseline)
    base = {r.id: list(r.generated) for r in baseline}
    aborted = [r.id for r in chaos_requests
               if r.finish_reason in ("fault", "deadline")]
    mismatched = [r.id for r in chaos_requests
                  if r.id not in aborted and list(r.generated) != base[r.id]]
    if mismatched:
        raise SystemExit(
            f"chaos-verify FAILED: requests {mismatched} diverged from the "
            "fault-free baseline")
    log.info("chaos-verify OK: %d/%d requests token-identical to fault-free "
             "baseline (%d aborted by injected faults)",
             len(chaos_requests) - len(aborted), len(chaos_requests),
             len(aborted))


def _http_main(args, cfg, model, params):
    """``--http``: serve real traffic over the asyncio SSE frontend
    instead of driving a synthetic request stream."""
    from repro.serve import server as server_lib

    engine, mode = _build_serving(args, model, params)
    _warmup(engine, mode)
    engine.metrics.clock = time.perf_counter
    log.info("http frontend over %s engine: %d slots, max_len %d",
             mode, engine.n_slots, engine.max_len)
    server_lib.run(engine, host=args.host, port=args.port,
                   queue_limit=args.queue_limit)


def _restore_latest(ckpt_dir, params, tag=""):
    """Restore ``params`` from the newest train checkpoint in ``ckpt_dir``."""
    from repro.checkpoint import checkpoint as ckpt_lib

    step = ckpt_lib.latest_step(ckpt_dir)
    if step is None:
        raise SystemExit(f"no checkpoint under {ckpt_dir}")
    params = ckpt_lib.restore(ckpt_dir, step, {"params": params})["params"]
    log.info("restored %sstep %d from %s", tag, step, ckpt_dir)
    return params


def _quantize_in_memory(model, params, mode):
    """Post-hoc quantization of an already-packed (model, params) pair."""
    from repro.core import export as export_lib
    from repro.kernels.quant import BITS

    params, report = export_lib.quantize_packed(model, params,
                                                bits=BITS[mode])
    log.info("quantized packed weights to %s: %d layers, "
             "max rel-rms err %.2e", mode, report["n_layers"],
             report["max_rel_rms"])
    model.quant_report = report
    return params


def _load_model(args):
    """Resolve (model, params) from the CLI: a packed export directory, a
    masked_dense train checkpoint folded on the fly, or random init —
    optionally quantized (``--quantize int8``)."""
    from repro.checkpoint import checkpoint as ckpt_lib

    over = {}
    if args.mpd_c:
        over["mpd_c"] = args.mpd_c
    if args.mpd_fuse:
        over["mpd_fuse"] = True
    cfg = get_config(args.arch, smoke=args.smoke, **over)

    if args.ckpt_dir and ckpt_lib.has_packed(args.ckpt_dir):
        # deployment artifact written by `train --fold-to-packed` /
        # export_packed: config + fold + perm-fusion + quantization all
        # recorded inside
        if over or args.fold_to_packed:
            log.info("note: packed export found — its recorded config "
                     "wins; ignoring --mpd-c/--mpd-fuse/--fold-to-packed")
        model, params = ckpt_lib.load_packed(args.ckpt_dir)
        stored_q = getattr(model, "quant_report", None)
        log.info("loaded packed export from %s/packed%s", args.ckpt_dir,
                 f" (quantized, {stored_q['bits']}-bit)" if stored_q else "")
        if args.quantize and not stored_q:
            params = _quantize_in_memory(model, params, args.quantize)
        elif args.quantize and stored_q:
            log.info("note: export already quantized (%d-bit) — its "
                     "stored form wins; ignoring --quantize %s",
                     stored_q["bits"], args.quantize)
        return model.cfg, model, params

    if args.fold_to_packed:
        import dataclasses
        cfg_md = dataclasses.replace(cfg, mpd_mode="masked_dense")
        model_md = build(cfg_md)
        params = model_md.init(jax.random.PRNGKey(0))
        if args.ckpt_dir:
            params = _restore_latest(args.ckpt_dir, params, "masked_dense ")
        model, params = model_md.to_packed(params, fuse=cfg.mpd_fuse,
                                           quantize=args.quantize or None)
        rep = getattr(model, "quant_report", None)
        log.info("folded to packed: %s params (was %s)%s",
                 f"{model.param_count():,}", f"{model_md.param_count():,}",
                 f", quantized {args.quantize} (max rel-rms err "
                 f"{rep['max_rel_rms']:.2e})" if rep else "")
        return model.cfg, model, params

    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        params = _restore_latest(args.ckpt_dir, params)
    if args.quantize:
        if cfg.mpd_mode != "packed":
            raise SystemExit("--quantize needs packed params: combine with "
                             "--fold-to-packed for a masked_dense run")
        params = _quantize_in_memory(model, params, args.quantize)
    return cfg, model, params


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCHS, required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--requests", type=int, default=16,
                   help="continuous-mode request count")
    p.add_argument("--rate", type=float, default=16.0,
                   help="continuous-mode Poisson arrival rate (req/s)")
    p.add_argument("--slots", type=int, default=4,
                   help="continuous-mode decode slots")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page")
    p.add_argument("--pages", type=int, default=0,
                   help="KV page-pool size; 0 = dense-equivalent "
                   "(n_slots * max_len / page_size + 1)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="prefill chunk tokens (page multiple); 0 = 4 pages")
    p.add_argument("--prefill-kernel", default="",
                   choices=("", "pallas", "interpret", "jnp"),
                   help="chunked-prefill attention backend: "
                   "pallas = flash paged-prefill kernel (TPU), interpret = "
                   "same kernel in Pallas interpret mode (CPU-testable, "
                   "slow), jnp = dense gather oracle (bitwise-stable "
                   "baseline, CPU default); empty = follow the global "
                   "kernel backend")
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="force the first N prompt tokens identical across "
                   "synthetic requests (exercises the prefix trie)")
    p.add_argument("--spec-draft", default="",
                   help="speculative decoding: directory "
                   "with a packed export to deploy as the draft model — "
                   "typically the target's own MPD-folded int8 artifact")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens proposed per verify window")
    p.add_argument("--http", action="store_true",
                   help="serve real traffic over HTTP/SSE (POST /v1/generate "
                   "streams tokens; GET /metrics, /healthz) instead of the "
                   "synthetic request stream")
    p.add_argument("--host", default="127.0.0.1", help="--http bind host")
    p.add_argument("--port", type=int, default=8000,
                   help="--http bind port (0 = ephemeral)")
    p.add_argument("--queue-limit", type=int, default=64,
                   help="--http admission-queue bound; beyond it new "
                   "requests get 429 + Retry-After")
    p.add_argument("--replicas", type=int, default=1,
                   help="data-parallel engine replicas behind the prefix-"
                   "affinity router (each with its own page pool, prefix "
                   "trie, and scheduler); 1 = plain single engine")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor parallelism: shard packed weights and the "
                   "paged attention kernels M-way over a 'model' mesh axis "
                   "(greedy output stays bit-identical to --tp 1)")
    p.add_argument("--disagg", action="store_true",
                   help="prefill/decode disaggregation (needs "
                   "--replicas >= 2): dedicated prefill replicas hand "
                   "requests to decode replicas at the first token, "
                   "migrating KV pages through the router")
    p.add_argument("--n-prefill", type=int, default=1,
                   help="--disagg: how many replicas take the prefill role "
                   "(the rest decode)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chaos-schedule", default="",
                   help="deterministic fault injection: a builtin schedule "
                   "name ('storm'), inline JSON list of fault specs, or "
                   "@file.json; faults fire at exact engine steps, keyed by "
                   "--chaos-seed")
    p.add_argument("--chaos-seed", type=int, default=0,
                   help="seed for injected fault values / retry jitter")
    p.add_argument("--chaos-verify", action="store_true",
                   help="after the chaos run, replay the identical request "
                   "stream on a fault-free engine and exit non-zero unless "
                   "every normally-completed request is token-identical")
    p.add_argument("--mpd-c", type=int, default=0, help="0 = config default")
    p.add_argument("--mpd-fuse", action="store_true",
                   help="Fig-3 permutation fusion (fused packed FFN kernel)")
    p.add_argument("--ckpt-dir", default="",
                   help="restore params; a packed/ export inside is "
                   "deployed directly")
    p.add_argument("--fold-to-packed", action="store_true",
                   help="treat the checkpoint (or init) as masked_dense and "
                   "fold it to packed before serving (paper Eq. 2)")
    p.add_argument("--quantize", choices=("int8", "int4"), default="",
                   help="serve int8-weight packed kernels (int4 = 4-bit "
                   "weights, nibble-packed at rest and unpacked to int8 at "
                   "deploy; scales stay f32); a quantized packed export "
                   "deploys its stored form automatically")
    return p


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache

    args = build_parser().parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    cfg0 = get_config(args.arch, smoke=args.smoke)
    if not cfg0.causal:
        raise SystemExit(f"{args.arch} is encoder-only (no decode)")
    if args.prefill_kernel:
        # must happen before the engine builds/warms its jits — the
        # backend is read at trace time
        from repro.kernels import ops
        ops.set_prefill_backend(args.prefill_kernel)
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    if args.tp < 1:
        raise SystemExit(f"--tp must be >= 1, got {args.tp}")
    if args.disagg and args.replicas < 2:
        raise SystemExit("--disagg needs --replicas >= 2 (dedicated "
                         "prefill and decode replicas)")
    if args.disagg and args.spec_draft:
        raise SystemExit("--disagg cannot combine with --spec-draft (the "
                         "draft page pool is not migrated)")
    if args.chaos_verify and not args.chaos_schedule:
        raise SystemExit("--chaos-verify needs --chaos-schedule")
    if args.chaos_verify and args.http:
        raise SystemExit("--chaos-verify drives the synthetic stream; it "
                         "cannot combine with --http")
    try:
        cfg, model, params = _load_model(args)
    except SystemExit:
        raise
    except Exception as e:
        # startup must fail with one clear line, never a traceback wall —
        # a corrupt packed artifact lands here as ArtifactCorruptError
        raise SystemExit(f"startup failed: {type(e).__name__}: {e}")
    log.info("serving %s: %s params (mode=%s)", cfg.name,
             f"{model.param_count():,}", cfg.mpd_mode)

    # the mesh context stays active for the whole serving lifetime:
    # engines capture it at construction and re-enter it per step
    with _mesh_ctx(args):
        if args.http:
            _http_main(args, cfg, model, params)
        else:
            _continuous_main(args, cfg, model, params)


if __name__ == "__main__":
    main()
