"""One run of one benchmark cell: set-up, the measured window, the
per-layer readings and the comparison with the plain reference.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything it
names is found by name: ``bench/configs/<config>.json`` (the model as run),
``bench/traffic/<traffic>.json`` (the mix, read by ``gen.py``, with the
engine's sizing) and ``bench/metrics/<metric>.py`` (one reader per
per-layer metric). Adding a configuration, a mix, a metric or a cell adds
files and entries; nothing here changes.

The served path is the one ``python -m repro.launch.serve --paged`` builds:
the model from ``configs/common.py``, ``Engine(paged=True)`` with the
degradation ladder, ``PagedCache``, ``Model.prefill_chunk`` /
``decode_step`` on the Pallas kernels. The harness drives ``Engine.submit``
and ``Engine.step`` itself and stamps every token through
``Engine.token_cb``.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

import gen            # noqa: E402  (bench/ is on sys.path beside this file)
import reference      # noqa: E402
import trace_reduce   # noqa: E402
import weights        # noqa: E402

# spans the drive loop writes into the profiler's trace
SPANS = ("bench.submit", "bench.step", "bench.idle")


class BenchError(Exception):
    """A run that cannot produce a result: it exits non-zero, printing
    none."""


# ------------------------------------------------------------------- cells
def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``name`` with its configuration, mix and metric entries."""
    bm = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    return SimpleNamespace(
        name=name, chips=cell["chips"],
        config=load_json(root / entry["file"]),
        mix=load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[m for m in bm["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if applies(m, name)])


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- the device
def require_chip(chips: int):
    """The devices of a TPU host with at least ``chips`` chips on the Pallas
    kernel route; anything else is an error, never a CPU fallback."""
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if ops.get_backend() != "pallas":
        raise BenchError(f"kernel route is {ops.get_backend()!r}, not pallas")
    return devs


def enable_cache() -> None:
    """Persistent compilation cache in ``<checkout>/.jax_cache`` (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, however
    quick to compile, so a warm run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Stages:
    """Set-up progress on standard error: seconds since process start, and
    the compilations and persistent-cache reads JAX reported since the
    last stage."""

    def __init__(self, t_start: float):
        import jax.monitoring

        self.t_start, self.events = t_start, collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, *args, **kwargs) -> None:
        if "compil" in event or "cache" in event:
            self.events[event.rsplit("/", 1)[-1]] += 1

    def __call__(self, what: str) -> None:
        counts = ", ".join(f"{k} {v}" for k, v in sorted(self.events.items()))
        print(f"[{time.perf_counter() - self.t_start:7.1f} s] {what}"
              f"{'  (' + counts + ')' if counts else ''}", file=sys.stderr,
              flush=True)
        self.events.clear()


# ------------------------------------------------------------ model, engine
def build_model(config: Dict):
    """The program's model for a configuration file, and the layout of the
    parameters it serves (int8 packed leaves where the file says so)."""
    import jax

    from repro.configs.common import get_config
    from repro.models import build

    cfg = get_config(config["arch"], **config["model"])
    for k, v in config["model"].items():
        if getattr(cfg, k) != v:
            raise BenchError(f"config {config['name']}: {k} runs as "
                             f"{getattr(cfg, k)!r}, the file says {v!r}")
    model = build(cfg)
    layout = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if config.get("quantize"):
        from repro.core.export import quantize_packed
        from repro.kernels.quant import BITS

        bits = BITS[config["quantize"]]
        layout = jax.eval_shape(
            lambda p: quantize_packed(model, p, bits=bits,
                                      compute_report=False)[0], layout)
    return model, layout


def build_engine(model, params, mix: Dict):
    """``Engine(paged=True)`` as the launcher builds it. The model's
    ``init_paged_caches`` runs as one compiled call, whatever its arguments:
    run eagerly, it holds the per-layer pools, their stacked copies and the
    result at once, some 2.5 pools, which a 6.4 GB pool cannot have on a
    16 GB chip."""
    import jax

    from repro.serve import DegradationLadder, Engine, Resilience

    if "init_paged_caches" not in vars(model):
        eager = model.init_paged_caches
        model.init_paged_caches = (
            lambda *a, **k: jax.jit(lambda: eager(*a, **k))())
    e = mix["engine"]
    return Engine(model, params, n_slots=e["slots"], max_len=e["max_len"],
                  paged=True, page_size=e["page_size"], n_pages=e.get("pages"),
                  prefill_chunk_tokens=e["prefill_chunk"],
                  resilience=Resilience(ladder=DegradationLadder()))


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def cell_widths(engine, mix: Dict):
    """Decode and prefill block-table widths this mix can reach: decode
    from the shortest request's first step to ``max_len``; prefill every
    rung a chunk can attend over."""
    shortest = mix["prompt"]["min"] + 1
    lo = min(_pow2(engine.cache.pages_for(shortest)), engine.cache.max_pages)
    return ([w for w in engine.decode_widths() if w >= lo],
            engine.prefill_widths())


def warm(engine, mix: Dict) -> None:
    """Compile the programs of this cell's widths only, on the null page so
    no state moves. The one place the benchmark reaches below the engine's
    public API: ``Engine.warmup`` compiles every rung and does not wait
    between programs, while each returns a whole new page pool (the pool
    is not donated), so the harness calls the jitted decode and chunk
    programs itself, waiting for and dropping each output before the next:
    no more than one extra pool is ever alive. A ``widths`` argument to
    ``Engine.warmup`` and a donated pool would let this call ``warmup``."""
    import jax
    import jax.numpy as jnp

    from repro.serve import sampling

    dec, pre = cell_widths(engine, mix)
    n = engine.n_slots
    for w in dec:
        jax.block_until_ready(engine._decode_paged(
            engine.params, engine.cache.caches, engine._dev,
            jnp.zeros((n, w), jnp.int32), jnp.zeros((n,), bool),
            engine._zero_poison))
    toks = jnp.zeros((1, engine.chunk_tokens), jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    for w in pre:
        for final in (False, True):
            jax.block_until_ready(engine._chunk(
                engine.params, engine.cache.caches, engine._dev, toks,
                jnp.zeros((w,), jnp.int32), zero, zero,
                jnp.ones((), jnp.int32), jnp.zeros((), jnp.float32), zero,
                sampling.base_key(0), final=final))


# ------------------------------------------------------------- the window
@dataclasses.dataclass
class Rec:
    id: int
    due: float
    n_prompt: int
    max_new: int
    submit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    reason: Optional[str] = None


class Tracer:
    """Profiler trace of a slice of the window, in a run of its own.
    Starting and stopping the profiler holds the drive loop for up to
    seconds; ``stalls`` keeps those spans, so host readers can leave out the
    requests that fell due in them."""

    def __init__(self, out_dir: Path, offset: float, length: float):
        self.dir, self.offset, self.length = out_dir, offset, length
        self.started = self.stopped = False
        self.stalls: List[tuple] = []

    def _timed(self, now: float, call) -> None:
        t = time.perf_counter()
        call()
        self.stalls.append((now, now + time.perf_counter() - t))

    def poll(self, now: float, t_open: Optional[float]) -> None:
        import jax

        if t_open is None or self.stopped:
            return
        if not self.started and now >= t_open + self.offset:
            # device ops and the harness's own spans; no Python call tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self._timed(now, lambda: jax.profiler.start_trace(
                str(self.dir), profiler_options=opts))
            self.started, self.t_start = True, now
        elif self.started and now >= self.t_start + self.length:
            self._timed(now, jax.profiler.stop_trace)
            self.stopped = True

    def close(self) -> None:
        import jax

        if self.started and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True


def drive(engine, reqs: List[gen.Req], mix: Dict, seconds: float,
          tracer: Optional[Tracer] = None, clock=time.perf_counter,
          wait_s: float = 60.0) -> SimpleNamespace:
    """Serve ``reqs`` open loop from their due times and measure a window
    of ``seconds``: from the mix's opening due-time, or from when the first
    requests have left every slot, to ``seconds`` later. Returns the
    records, the window's bounds and the engine's counters at its edges."""
    import jax

    from repro.serve import Request, SamplingParams

    recs = {r.id: Rec(r.id, r.due, len(r.prompt), r.max_new) for r in reqs}
    objs = {r.id: Request(id=r.id, prompt=r.prompt, max_new_tokens=r.max_new,
                          sampling=SamplingParams(temperature=0.0),
                          arrival_time=r.due) for r in reqs}
    pending = collections.deque(sorted(reqs, key=lambda r: (r.due, r.id)))
    first_slots = [r.id for r in list(pending)[:engine.n_slots]]
    t0 = clock()
    engine.metrics.clock = lambda: clock() - t0

    def on_token(req, tok, idx):
        rec = recs[req.id]
        if idx == len(rec.times):
            rec.times.append(clock() - t0)

    def on_done(req):
        rec = recs[req.id]
        rec.done, rec.reason = clock() - t0, req.finish_reason

    engine.token_cb, engine.done_cb = on_token, on_done

    def counters():
        return {"prefill": engine.n_prefill_tokens,
                "skipped": engine.n_prefill_tokens_skipped,
                "occ": len(engine.metrics._occupancy)}

    open_at = gen.window_open(mix)
    t_open = t_close = None
    at_open = at_close = None
    ann = jax.profiler.TraceAnnotation
    while True:
        now = clock() - t0
        while pending and pending[0].due <= now:
            r = pending.popleft()
            with ann("bench.submit"):
                engine.submit(objs[r.id])
            recs[r.id].submit = clock() - t0
        if t_open is None and (
                (open_at is not None and now >= open_at)
                or (open_at is None
                    and all(recs[i].done is not None for i in first_slots))):
            t_open = now if open_at is None else open_at
            at_open = counters()
        elif t_open is not None and t_close is None \
                and now >= t_open + seconds:
            t_close, at_close = t_open + seconds, counters()
        if tracer is not None:
            tracer.poll(now, t_open)
        if t_close is not None:
            due_in = [r for r in recs.values() if t_open <= r.due < t_close]
            if (mix["arrival"]["kind"] == "backlog"
                    or all(r.times or r.reason for r in due_in)
                    or now > t_close + wait_s):
                break
        if engine.has_work():
            with ann("bench.step"):
                engine.step()
        else:
            with ann("bench.idle"):
                gap = pending[0].due - now if pending else 0.0005
                time.sleep(min(max(gap, 0.0), 0.0005))
    if tracer is not None:
        tracer.close()
    jax.block_until_ready(engine.cache.caches)
    qwait = {i: m.queue_wait for i, m in engine.metrics.requests.items()}
    occ = engine.metrics._occupancy[at_open["occ"]:at_close["occ"]]
    return SimpleNamespace(recs=list(recs.values()), t0=t0, t_open=t_open,
                           t_close=t_close, at_open=at_open,
                           at_close=at_close, queue_wait=qwait,
                           occupancy=occ, objs=objs,
                           stalls=tracer.stalls if tracer else [])


# -------------------------------------------------------- end-to-end metrics
def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) if len(
        values) else None


def in_window(run, t: float) -> bool:
    return run.t_open <= t < run.t_close


def window_samples(run):
    """Tokens emitted in the window, every gap between two of a request's
    tokens both inside it (ms), and every first-token time of a request due
    inside it, from its due time (ms)."""
    tokens, gaps, ttft = 0, [], []
    for r in run.recs:
        tokens += sum(1 for t in r.times if in_window(run, t))
        gaps += [(b - a) * 1e3 for a, b in zip(r.times, r.times[1:])
                 if in_window(run, a) and in_window(run, b)]
        if in_window(run, r.due) and r.times:
            ttft.append((r.times[0] - r.due) * 1e3)
    return tokens, gaps, ttft


def end_to_end(run, setup_s: float) -> Dict[str, Optional[float]]:
    """Every end-to-end metric this harness knows, over the whole window."""
    tokens, gaps, ttft = window_samples(run)
    return {"tok_s": tokens / (run.t_close - run.t_open),
            "itl_p95_ms": pct(gaps, 95), "ttft_p95_ms": pct(ttft, 95),
            "setup_s": setup_s}


def attempted_failed(run):
    touched = [r for r in run.recs
               if in_window(run, r.due) and r.submit is not None
               or any(in_window(run, t) for t in r.times)]
    failed = [r for r in touched if r.reason is not None
              or (in_window(run, r.due) and not r.times)]
    return len(touched), len(failed)


# ------------------------------------------------------------ correctness
def sample_finished(run, n: int, seed: int) -> List[Rec]:
    """``n`` requests finished normally by the close, drawn from the seed,
    the one with most served tokens among them."""
    done = [r for r in run.recs
            if r.done is not None and r.reason is None and r.done < run.t_close
            and len(r.times) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.max_new, r.n_prompt, -r.id))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng((int(seed) & ((1 << 64) - 1), 7))
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(model_key: str, params, prompt, served, max_len: int,
               fmt=None):
    """For each served token: the reference's best logit minus its logit
    for the served token (0 where they agree). With ``fmt``, instead the
    gap of the token that the reference computed in that lower precision
    puts first at each position, over the same prompt and tokens."""
    import jax.numpy as jnp

    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    padded = np.zeros((max_len,), np.int32)
    padded[:len(seq)] = seq
    pos = np.arange(len(served)) + len(prompt) - 1
    ref = reference.forward(model_key, params, padded)[pos]
    if fmt is None:
        pick = jnp.asarray(np.asarray(served, np.int32))
    else:
        low = reference.forward(model_key, params, padded, fmt=fmt)[pos]
        pick = jnp.argmax(low, axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return np.asarray(ref.max(axis=-1) - got)


def compare(config: Dict, params, run, max_len: int, seed: int,
            fmt=None) -> Dict:
    key = reference.register(config["name"], config["model"])
    sample = sample_finished(run, config["check"]["sample_requests"], seed)
    gaps = [logit_gaps(key, params, run.objs[r.id].prompt,
                       run.objs[r.id].generated, max_len, fmt)
            for r in sample]
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_logit_gap": float(g.max()) if len(g) else None,
            "mean_logit_gap": float(g.mean()) if len(g) else None,
            "mismatch_share": float((g > 0).mean()) if len(g) else None,
            "requests": len(sample), "tokens": len(g)}


# ----------------------------------------------------------------- the run
def cell_run(cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None, out_dir: Optional[Path] = None,
             hooks: Optional[Callable] = None) -> Dict:
    """Set up, measure and check one run; returns the result line's dict.
    ``hooks(engine)`` lets a test break the timed path underneath."""
    import jax

    import peaks as peaks_lib

    config, mix = cell.config, cell.mix
    stage = Stages(t_start)
    model, layout = build_model(config)
    stage("model built")
    params = weights.make_params(layout, seed)
    jax.block_until_ready(params)
    stage("weights made")
    engine = build_engine(model, params, mix)
    if hooks is not None:
        hooks(engine)
    stage("engine built")
    warm(engine, mix)
    stage("programs warm")
    reqs = gen.generate(mix, seed, seconds, config["model"]["vocab"])
    tracer = None
    if trace:
        out_dir = out_dir or ROOT / ".bench_out"
        tdir = out_dir / f"trace-{cell.name}"
        if tdir.exists():
            import shutil
            shutil.rmtree(tdir)
        tracer = Tracer(tdir, offset=min(10.0, 0.25 * seconds),
                        length=min(5.0, 0.5 * seconds))
    run = drive(engine, reqs, mix, seconds, tracer)
    stage("window closed")
    setup_s = run.t0 + run.t_open - t_start
    dev = (devices or jax.devices())[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices or jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    attempted, failed = attempted_failed(run)
    e2e = end_to_end(run, setup_s)
    tokens, gaps, ttft = window_samples(run)
    print(f"window: {tokens} tokens, {len(gaps)} gaps (p50 {pct(gaps, 50)} "
          f"ms), {len(ttft)} first tokens (p50, p85, p90: "
          f"{[pct(ttft, q) for q in (50, 85, 90)]} ms)", file=sys.stderr)
    red = None
    if trace:
        tr = trace_reduce.from_xplane(str(tracer.dir), host_names=SPANS)
        spans = [h for h in tr["host"] if h[0] in SPANS]
        lo = min(s for _, s, _ in spans)
        hi = max(s + d for _, s, d in spans)
        red = trace_reduce.reduce(tr, lo, hi)
        device["busy_s"] = trace_reduce.busy_s(red)
        device["window_s"] = trace_reduce.window_s(red)
    ctx = SimpleNamespace(run=run, config=config, model=config["model"],
                          quantize=config.get("quantize"), mix=mix,
                          red=red, seconds=run.t_close - run.t_open,
                          peaks=(peaks_lib.peaks(dev.platform,
                                                 dev.device_kind)
                                 if dev.platform == "tpu" else None))
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    # the program's state goes before the reference runs
    engine.cache.caches = engine._dev = None
    del engine
    gc.collect()
    cmp = compare(config, params, run, mix["engine"]["max_len"], seed)
    checks = {k: {"value": cmp[k], "limit": lim}
              for k, lim in config["check"]["limits"].items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if red is not None:
        out["breakdown"] = trace_reduce.breakdown(red)
    out["checks"] = checks
    print(f"compared {cmp['tokens']} served tokens of {cmp['requests']} "
          f"requests with the float32 reference", file=sys.stderr)
    return out
