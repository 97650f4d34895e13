"""The engine's profiler spans, the model programs' layer-scan scopes and
the compile counter.

* **Spans** — a tiny paged engine served under ``jax.profiler`` writes the
  spans of ``repro.serve.engine.SPANS``, each nested under ``engine.step``
  in the order the step runs them, at most one of each per step except
  the prefill chunks, with their ``step`` / ``req`` arguments; the
  benchmark's trace reduction (``bench/trace_reduce.py``) still keeps the
  spans a caller wraps around the step.
* **Scopes** — in the compiled decode and chunk programs the ops the layer
  scan adds carry ``layer_scan`` and not ``layer``; the body's carry both.
  None of the scan's own ops moves a layer's page pool.
* **KV tokens** — ``kv_tokens`` on the decode prep span, and
  ``ServeMetrics.kv_tokens_attended``, are the lengths the decode
  attention kernel is handed, summed over live rows.
* **Compiles** — ``ServeMetrics.n_compiles`` rises on a new decode width
  and not on a repeat.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.serve import Engine, ServeMetrics, sampling
from repro.serve.engine import SPANS

from test_serve_paged import _model, _requests
from test_serve_spec import _drafts

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import trace_marks  # noqa: E402
import trace_reduce  # noqa: E402

# the order a step runs its spans in; prefill chunks and the final chunk's
# token read interleave
RANK = {"engine.admit": 0, "engine.prefill.chunk": 1,
        "engine.prefill.sync": 1, "engine.decode.prep": 2,
        "engine.decode.dispatch": 3, "engine.decode.sync": 4,
        "engine.emit": 5}


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.mark.parametrize("spec", [False, True], ids=["paged", "spec"])
def test_engine_spans_nest_under_step(tmp_path, spec):
    m, p = _model("olmo-1b")
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 prefill_chunk_tokens=8,
                 spec_draft=_drafts("olmo-1b")["perfect"] if spec else None)
    reqs = _requests(m.cfg, 4, seed=3, max_prompt=24)
    for r in reqs:
        eng.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    n_steps = 0
    try:
        while eng.has_work():
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
            n_steps += 1
    finally:
        jax.profiler.stop_trace()

    names = set(SPANS) | {"bench.step"}
    host = trace_reduce.from_xplane(str(tmp_path), host_names=names)["host"]
    bench = [h for h in host if h[0] == "bench.step"]
    steps = [h for h in host if h[0] == "engine.step"]
    assert len(bench) == len(steps) == n_steps
    assert all(any(_inside(s, b) for b in bench) for s in steps)
    seen = {h[0] for h in host}
    assert seen == names, names - seen
    decodes = 0
    for s in steps:
        kids = sorted((h for h in host if h[0] in RANK and _inside(h, s)),
                      key=lambda h: h[1])
        ranks = [RANK[h[0]] for h in kids]
        assert ranks == sorted(ranks), [h[0] for h in kids]
        assert kids[0][0] == "engine.admit"
        once = [h[0] for h in kids if h[0] != "engine.prefill.chunk"]
        assert len(once) == len(set(once)), once    # none per slot or token
        decodes += "engine.decode.dispatch" in once
    assert decodes > 0
    # every span but the step's own lies inside one step
    assert all(any(_inside(h, s) for s in steps)
               for h in host if h[0] in RANK)

    xplane = next(tmp_path.glob("**/*.xplane.pb"))
    args = {}
    for name, _, _, a in trace_marks.host_spans(str(xplane), set(SPANS)):
        args.setdefault(name, []).append(a)
    assert [a["step"] for a in args["engine.step"]] == list(range(n_steps))
    assert {a["req"] for a in args["engine.prefill.sync"]} == \
        {r.id for r in reqs}
    assert all({"req", "pos", "n"} <= set(a)
               for a in args["engine.prefill.chunk"])
    assert all({"live", "width"} <= set(a)
               for a in args["engine.decode.prep"])
    assert all("n" in a for a in args["engine.admit"] + args["engine.emit"])
    assert sum(a["n"] for a in args["engine.admit"]) == len(reqs)


@pytest.mark.parametrize("spec", [False, True], ids=["paged", "spec"])
def test_kv_tokens_are_the_decode_kernel_lengths(tmp_path, spec):
    """``kv_tokens`` on each ``engine.decode.prep`` span is the sum over
    live rows of the lengths the step's attention kernel is handed (plain:
    the cache ``pos`` + 1; spec: the verify window's end, ``pos0`` + k +
    1), read here from the arguments of the dispatched program; the
    metrics counter and its Prometheus sample hold the same total."""
    import numpy as np

    m, p = _model("olmo-1b")
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 prefill_chunk_tokens=8,
                 spec_draft=_drafts("olmo-1b")["perfect"] if spec else None)
    handed = []
    decode, verify = eng._decode_paged, getattr(eng, "_verify", None)

    def on_decode(params, caches, dev, bt, live, poison):
        pos = np.asarray(caches[0]["pos"][0])
        handed.append(int((pos + 1)[np.asarray(live)].sum()))
        return decode(params, caches, dev, bt, live, poison)

    def on_verify(params, caches, dev, bt, live, pos0, toks, q, poison):
        window = toks.shape[1] + 1
        handed.append(int((np.asarray(pos0) + window)[np.asarray(live)]
                          .sum()))
        return verify(params, caches, dev, bt, live, pos0, toks, q, poison)

    eng._decode_paged = on_decode
    if spec:
        eng._verify = on_verify
    for r in _requests(m.cfg, 4, seed=5, max_prompt=24):
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()

    xplane = next(tmp_path.glob("**/*.xplane.pb"))
    preps = trace_marks.host_spans(str(xplane), {"engine.decode.prep"})
    got = [a["kv_tokens"] for _, _, _, a in preps]
    assert handed and got == handed
    assert eng.metrics.kv_tokens_attended == sum(handed)
    assert (f"repro_serve_kv_tokens_attended_total {sum(handed)}"
            in eng.metrics.prometheus())


def _engine():
    m, p = _model("olmo-1b")
    return Engine(m, p, n_slots=2, max_len=64, page_size=8,
                  prefill_chunk_tokens=8)


def _compiled_text(eng, program):
    if program == "decode":
        return eng.lower_decode(2).compile().as_text()
    zero = jnp.zeros((), jnp.int32)
    with eng._mesh_scope():
        return eng._chunk.lower(
            eng.params, eng.cache.caches, eng._dev,
            jnp.zeros((1, eng.chunk_tokens), jnp.int32),
            jnp.zeros((2,), jnp.int32), zero, zero, jnp.ones((), jnp.int32),
            jnp.zeros((), jnp.float32), zero, sampling.base_key(0),
            final=True).compile().as_text()


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_layer_scan_scopes(program):
    eng = _engine()
    ops = re.findall(r'^\s*(?:ROOT\s+)?%?\S+\s*=\s*(\S+)\s.*'
                     r'\bop_name="([^"]*)"', _compiled_text(eng, program),
                     re.M)
    scopes = [s for _, s in ops]
    scan_only = [s for s in scopes if trace_marks.scan_only(s)]
    body = [s for s in scopes if "layer" in s.split("/")]
    # the scan slices each layer's parameters and ``pos`` row in and stacks
    # ``pos`` back; the page pool rides the carry, and the body reads only
    # the pages its block tables name, so no op, the scan's own or the
    # body's, has the shape of one layer's pool
    assert any(s.endswith("/dynamic_slice") for s in scan_only), scan_only
    assert any(s.endswith("/dynamic_update_slice") for s in scan_only)
    assert any("dot_general" in s for s in body)
    assert not any("dot_general" in s for s in scan_only)
    dims = ",".join(map(str, eng.cache.caches[0]["kp"].shape[1:]))
    layer_pool = re.compile(rf"\[(?:1,)?{dims}\]")
    assert not [(shape, s) for shape, s in ops if layer_pool.search(shape)]


def test_compile_counter_rises_on_new_width_only():
    eng = _engine()
    n = eng.n_slots

    def decode(width):
        jax.block_until_ready(eng._decode_paged(
            eng.params, eng.cache.caches, eng._dev,
            jnp.zeros((n, width), jnp.int32), jnp.zeros((n,), bool),
            eng._zero_poison))
        return eng.metrics.n_compiles

    first = decode(1)
    assert first >= 1
    assert decode(1) == first
    assert decode(2) > first
    # a metrics object counts from when it was made
    assert ServeMetrics().n_compiles == 0
