"""Granite-8B-Code as the benchmark serves it (group-4 GQA, a bias on every
projection, ``rope_theta`` 1e7), at smoke widths on seeded random weights
on the CPU, against the plain float32 reference (``bench/reference.py``);
and the ``paged_attention_roofline`` reader on a small synthetic trace.

Tolerances: the program and the reference both compute in float32 and
differ only in summation order (packed blocks and permutation gathers
against one dense matmul; an online softmax over pages against one softmax
on the Pallas route), so their logits agree to float32 rounding: 2e-4 of
the largest logit, as ``test_reference.py`` holds the full forward (read
here: 3e-7 to 5e-7 on three seeds, on both routes). Zeroing the biases
moves the logits by 17% to 32% of the largest logit and fp8 weights by
about 5%, so either departure is seen.
"""

import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import attn_bytes
import gen
import harness
import peaks
import reference
import trace_reduce as tr
import weights
from conftest import BENCH, smoke_config, smoke_mix

CONFIG = "granite-8b-code.c8.bf16"
TOL = 2e-4          # of the largest logit: float32 summation order


def _far(got, want):
    """The widest logit gap over the largest logit."""
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def granite():
    cfg = smoke_config(CONFIG)
    m = cfg["model"]
    assert m["n_heads"] == 4 * m["n_kv_heads"] and m["use_bias"]
    assert m["rope_theta"] == 1e7
    model, layout = harness.build_model(cfg)
    params = weights.make_params(layout, 2**33 + 17)
    key = reference.register(cfg["name"], m)
    return cfg, model, layout, params, key


def _zero_biases(params):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if getattr(path[-1], "key", None) == "b"
        else a, params)


def test_full_forward_matches_the_reference(granite):
    cfg, model, _, params, key = granite
    toks = np.random.default_rng(1).integers(0, cfg["model"]["vocab"], 40)
    toks = toks.astype(np.int32)
    want = np.asarray(reference.forward(key, params, toks))
    got = np.asarray(model.logits(params, toks[None]))[0]
    assert _far(got, want) < TOL
    # the check sees the biases: the same forward with them zeroed
    bare = np.asarray(model.logits(_zero_biases(params), toks[None]))[0]
    assert _far(bare, want) > 10 * TOL


class _Tap:
    """The model as the engine sees it, keeping the logits of every final
    prefill chunk and decode step (``jax.debug.callback`` inside the
    engine's jitted programs)."""

    def __init__(self, model):
        self._model, self.last = model, {}

    def __getattr__(self, name):
        return getattr(self._model, name)

    def _keep(self, kind, logits):
        def put(x):
            self.last[kind] = np.asarray(x)
        jax.debug.callback(put, logits)

    def prefill_chunk(self, *args, final=True, **kw):
        logits, caches = self._model.prefill_chunk(*args, final=final, **kw)
        if final:
            self._keep("chunk", logits)
        return logits, caches

    def decode_step(self, *args, **kw):
        logits, caches = self._model.decode_step(*args, **kw)
        self._keep("decode", logits)
        return logits, caches


def _serve(model, params, backend):
    """Serve four requests through ``Engine(paged=True)`` as the harness
    builds it (prompts of 20-40 tokens in chunks of 16, pages of 8, up to
    12 tokens out, greedy) and return, per request, its prompt, its tokens
    and the logits each token was drawn from."""
    from repro.kernels import ops
    from repro.serve import Request, SamplingParams

    mix = {"engine": {"slots": 4, "max_len": 64, "page_size": 8,
                      "prefill_chunk": 16}}
    saved = ops._BACKEND
    ops.set_backend(backend)
    try:
        engine = harness.build_engine(model, params, mix)
        tap = engine.model = _Tap(model)
        served = {}

        def on_token(req, tok, idx):
            row = (tap.last["chunk"][0] if idx == 0
                   else tap.last["decode"][req.slot])
            served[req.id][1].append(row)

        engine.token_cb = on_token
        rng = np.random.default_rng(3)
        for i in range(4):
            prompt = rng.integers(0, model.cfg.vocab,
                                  int(rng.integers(20, 41))).astype(np.int32)
            req = Request(id=i, prompt=prompt,
                          max_new_tokens=int(rng.integers(6, 13)),
                          sampling=SamplingParams(temperature=0.0))
            served[i] = (req, [])
            engine.submit(req)
        while engine.has_work():
            engine.step()
    finally:
        ops._BACKEND = saved
    return [(r.prompt, r.generated, np.stack(rows))
            for r, rows in served.values()]


def _reference_rows(key, params, prompt, generated, fmt=None):
    seq = np.concatenate([prompt, np.asarray(generated[:-1], np.int32)])
    ref = np.asarray(reference.forward(key, params, seq, fmt=fmt))
    return ref[len(prompt) - 1:]


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_served_path_matches_the_reference(granite, backend):
    """Chunked prefill (non-final and final chunks) then decode through
    the page pool, compared on the logits every served token was drawn
    from with the reference's full forward over the same tokens."""
    cfg, model, _, params, key = granite
    runs = _serve(model, params, backend)
    assert any(len(p) > 32 for p, _, _ in runs)      # three chunks
    for prompt, generated, rows in runs:
        assert len(rows) == len(generated)
        want = _reference_rows(key, params, prompt, generated)
        assert _far(rows, want) < TOL
        np.testing.assert_array_equal(rows.argmax(-1), generated)


def test_zero_biases_and_the_control_fail(granite):
    """Served with its biases zeroed, the program departs from the
    reference by more than the tolerance; so does the reference computed
    with fp8 weights (the configuration's control)."""
    cfg, model, _, params, key = granite
    for prompt, generated, rows in _serve(model, _zero_biases(params),
                                          "jnp"):
        want = _reference_rows(key, params, prompt, generated)
        assert _far(rows, want) > 10 * TOL
    fmt = cfg["check"]["control"]
    assert fmt == "fp8"
    toks = np.random.default_rng(2).integers(0, cfg["model"]["vocab"], 40)
    toks = toks.astype(np.int32)
    want = np.asarray(reference.forward(key, params, toks))
    low = np.asarray(reference.forward(key, params, toks, fmt=fmt))
    assert _far(low, want) > 10 * TOL


# ------------------------------------------- the cell's own check, at smoke
def _own_check():
    """The cell at smoke size, judged by the configuration's own limits
    (``mean_logit_gap``), not the smoke limit conftest gives every
    configuration."""
    cfg = smoke_config(CONFIG)
    with open(BENCH / "configs" / f"{CONFIG}.json") as f:
        cfg["check"]["limits"] = json.load(f)["check"]["limits"]
    assert set(cfg["check"]["limits"]) == {"mean_logit_gap"}
    return cfg, smoke_mix("backlog-short")


def _serve_zeroed_biases(engine):
    engine.params = _zero_biases(engine.params)


@pytest.mark.parametrize("fault", [None, _serve_zeroed_biases],
                         ids=["sound", "zeroed-biases"])
def test_cell_check_sees_the_biases(fault, tmp_path):
    """A whole run through ``harness.cell_run``: sound, it is correct under
    the cell's limit; served with its biases zeroed (the reference keeps
    them), the same limit finds it not correct."""
    cfg, mix = _own_check()
    cell = SimpleNamespace(name="smoke", chips=1, config=cfg, mix=mix,
                           end_to_end=[], per_layer=[])
    out = harness.cell_run(cell, 2**31 + 77, 2.0, False, time.perf_counter(),
                           out_dir=tmp_path, hooks=fault)
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] is not None
    if fault is None:
        assert out["correct"] and out["failed"] == 0
    else:
        assert not out["correct"] and gap["value"] > gap["limit"]


def test_cell_check_fails_the_control():
    """The fp8 control, the reference computed with fp8 weights in the
    program's place, is not correct under the cell's limit on the served
    tokens of three seeds; the program on the same tokens is."""
    cfg, mix = _own_check()
    limits, fmt = cfg["check"]["limits"], cfg["check"]["control"]
    model, layout = harness.build_model(cfg)
    max_len = mix["engine"]["max_len"]
    for seed in (1, 2, 3):
        params = weights.make_params(layout, seed)
        engine = harness.build_engine(model, params, mix)
        harness.warm(engine, mix)
        reqs = gen.generate(mix, seed, 2.0, cfg["model"]["vocab"])
        run = harness.drive(engine, reqs, mix, 2.0)
        sound = harness.compare(cfg, params, run, max_len, seed)
        ctl = harness.compare(cfg, params, run, max_len, seed, fmt=fmt)
        # harness.cell_run's rule: every limit holds
        assert all(sound[k] <= lim for k, lim in limits.items())
        assert not all(ctl[k] <= lim for k, lim in limits.items())


# ------------------------------------------------ paged_attention_roofline
MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 1,
         "dtype": "bfloat16"}


def _attn_ctx(strip_kv_tokens=False):
    with open(BENCH / "tests" / "data" / "trace_attn.json") as f:
        trace = json.load(f)
    red = tr.reduce({"devices": trace["devices"],
                     "host": [h[:3] for h in trace["host"]]}, 0, 10000)
    spans = [[n, s, d, {k: v for k, v in a.items()
                        if not (strip_kv_tokens and k == "kv_tokens")}]
             for n, s, d, a in trace["host"]
             if red["lo"] <= s and s + d <= red["hi"]]
    return SimpleNamespace(red=red, engine_spans=spans, model=MODEL,
                           config={"kv_dtype": "bfloat16"},
                           peaks=peaks.peaks("tpu", "TPU v5 lite"))


def test_attn_bytes_by_hand():
    # Granite: 36 layers x 2 (K, V) x 8 KV heads x 128 x 2 B = 147,456 B a
    # token, the cell's pool bytes per token; 16 live rows' q and output
    g = {"n_layers": 36, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
         "dtype": "bfloat16"}
    f, b = attn_bytes.decode_step(g, "bfloat16", 1000, 16)
    assert b == 1000 * 147456 + 16 * 36 * 2 * 4096 * 2
    assert f == 4.0 * 1000 * 36 * 4096


def test_paged_attention_roofline_by_hand():
    ctx = _attn_ctx()
    # whole pairs: the prep at 1500 (1000 tokens, 4 live) with the decode
    # at 2550 (paged_attention 300 + 200 ns), the prep at 4000 (2000, 3
    # live) with the decode at 3950 (400 + 400 ns), which the trace's
    # clocks put before its prep. The decode at 150 has its prep before
    # the slice, the one at 9450 runs past its end.
    per_token = 2 * 2 * 1 * 16 * 2           # layers x K,V x Kh x Dh x B
    q_out = 2 * 2 * 4 * 16 * 2               # layers x q,o x H x Dh x B
    least = (1000 * per_token + 4 * q_out + 2000 * per_token
             + 3 * q_out) / 819e9            # bytes bound both steps
    got = harness.metric_reader("paged_attention_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / 1300e-9)


def test_paged_attention_roofline_needs_kv_tokens():
    read = harness.metric_reader("paged_attention_roofline")
    assert read(_attn_ctx(strip_kv_tokens=True)) is None     # older program
    assert read(SimpleNamespace(red=None)) is None            # untraced
