"""Fault injection, quarantine, degradation ladder, deadline enforcement.

The load-bearing properties:

* **Chaos determinism** — under a seeded storm (NaN logits + engine-step
  exception + pool exhaustion) across concurrent requests, every request
  the engine completes is token-identical to a fault-free run: quarantine
  frees only the offending slot, the deterministic retry regenerates the
  same tokens, and co-batched survivors are never perturbed.
* **Bounded retry** — a persistently-poisoned request fails cleanly with
  ``finish_reason="fault"`` after ``max_fault_retries``; its pages come
  home and the engine keeps serving.
* **Deadline contract** — ``enforce_deadline`` requests past their e2e SLO
  abort with ``finish_reason="deadline"`` within one step, pages freed.
* **Ladder hysteresis** — stage transitions need sustained pressure
  (up_steps / down_steps consecutive observations); the dead band holds.
* **Artifact integrity** — a flipped byte in a packed export surfaces as
  ``ArtifactCorruptError``, never a silent wrong-weights deploy.
* **Server error paths** — malformed JSON / unknown fields / mid-stream
  engine death / load shedding all yield structured errors, never
  tracebacks on the wire.
"""

import asyncio
import functools
import json
import math

import jax
import numpy as np
import pytest

from repro.configs import common
from repro.models import build
from repro.serve import (DegradationLadder, Engine, FaultInjector, FaultSpec,
                         GenerateServer, InjectedFault, Request, Resilience,
                         SamplingParams, parse_schedule, storm_schedule)
from repro.serve.cache import NULL_PAGE

from test_serve_paged import _model, _reference, _requests
from test_serve_server import _generate, _get


def _fresh_requests(cfg, n, seed=0):
    return _requests(cfg, n, seed=seed)


def _pool_conserved(cache):
    pool = cache.pool
    assert pool.free_count + pool.allocated_count == pool.n_pages - 1
    # after a full drain the only legitimate holders are trie nodes
    expect = np.zeros(pool.n_pages, np.int64)
    expect[NULL_PAGE] = 1
    for value in cache.trie.nodes.values():
        expect[cache._own_pid(value)] += 1
    assert (pool.ref == expect).all(), (pool.ref.tolist(), expect.tolist())


# ------------------------------------------------------------- injector unit

def test_injector_deterministic_replay():
    """Same schedule + seed => identical poison vectors, counts, and
    exception steps on replay."""
    def mk():
        return FaultInjector(storm_schedule(), seed=7)
    a, b = mk(), mk()
    for step in range(16):
        va = a.poison("decode_logits", step, 4)
        vb = b.poison("decode_logits", step, 4)
        if va is None:
            assert vb is None
        else:
            np.testing.assert_array_equal(va, vb)
        assert a.withheld_pages(step) == b.withheld_pages(step)
        for inj in (a, b):
            try:
                inj.check("engine_step", step)
                fired = False
            except InjectedFault as e:
                fired = True
                assert e.site == "engine_step" and e.step == step
            assert fired == (step == 5)
    assert a.counts == b.counts
    assert a.counts["decode_logits"] == 2
    assert a.counts["pool_exhaust"] == 3
    # NaN at slot 0 step 3; Inf at slot 1 step 9
    v3 = FaultInjector(storm_schedule()).poison("decode_logits", 3, 4)
    assert math.isnan(v3[0]) and v3[1] == 0.0
    v9 = FaultInjector(storm_schedule()).poison("decode_logits", 9, 4)
    assert math.isinf(v9[1])


def test_parse_schedule_forms(tmp_path):
    assert [s.site for s in parse_schedule("storm")] == \
        [s.site for s in storm_schedule()]
    js = json.dumps([{"site": "decode_logits", "step": 2, "slot": 1},
                     {"site": "pool_exhaust", "step": 4, "n_steps": 2}])
    sched = parse_schedule(js)
    assert sched[0].slot == 1 and sched[1].active(5)
    f = tmp_path / "sched.json"
    f.write_text(js)
    assert len(parse_schedule(f"@{f}")) == 2
    with pytest.raises(ValueError):
        parse_schedule(json.dumps([{"site": "nope"}]))
    with pytest.raises(ValueError):
        parse_schedule(json.dumps({"site": "engine_step"}))


# --------------------------------------------------------------- ladder unit

def test_ladder_hysteresis():
    lad = DegradationLadder(enter=0.9, exit=0.5, up_steps=3, down_steps=4)
    # two high observations then relief: no transition (streak broken)
    lad.observe(1.0), lad.observe(1.0), lad.observe(0.2)
    assert lad.stage == 0
    # dead-band observations also reset the climb streak
    lad.observe(1.0), lad.observe(1.0), lad.observe(0.7)
    assert lad.stage == 0
    # sustained pressure climbs exactly one stage per up_steps window
    for _ in range(3):
        lad.observe(0.95)
    assert lad.stage == 1 and lad.spec_disabled and not lad.flush_prefix
    for _ in range(3):
        lad.observe(1.0)
    assert lad.stage == 2 and lad.flush_prefix
    # the ladder saturates at shed_batch
    for _ in range(9):
        lad.observe(1.0)
    assert lad.stage == 3 and lad.shed_batch and lad.max_stage == 3
    # descent needs down_steps consecutive relief
    for _ in range(3):
        lad.observe(0.1)
    lad.observe(0.7)                      # dead band: streak resets
    assert lad.stage == 3
    for _ in range(4):
        lad.observe(0.1)
    assert lad.stage == 2
    # transitions are recorded (old, new) pairs, each a single step move
    assert [(o, n) for _, o, n in lad.transitions] == \
        [(0, 1), (1, 2), (2, 3), (3, 2)]


def test_ladder_force_pins():
    lad = DegradationLadder()
    lad.force(1)
    assert lad.stage == 1 and lad.spec_disabled
    for _ in range(50):
        lad.observe(1.0)                  # pinned: pressure is ignored
    assert lad.stage == 1
    lad.force(None)
    for _ in range(3):
        lad.observe(1.0)
    assert lad.stage == 2


def test_backoff_deterministic_and_monotone():
    res = Resilience(seed=3)
    a = [res.backoff_steps(11, k) for k in (1, 2, 3)]
    b = [res.backoff_steps(11, k) for k in (1, 2, 3)]
    assert a == b                          # seeded: replayable
    base = res.retry_backoff_steps
    for k, v in enumerate(a, start=1):
        lo = base * (2 ** (k - 1))
        assert lo <= v <= lo + base


# -------------------------------------------------- chaos determinism (CORE)

def test_chaos_storm_token_identical():
    """The acceptance test: NaN logits + engine-step exception + pool
    exhaustion over 4 concurrent requests on 3 slots. Every request must
    finish with exactly the fault-free tokens (the quarantined one via
    deterministic retry), and the page pool must balance."""
    m, p = _model("olmo-1b")
    baseline = {r.id: _reference(m, p, r)
                for r in _fresh_requests(m.cfg, 4, seed=11)}

    schedule = [
        FaultSpec("decode_logits", step=3, slot=0),
        FaultSpec("engine_step", step=5),
        FaultSpec("pool_exhaust", step=7, n_steps=3),
        FaultSpec("slow_step", step=4, duration_s=0.002),
    ]
    res = Resilience(injector=FaultInjector(schedule, seed=0),
                     ladder=DegradationLadder())
    eng = Engine(m, p, n_slots=3, max_len=64, page_size=8,
                 resilience=res)
    reqs = _fresh_requests(m.cfg, 4, seed=11)
    out = eng.run(reqs)

    for r in reqs:
        assert r.finish_reason not in ("fault", "deadline"), r.id
        assert out[r.id] == baseline[r.id], r.id
    inj = res.injector
    assert inj.counts["decode_logits"] >= 1
    assert inj.counts["engine_step"] == 1
    assert inj.counts["pool_exhaust"] == 3
    assert eng.n_quarantines >= 1
    assert eng.metrics.n_quarantines == eng.n_quarantines
    assert eng.metrics.n_step_faults == 1
    s = eng.metrics.summary()
    assert s["n_done"] == 4
    assert s["faults_injected_total"] == inj.total_injected
    _pool_conserved(eng.cache)


def test_chaos_storm_with_spec_draft():
    """Same storm shape with speculative decoding on: draft-logit poison
    must quarantine (never leak resampled garbage), and survivors stay
    identical to fault-free spec output (== static greedy)."""
    m, p = _model("olmo-1b")

    def requests():
        reqs = _fresh_requests(m.cfg, 3, seed=12)
        # the draft site's row samples: a greedy row proposes the argmax
        # with a one-hot q, which a poisoned draft leaves finite, and its
        # acceptance against the target's argmax takes no garbage token
        reqs[0].sampling = SamplingParams(temperature=0.8, top_k=8, seed=3)
        return reqs

    def serve(resilience):
        eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                     spec_draft=(m, p), spec_k=3, resilience=resilience)
        assert eng.spec_active
        reqs = requests()
        return eng, reqs, eng.run(reqs)

    _, _, baseline = serve(None)
    for r in requests()[1:]:
        assert baseline[r.id] == _reference(m, p, r), r.id
    # the draft is the target itself, so nearly every window is accepted
    # whole and the fault-free run drains in a few steps: step 0 decodes
    # request 0 in slot 0 (request 1 still queued for its chunk), step 1
    # decodes request 1 in slot 1. Each fault lands on a live slot there.
    schedule = [
        FaultSpec("draft_logits", step=0, slot=0),
        FaultSpec("decode_logits", step=1, slot=1,
                  value=float("inf")),
    ]
    res = Resilience(injector=FaultInjector(schedule, seed=1))
    eng, reqs, out = serve(res)
    for r in reqs:
        assert r.finish_reason not in ("fault", "deadline"), r.id
        assert out[r.id] == baseline[r.id], r.id
    assert res.injector.counts["draft_logits"] == 1
    assert res.injector.counts["decode_logits"] == 1
    assert eng.n_quarantines == 2           # both poisoned rows were live
    _pool_conserved(eng.cache)
    _pool_conserved(eng.draft_cache)


def test_retries_exhausted_finish_reason_fault():
    """A slot poisoned at every step exhausts its retry budget and fails
    terminally; the engine drains, pages balance, and the failure is an
    abort (not a completion) in the metrics."""
    m, p = _model("olmo-1b")
    schedule = [FaultSpec("decode_logits", step=0, n_steps=10_000, slot=0)]
    res = Resilience(injector=FaultInjector(schedule), max_fault_retries=2,
                     retry_backoff_steps=1)
    eng = Engine(m, p, n_slots=1, max_len=64, page_size=8,
                 resilience=res)
    req = _fresh_requests(m.cfg, 1, seed=5)[0]
    eng.submit(req)
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work()
    assert req.finish_reason == "fault"
    assert req.n_fault_retries == 2
    assert eng.n_fault_failures == 1
    rm = eng.metrics.requests[req.id]
    assert rm.aborted and rm.finish_reason == "fault"
    s = eng.metrics.summary()
    assert s["n_fault_failures"] == 1 and s["n_done"] == 0
    _pool_conserved(eng.cache)


def test_quarantine_does_not_perturb_dense_engine():
    """The watchdog also covers a recurrent arch, whose quarantined slot
    must give back its pinned state row with its pages: the retry and the
    co-batched survivor both match the static decode."""
    m, p = _model("rwkv6-3b")
    baseline = {r.id: _reference(m, p, r)
                for r in _fresh_requests(m.cfg, 3, seed=13)}
    res = Resilience(
        injector=FaultInjector([FaultSpec("decode_logits", step=2, slot=0)]))
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8, resilience=res)
    reqs = _fresh_requests(m.cfg, 3, seed=13)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == baseline[r.id], r.id
    assert eng.n_quarantines >= 1


def test_quarantined_head_does_not_wedge_preemption():
    """A quarantined interactive head still in retry backoff is skipped by
    admission — preemption must skip it too, or the admission loop evicts
    a running batch request on its behalf, the victim instantly re-admits
    off its trie-published prefix, and one step spins forever (found by
    the HTTP chaos smoke)."""
    m, p = _model("olmo-1b")
    i = Request(id=0, prompt=np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32),
                max_new_tokens=8, priority="interactive")
    b = Request(id=1, prompt=np.array([2, 7, 1, 8, 2, 8, 1, 8, 2, 8],
                                      np.int32),
                max_new_tokens=8, priority="batch")
    baseline = {r.id: _reference(m, p, r) for r in (i, b)}
    res = Resilience(injector=FaultInjector(storm_schedule()))
    eng = Engine(m, p, n_slots=4, max_len=48, page_size=8,
                 preemption=True, resilience=res)
    out = eng.run([i, b], max_steps=200)     # pre-fix: never drains
    for r in (i, b):
        assert out[r.id] == baseline[r.id], r.id
    assert eng.n_quarantines >= 1
    _pool_conserved(eng.cache)


# ----------------------------------------------------------------- deadlines

def test_deadline_abort_frees_within_step():
    """enforce_deadline + expired e2e SLO: the request aborts on the next
    step with finish_reason="deadline"; a co-running request without the
    flag is untouched and stays exact."""
    m, p = _model("olmo-1b")
    reqs = _fresh_requests(m.cfg, 2, seed=14)
    for r in reqs:                 # long-lived: still running at the abort
        r.max_new_tokens = 16
    baseline = _reference(m, p, reqs[1])
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8)
    now = [0.0]
    eng.metrics.clock = lambda: now[0]
    reqs[0].e2e_slo_s = 0.5
    reqs[0].enforce_deadline = True
    reqs[1].e2e_slo_s = 0.5              # SLO tracked but NOT enforced
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    for _ in range(3):
        eng.step()
    assert reqs[0].finish_reason is None
    now[0] = 1.0                          # both requests blow the SLO
    eng.step()
    assert reqs[0].finish_reason == "deadline"
    assert eng.n_deadline_aborts == 1
    while eng.has_work():
        eng.step()
    assert reqs[1].finish_reason is None
    assert list(reqs[1].generated) == baseline
    s = eng.metrics.summary()
    assert s["n_deadline_aborts"] == 1
    assert s["n_done"] == 1               # the abort is not a completion
    _pool_conserved(eng.cache)


# ------------------------------------------------------- ladder in the engine

def test_ladder_spec_suspend_resume_exact():
    """Forcing the ladder to no_spec mid-run swaps in the plain paged
    decode; releasing it resumes speculation — outputs stay exact through
    both transitions (stale draft KV costs acceptance, never tokens)."""
    m, p = _model("olmo-1b")
    baseline = {r.id: _reference(m, p, r)
                for r in _fresh_requests(m.cfg, 2, seed=15)}
    lad = DegradationLadder()
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 spec_draft=(m, p), spec_k=3,
                 resilience=Resilience(ladder=lad))
    reqs = _fresh_requests(m.cfg, 2, seed=15)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    lad.force(1)
    assert eng.spec_suspended
    for _ in range(3):
        eng.step()
    lad.force(0), lad.force(None)
    assert not eng.spec_suspended
    while eng.has_work():
        eng.step()
    for r in reqs:
        assert list(r.generated) == baseline[r.id], r.id


def test_ladder_flush_prefix_stage_flushes_and_suspends_publish():
    m, p = _model("olmo-1b")
    lad = DegradationLadder()
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 resilience=Resilience(ladder=lad))
    reqs = _fresh_requests(m.cfg, 2, seed=16)
    eng.run(reqs)
    assert len(eng.cache.trie.nodes) > 0   # published prefixes linger
    lad.force(2)
    assert len(eng.cache.trie.nodes) == 0
    assert not eng.cache.publish_enabled
    assert eng.metrics.degradation_stage == 2
    lad.force(0)
    assert eng.cache.publish_enabled
    assert eng.metrics.degradation_transitions == 2
    _pool_conserved(eng.cache)
    assert eng.cache.pool.allocated_count == 0


# ----------------------------------------------------------- artifact checks

def test_artifact_checksum_roundtrip_and_corruption(tmp_path):
    from repro.checkpoint import checkpoint as ckpt_lib
    from repro.checkpoint.checkpoint import ArtifactCorruptError

    cfg = common.get_config("olmo-1b", smoke=True, mpd_mode="masked_dense")
    m = build(cfg)
    p = m.init(jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    ckpt_lib.export_packed(d, 0, m, p, quantize="int8")
    model2, params2 = ckpt_lib.load_packed(d)        # clean load passes
    assert model2.cfg.mpd_mode == "packed"

    inj = FaultInjector([FaultSpec("artifact_load", step=0)], seed=4)
    step_dir = next((tmp_path / "ck" / "packed").glob("step_*"))
    corrupted = inj.corrupt_artifact(str(step_dir))
    assert corrupted is not None
    with pytest.raises(ArtifactCorruptError):
        ckpt_lib.load_packed(d)
    assert inj.counts["artifact_load"] == 1


# -------------------------------------------------------- server error paths

def _raw_post(port, path, body: bytes):
    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write((f"POST {path} HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await writer.drain()
        data = b""
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                break
            data += chunk
        writer.close()
        return data
    return go


def test_server_rejects_malformed_and_unknown_fields():
    m, p = _model("olmo-1b")
    engine = Engine(m, p, n_slots=1, max_len=64, page_size=8)

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=4,
                                auto_pump=False)
        await server.start()
        bad_json = await _raw_post(server.port, "/v1/generate",
                                   b"{not json")()
        unknown = await _raw_post(
            server.port, "/v1/generate",
            json.dumps({"prompt": [1, 2, 3], "max_new_tok": 4}).encode())()
        not_dict = await _raw_post(server.port, "/v1/generate",
                                   json.dumps([1, 2]).encode())()
        await server.close()
        return bad_json, unknown, not_dict

    bad_json, unknown, not_dict = asyncio.run(main())
    for resp in (bad_json, unknown, not_dict):
        head, body = resp.split(b"\r\n\r\n", 1)
        assert b"400" in head.split(b"\r\n")[0]
        assert b"error" in body
        assert b"Traceback" not in resp
    assert b"max_new_tok" in unknown       # names the offending field
    assert not engine.has_work()           # nothing was admitted


def test_server_midstream_engine_fault_structured_error():
    """A persistent engine fault mid-stream must surface as a structured
    SSE error event (finish_reason=engine_fault), flip /healthz to
    ok:false, and 503 subsequent generates — never a hung stream."""
    m, p = _model("olmo-1b")
    res = Resilience(
        injector=FaultInjector([FaultSpec("engine_step", step=1,
                                          n_steps=1000)]),
        max_consecutive_step_faults=0)     # first fault is terminal
    engine = Engine(m, p, n_slots=1, max_len=64, page_size=8,
                    resilience=res)

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=4)
        await server.start()
        toks, done = await _generate(server.port, {
            "prompt": [3, 1, 4, 1, 5], "max_new_tokens": 8})
        raw = await _raw_post(
            server.port, "/v1/generate",
            json.dumps({"prompt": [1, 2], "max_new_tokens": 2}).encode())()
        health = await _get(server.port, "/healthz")
        await server.close()
        return toks, done, raw, health

    toks, done, raw, health = asyncio.run(main())
    assert done is None                    # no done event — an error event
    assert len(toks) < 8
    assert b"503" in raw.split(b"\r\n")[0]
    assert json.loads(health.split("\r\n\r\n", 1)[1])["ok"] is False


def test_server_sheds_batch_when_ladder_saturated():
    m, p = _model("olmo-1b")
    lad = DegradationLadder()
    engine = Engine(m, p, n_slots=1, max_len=64, page_size=8,
                    resilience=Resilience(ladder=lad))
    lad.force(3)

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=4)
        await server.start()
        shed = await _raw_post(
            server.port, "/v1/generate",
            json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 2,
                        "priority": "batch"}).encode())()
        toks, done = await _generate(server.port, {
            "prompt": [1, 2, 3], "max_new_tokens": 2})   # interactive: served
        await server.close()
        return shed, toks, done

    shed, toks, done = asyncio.run(main())
    head = shed.split(b"\r\n")[0]
    assert b"503" in head
    assert b"retry-after" in shed.lower()
    assert engine.metrics.n_shed == 1
    assert len(toks) == 2 and done["finish_reason"] == "length"


def test_server_injected_500_is_structured():
    m, p = _model("olmo-1b")
    res = Resilience(
        injector=FaultInjector([FaultSpec("server_error", step=0)]))
    engine = Engine(m, p, n_slots=1, max_len=64, page_size=8,
                    resilience=res)

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=4,
                                auto_pump=False)
        await server.start()
        raw = await _raw_post(
            server.port, "/v1/generate",
            json.dumps({"prompt": [1, 2], "max_new_tokens": 2}).encode())()
        await server.close()
        return raw

    raw = asyncio.run(main())
    head, body = raw.split(b"\r\n\r\n", 1)
    assert b"500" in head.split(b"\r\n")[0]
    payload = json.loads(body)
    assert payload["injected"] is True
    assert b"Traceback" not in raw


# ----------------------------------------------------------------- telemetry

def test_prometheus_chaos_series():
    m, p = _model("olmo-1b")
    lad = DegradationLadder()
    res = Resilience(
        injector=FaultInjector([FaultSpec("decode_logits", step=3, slot=0)]),
        ladder=lad)
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 resilience=res)
    reqs = _fresh_requests(m.cfg, 2, seed=17)
    for r in reqs:                 # long-lived: slot 0 still live at step 3
        r.max_new_tokens = 12
    eng.run(reqs)
    lad.force(1)
    text = eng.metrics.prometheus()
    assert 'repro_serve_faults_injected_total{site="decode_logits"} 1' in text
    assert f"repro_serve_quarantines_total {eng.n_quarantines}" in text
    assert eng.n_quarantines >= 1
    assert "repro_serve_degradation_stage 1" in text
    assert "repro_serve_degradation_transitions_total 1" in text
    # the engine compiled its programs while serving (no warmup)
    assert eng.metrics.n_compiles >= 1
    assert f"repro_serve_compiles_total {eng.metrics.n_compiles}" in text
