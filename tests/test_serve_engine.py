"""Continuous-batching engine correctness.

The load-bearing property: for row-independent architectures, the engine's
greedy output is **token-for-token identical** to a static batched greedy
decode of the same prompts — across chunked prefill with a padded tail,
staggered admission, slot reuse after eviction, and per-request EOS stops.
Verified for an attention arch (olmo smoke), an RWKV arch (rwkv6 smoke), and
a pure Mamba config.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import common
from repro.models import ModelConfig, build
from repro.serve import (Engine, Request, RequestState, SamplingParams,
                         Scheduler, sample)

MAMBA = ModelConfig(name="mamba-tiny", n_layers=2, d_model=64, n_heads=4,
                    n_kv_heads=4, d_ff=128, vocab=96, pattern=("mamba",),
                    mpd_c=4)
ARCHS = ("olmo-1b", "rwkv6-3b", "mamba-tiny")
PAGE = 8        # small pages: a 20-token prompt spans three of them


@functools.lru_cache(maxsize=None)
def _model(arch):
    cfg = MAMBA if arch == "mamba-tiny" else common.get_config(arch, smoke=True)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _requests(cfg, n, seed=0, max_prompt=20, max_gen=10):
    rng = np.random.default_rng(seed)
    return [Request(id=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(3, max_prompt))),
                    max_new_tokens=int(rng.integers(2, max_gen)))
            for i in range(n)]


def _reference(m, p, req):
    """Static greedy decode of one request: exact-length batch-1 prefill +
    lockstep decode_step — the legacy serving path."""
    caches = m.init_caches(1, 64)
    lg, caches = jax.jit(m.prefill)(p, jnp.asarray(req.prompt)[None], caches)
    toks = [int(jnp.argmax(lg, -1)[0])]
    decode = jax.jit(m.decode_step)
    while len(toks) < req.max_new_tokens:
        lg, caches = decode(p, jnp.asarray([toks[-1]]), caches)
        toks.append(int(jnp.argmax(lg, -1)[0]))
    return toks


# ------------------------------------------------------------------ exactness

@pytest.mark.parametrize("arch", ARCHS)
def test_engine_matches_static_greedy(arch):
    """More requests than slots: admission, eviction, slot reuse, chunked
    prefill — greedy output must equal the static batched decode exactly."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 6, seed=1)
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=PAGE)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), (arch, r.id)
    s = eng.metrics.summary()
    assert s["n_done"] == 6
    assert s["total_tokens"] == sum(len(v) for v in out.values())
    assert 0.0 < s["occupancy_mean"] <= 1.0


@pytest.mark.parametrize("arch", ARCHS)
def test_staggered_admission(arch):
    """Request B arrives mid-decode of request A: B's output must be
    unaffected by when it was admitted, and A's by B's arrival."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 3, seed=2, max_gen=12)
    eng = Engine(m, p, n_slots=3, max_len=64, page_size=PAGE)
    eng.submit(reqs[0])
    for _ in range(3):                       # A decodes alone for 3 steps
        eng.step()
    eng.submit(reqs[1])                      # B lands mid-decode of A
    eng.step()
    eng.submit(reqs[2])
    while eng.has_work():
        eng.step()
    for r in reqs:
        assert list(r.generated) == _reference(m, p, r), (arch, r.id)


@pytest.mark.parametrize("arch", ARCHS)
def test_slot_reuse_after_eviction(arch):
    """n_slots=1 forces strict sequential reuse of the single slot: the
    next occupant must see none of the previous one's K/V pages or pinned
    recurrent-state rows."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 3, seed=3)
    eng = Engine(m, p, n_slots=1, max_len=64, page_size=PAGE)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), (arch, r.id)


@pytest.mark.parametrize("arch", ARCHS)
def test_per_request_eos_stop(arch):
    """EOS taken from the reference continuation stops that request early;
    the co-resident request is unaffected."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 2, seed=4, max_gen=12)
    ref0 = _reference(m, p, reqs[0])
    assert len(ref0) >= 4
    reqs[0].eos_id = ref0[2]                 # stop after the 3rd token
    cut = ref0.index(reqs[0].eos_id) + 1     # first occurrence wins
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=PAGE)
    out = eng.run(reqs)
    assert out[reqs[0].id] == ref0[:cut]
    assert out[reqs[1].id] == _reference(m, p, reqs[1])


def _close(got, want):
    # the padded chunk and the exact prefill run the same arithmetic on
    # different shapes (a masked attention over the chunk's pages against
    # a dense causal one), so they agree to float32 re-association, not
    # bitwise; 1e-4 of the tensor's own scale, as for the logits
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.max(np.abs(want))) + 1e-6
    np.testing.assert_allclose(got, want, atol=1e-4 * scale)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_chunk_padded_tail_matches_prefill(arch):
    """A prefill chunk whose real tokens stop short of its width (the
    engine's right-padded final chunk) gives the unpadded prefill's logits
    at the last real token and the same carried state: the K/V in the
    request's pages and its slot's depth for attention, the slot's
    recurrent-state rows for mamba/rwkv."""
    m, p = _model(arch)
    width, n, slot = 2 * PAGE, 11, 1
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, n), 0, m.cfg.vocab)
    padded = jnp.zeros((1, width), jnp.int32).at[:, :n].set(toks)
    bt_row = jnp.asarray([3, 1], jnp.int32)     # out of order on purpose
    chunk = jax.jit(m.prefill_chunk, static_argnames=("final",))
    lg, caches = chunk(p, padded, m.init_paged_caches(2, 4, PAGE), bt_row,
                       slot, 0, n)
    lg_ref, ref = m.prefill(p, toks, m.init_caches(1, width))
    _close(lg[0], lg_ref[0])
    assert int(jnp.argmax(lg[0])) == int(jnp.argmax(lg_ref[0]))
    for spec, c, r in zip(m.block_specs, caches, ref):
        if spec["kind"] in ("attn", "attn_moe"):
            for pool, dense in (("kp", "k"), ("vp", "v")):
                rows = c[pool][:, bt_row].reshape(
                    (c[pool].shape[0], width) + c[pool].shape[3:])
                _close(rows[:, :n], r[dense][:, 0, :n])
            np.testing.assert_array_equal(np.asarray(c["pos"][:, slot]), n)
        else:
            for k in r:
                _close(c[k][:, slot], r[k][:, 0])


# ------------------------------------------------------------------- sampling

def test_sampling_greedy_and_topk():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 32))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(4)])
    zeros = jnp.zeros((4,))
    # temperature 0 -> argmax, regardless of key/top_k
    got = sample(logits, zeros, jnp.asarray([0, 1, 5, 32]), keys)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k=1 -> argmax even at high temperature
    got = sample(logits, jnp.full((4,), 10.0), jnp.ones((4,), jnp.int32), keys)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k=2 -> support restricted to the top 2 ids
    top2 = np.asarray(jax.lax.top_k(logits, 2)[1])
    for i in range(20):
        ks = jnp.stack([jax.random.PRNGKey(100 + i)] * 4)
        got = np.asarray(sample(logits, jnp.full((4,), 1.0),
                                jnp.full((4,), 2, jnp.int32), ks))
        for b in range(4):
            assert got[b] in top2[b]
    # same key -> same draw; different key -> may differ (determinism)
    a = sample(logits, jnp.full((4,), 1.0), zeros.astype(jnp.int32), keys)
    b = sample(logits, jnp.full((4,), 1.0), zeros.astype(jnp.int32), keys)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sampled_decode_runs():
    """Non-greedy decode end-to-end: tokens stay in-vocab and the run
    drains (stop conditions hold under sampling)."""
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 3, seed=6)
    for i, r in enumerate(reqs):
        r.sampling = SamplingParams(temperature=0.8, top_k=8, seed=i)
    out = Engine(m, p, n_slots=2, max_len=64).run(reqs)
    for r in reqs:
        assert 1 <= len(out[r.id]) <= r.max_new_tokens
        assert all(0 <= t < m.cfg.vocab for t in out[r.id])


def test_resubmit_is_fresh():
    """Re-running the same Request objects (a retry) must reproduce the
    first run, not append to it."""
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 2, seed=8)
    first = Engine(m, p, n_slots=2, max_len=64).run(reqs)
    second = Engine(m, p, n_slots=2, max_len=64).run(reqs)
    assert first == second


# ---------------------------------------------------------------- sharding

def test_engine_on_mesh_matches_unsharded():
    """Page pools placed through repro.dist on a (2,4) host mesh (per the
    long-context rules): greedy output must equal the no-mesh run
    exactly."""
    from conftest import run_forced_device_subprocess
    out = run_forced_device_subprocess("""
import numpy as np, jax
from repro.configs import common
from repro.models import build
from repro.serve import Engine, Request
from repro.dist import sharding as sh
from repro.dist.mesh import make_host_mesh

m = build(common.get_config("olmo-1b", smoke=True))
p = m.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(1)
def reqs():
    rng = np.random.default_rng(7)
    return [Request(id=i, prompt=rng.integers(0, 96, size=int(rng.integers(3, 18))),
                    max_new_tokens=int(rng.integers(2, 8))) for i in range(4)]
plain = Engine(m, p, n_slots=2, max_len=48, page_size=8).run(reqs())
with sh.use_mesh_rules(make_host_mesh(2, 4), sh.long_context_rules()):
    meshed = Engine(m, p, n_slots=2, max_len=48, page_size=8).run(reqs())
assert plain == meshed, (plain, meshed)
print("MESH_OK")
""")
    assert "MESH_OK" in out


# ------------------------------------------------------------------ scheduler

def test_scheduler_admission():
    """FCFS into free slots, reuse of a freed slot, and the ``max_len``
    rejection at submit (prompt + max_new_tokens), which holds for any
    prompt length the budget admits."""
    s = Scheduler(n_slots=2, max_len=64)
    r = [Request(id=i, prompt=np.arange(4) + 1, max_new_tokens=2)
         for i in range(3)]
    for x in r:
        s.submit(x)
    admitted = s.admit()
    assert [(q.id, sl) for q, sl in admitted] == [(0, 0), (1, 1)]  # FCFS
    assert s.admit() == []                    # no free slots
    s.finish(r[0])
    assert [(q.id, sl) for q, sl in s.admit()] == [(2, 0)]  # reuse slot 0
    with pytest.raises(ValueError):
        s.submit(Request(id=9, prompt=np.zeros(60, np.int32),
                         max_new_tokens=30))  # exceeds max_len
    with pytest.raises(ValueError):           # rejected before a slot is held
        s.submit(Request(id=10, prompt=np.zeros(60, np.int32),
                         max_new_tokens=8))
    assert [q.id for q in s.waiting] == []
    s.submit(Request(id=11, prompt=np.zeros(56, np.int32), max_new_tokens=8))
    assert [(q.id, sl) for q, sl in s.admit()] == []   # both slots busy
    s.finish(r[1])
    assert [(q.id, sl) for q, sl in s.admit()] == [(11, 1)]


# -------------------------------------------------- scheduler lifecycle edges

def _reqs(n, start_id=0):
    return [Request(id=start_id + i, prompt=np.arange(4) + 1,
                    max_new_tokens=2) for i in range(n)]


def test_finish_never_admitted_request():
    """Cancelling a queued (never-admitted) request must remove it from the
    waiting queue — a later admit() must not resurrect it — and must not
    corrupt the slot free-list."""
    s = Scheduler(n_slots=1, max_len=64)
    a, b, c = _reqs(3)
    for r in (a, b, c):
        s.submit(r)
    [(first, slot0)] = s.admit()              # a takes the only slot
    assert first is a and slot0 == 0
    s.finish(b)                               # cancel b while still waiting
    assert b.state == RequestState.DONE
    assert len(s.free_slots) == 0             # b never held a slot
    s.finish(a)
    assert [(q.id, sl) for q, sl in s.admit()] == [(c.id, 0)]  # b skipped
    assert not s.waiting
    s.finish(c)
    assert not s.has_work()


def test_resubmit_finished_request_resets_runtime_fields():
    """A finished request resubmitted (retry) must start from a clean
    slate: state, slot, generated, and prefill progress all reset."""
    s = Scheduler(n_slots=1, max_len=64)
    r = _reqs(1)[0]
    s.submit(r)
    s.admit()
    r.generated += [7, 8]
    r.prefill_pos, r.n_matched = 4, 4
    s.finish(r)
    assert r.state == RequestState.DONE and r.slot is None
    s.submit(r)
    assert r.state == RequestState.WAITING
    assert r.generated == [] and r.slot is None
    assert r.prefill_pos == 0 and r.n_matched == 0
    [(again, slot)] = s.admit()
    assert again is r and slot == 0


def test_admission_order_stable_when_slots_free_out_of_order():
    """Slots released in arbitrary order must not perturb FCFS: waiting
    requests land in submission order, into the lowest free slot."""
    s = Scheduler(n_slots=3, max_len=64)
    first = _reqs(3)
    for r in first:
        s.submit(r)
    admitted = dict((q.id, sl) for q, sl in s.admit())
    assert admitted == {0: 0, 1: 1, 2: 2}
    later = _reqs(3, start_id=10)
    for r in later:
        s.submit(r)
    # free slots out of order: 2 first, then 0 — admission order must stay
    # 10, 11 (FCFS), slots lowest-first (2 then... 0 joins later)
    s.finish(first[2])
    assert [(q.id, sl) for q, sl in s.admit()] == [(10, 2)]
    s.finish(first[0])
    s.finish(first[1])
    assert [(q.id, sl) for q, sl in s.admit()] == [(11, 0), (12, 1)]
    # max_n caps a single admit() round (the engine re-checks the pool
    # between admissions)
    for r in list(s.running.values()):
        s.finish(r)
    more = _reqs(2, start_id=20)
    for r in more:
        s.submit(r)
    assert len(s.admit(max_n=1)) == 1
    assert len(s.admit(max_n=1)) == 1
