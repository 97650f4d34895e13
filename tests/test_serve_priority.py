"""Priority classes, preemption-by-page-eviction, and cancellation.

The load-bearing properties:

* **FCFS stability** — with a single priority class the scheduler is
  byte-identical to the old FCFS queue: admission order equals submit
  order, outputs stay exact.
* **Priority ordering** — an ``interactive`` arrival admits ahead of
  queued ``batch`` requests without perturbing order within a class.
* **Preemption exactness** — under page-pool pressure an interactive
  arrival evicts the youngest batch slot; the victim requeues at its
  original arrival position and, because regeneration is deterministic,
  finishes with output identical to an uncontended run.
* **Allocator conservation** — across preemptions/cancels the pool's
  refcounts always equal the refs implied by live block tables + trie
  nodes, and trie-shared prefix pages survive eviction (the resubmitted
  victim re-prefills via prefix reuse, not from scratch).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import common
from repro.models import build
from repro.serve import Engine, Request, RequestState, Scheduler
from repro.serve.cache import NULL_PAGE


@functools.lru_cache(maxsize=None)
def _model():
    cfg = common.get_config("olmo-1b", smoke=True)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _reference(m, p, req, max_len=64):
    caches = m.init_caches(1, max_len)
    lg, caches = jax.jit(m.prefill)(p, jnp.asarray(req.prompt)[None], caches)
    toks = [int(jnp.argmax(lg, -1)[0])]
    decode = jax.jit(m.decode_step)
    while len(toks) < req.max_new_tokens:
        lg, caches = decode(p, jnp.asarray([toks[-1]]), caches)
        toks.append(int(jnp.argmax(lg, -1)[0]))
    return toks


def _check_refcounts(cache):
    """Pool refcounts must equal the refs implied by block tables + trie
    nodes — preemption/cancel may neither leak nor double-free a page."""
    expected = np.zeros(cache.pool.n_pages, np.int32)
    expected[NULL_PAGE] = 1
    for row in cache.block_tables:
        for pid in row[row != NULL_PAGE]:
            expected[pid] += 1
    for val in cache.trie.nodes.values():
        for pool, pid in zip(cache.trie.pools, cache.trie._as_tuple(val)):
            if pool is cache.pool:
                expected[pid] += 1
    np.testing.assert_array_equal(expected, cache.pool.ref)
    # free-list consistency: exactly the zero-ref pages are free
    assert cache.pool.free_count == int((cache.pool.ref == 0).sum())


def _track_admissions(eng):
    order = []
    orig = eng.metrics.on_admit

    def on_admit(req_id):
        order.append(req_id)
        return orig(req_id)
    eng.metrics.on_admit = on_admit
    return order


# ------------------------------------------------------------ scheduler unit

def test_scheduler_fcfs_within_class():
    s = Scheduler(n_slots=2, max_len=64)
    reqs = [Request(id=i, prompt=np.arange(1, 5), priority="batch")
            for i in range(4)]
    for r in reqs:
        s.submit(r)
    assert [r.id for r in s.waiting] == [0, 1, 2, 3]


def test_scheduler_priority_ordering():
    s = Scheduler(n_slots=2, max_len=64)
    s.submit(Request(id=0, prompt=np.arange(1, 5), priority="batch"))
    s.submit(Request(id=1, prompt=np.arange(1, 5), priority="batch"))
    s.submit(Request(id=2, prompt=np.arange(1, 5), priority="interactive"))
    s.submit(Request(id=3, prompt=np.arange(1, 5), priority="batch"))
    assert [r.id for r in s.waiting] == [2, 0, 1, 3]


def test_scheduler_preempt_requeues_at_original_position():
    s = Scheduler(n_slots=2, max_len=64)
    reqs = [Request(id=i, prompt=np.arange(1, 5), priority="batch")
            for i in range(4)]
    for r in reqs:
        s.submit(r)
    s.admit()                                   # 0, 1 take the slots
    assert sorted(s.running) == [0, 1]
    s.preempt(reqs[1])
    # arrival_seq survives: 1 rejoins AHEAD of 2 and 3, not behind them
    assert [r.id for r in s.waiting] == [1, 2, 3]
    assert reqs[1].slot is None and reqs[1].n_preemptions == 1
    assert reqs[1].generated == [] and reqs[1].prefill_pos == 0


def test_request_rejects_unknown_priority():
    with pytest.raises(ValueError, match="priority"):
        Request(id=0, prompt=np.arange(1, 5), priority="bulk")


# -------------------------------------------------------------- engine level

def test_equal_priority_fcfs_stable():
    """Single class == the old FCFS engine: admission follows submit
    order and every output matches the static reference."""
    m, p = _model()
    rng = np.random.default_rng(0)
    reqs = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=10),
                    max_new_tokens=5) for i in range(5)]
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8)
    order = _track_admissions(eng)
    out = eng.run(reqs)
    assert sorted(order[:2]) == [0, 1]      # first wave fills both slots
    assert order == sorted(order)           # then strictly FCFS
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), r.id
    assert eng.n_preemptions == 0           # same class never preempts


def test_interactive_admits_before_queued_batch():
    """Without preemption an interactive arrival still jumps the waiting
    queue: it admits as soon as a slot frees, ahead of older batch."""
    m, p = _model()
    rng = np.random.default_rng(1)
    reqs = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=8),
                    max_new_tokens=4, priority="batch") for i in range(3)]
    eng = Engine(m, p, n_slots=1, max_len=64, page_size=8,
                 preemption=False)
    order = _track_admissions(eng)
    for r in reqs:
        eng.submit(r)
    eng.step()                              # batch 0 takes the only slot
    inter = Request(id=9, prompt=rng.integers(0, m.cfg.vocab, size=8),
                    max_new_tokens=4, priority="interactive")
    eng.submit(inter)
    while eng.has_work():
        eng.step()
    assert order == [0, 9, 1, 2]
    assert eng.n_preemptions == 0
    for r in reqs + [inter]:
        assert list(r.generated) == _reference(m, p, r), r.id


def test_interactive_preempts_batch_and_resumes_identical():
    """The tentpole invariant: under page-pool pressure an interactive
    arrival evicts the youngest batch slot; the victim later resumes and
    finishes byte-identical to an uncontended run, and pool refcounts
    stay conserved through every step."""
    m, p = _model()
    rng = np.random.default_rng(2)
    # two batch requests: 16-token prompts (2 full pages each -> published
    # to the trie) + 8 new tokens = 3 worst-case pages each
    batch = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=16),
                     max_new_tokens=8, priority="batch") for i in range(2)]
    # pool of 7 usable pages: both batch requests reserve 3+3, leaving 1 —
    # not enough for the interactive worst case (2) without eviction
    eng = Engine(m, p, n_slots=2, max_len=32, page_size=8,
                 n_pages=8)
    for r in batch:
        eng.submit(r)
    for _ in range(4):
        eng.step()
        _check_refcounts(eng.cache)
    assert all(r.state == RequestState.DECODE for r in batch)

    inter = Request(id=7, prompt=rng.integers(0, m.cfg.vocab, size=8),
                    max_new_tokens=8, priority="interactive")
    eng.submit(inter)
    eng.step()
    _check_refcounts(eng.cache)
    # the YOUNGEST batch slot was evicted; the older one kept decoding
    assert eng.n_preemptions == 1
    assert batch[1].n_preemptions == 1 and batch[1].slot is None
    assert batch[1].state == RequestState.WAITING
    assert batch[0].n_preemptions == 0 and batch[0].slot is not None
    assert inter.slot is not None

    while eng.has_work():
        eng.step()
        _check_refcounts(eng.cache)
    for r in batch + [inter]:
        assert list(r.generated) == _reference(m, p, r, max_len=32), r.id
    s = eng.metrics.summary()
    assert s["n_preempted"] == 1
    assert s["interactive_n_done"] == 1 and s["batch_n_done"] == 2


def test_preemption_spares_trie_shared_pages():
    """Eviction returns only the victim's private pages: its trie-published
    prompt pages survive (the trie holds its own ref), so the resubmitted
    victim re-prefills via prefix reuse instead of from scratch."""
    m, p = _model()
    rng = np.random.default_rng(3)
    # 17-token prompts: 2 *full* pages land in the trie, and the partial
    # third page leaves a tail to prefill, so a later match can legally
    # reuse both full pages (a whole-prompt match is never taken — the
    # last token must prefill to produce first-token logits)
    batch = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=17),
                     max_new_tokens=7, priority="batch") for i in range(2)]
    # 8 usable pages: both batch requests decode (3 pages each), leaving 2
    # free — short of the interactive worst case (3), forcing preemption,
    # but with enough slack that admission never reclaims trie pages
    eng = Engine(m, p, n_slots=2, max_len=32, page_size=8,
                 n_pages=9)
    for r in batch:
        eng.submit(r)
    while batch[1].state != RequestState.DECODE:
        eng.step()
    # victim's 2 prompt pages are now published to the trie
    trie_pages = {pid for key, val in eng.cache.trie.nodes.items()
                  for pid in eng.cache.trie._as_tuple(val)
                  if tuple(batch[1].prompt[:len(key)]) == key}
    assert len(trie_pages) == 2
    skipped0 = eng.n_prefill_tokens_skipped

    eng.submit(Request(id=7, prompt=rng.integers(0, m.cfg.vocab, size=17),
                       max_new_tokens=7, priority="interactive"))
    eng.step()
    assert batch[1].n_preemptions == 1
    # shared pages still held by the trie, never returned to the free list
    for pid in trie_pages:
        assert eng.cache.pool.ref[pid] >= 1
        assert pid not in eng.cache.pool._free
    _check_refcounts(eng.cache)

    while eng.has_work():
        eng.step()
    # the victim's re-prefill hit the trie for its whole 16-token prompt
    assert batch[1].n_matched == 16
    assert eng.n_prefill_tokens_skipped >= skipped0 + 16
    for r in batch:
        assert list(r.generated) == _reference(m, p, r, max_len=32), r.id


def test_cancel_running_and_waiting():
    """Cancel pulls a request out of any stage: a decoding slot frees its
    pages immediately, a waiting request leaves the queue; survivors are
    unperturbed and refcounts stay conserved."""
    m, p = _model()
    rng = np.random.default_rng(4)
    reqs = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=10),
                    max_new_tokens=12) for i in range(3)]
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8)
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    assert reqs[0].state == RequestState.DECODE
    assert reqs[2].state == RequestState.WAITING

    eng.cancel(reqs[0])                      # mid-decode
    eng.cancel(reqs[2])                      # never admitted
    _check_refcounts(eng.cache)
    assert reqs[0].state == RequestState.DONE
    assert reqs[2].state == RequestState.DONE
    assert reqs[2] not in eng.scheduler.waiting
    assert 0 not in {r.id for r in eng.scheduler.running.values()}

    while eng.has_work():
        eng.step()
    _check_refcounts(eng.cache)
    assert list(reqs[1].generated) == _reference(m, p, reqs[1])
    s = eng.metrics.summary()
    assert s["n_cancelled"] == 2 and s["n_done"] == 1
