"""Paged KV cache correctness.

The load-bearing properties:

* **Exactness** — paged greedy decode is token-for-token identical to the
  slot-dense engine / static decode for attention, RWKV, and Mamba archs,
  including staggered admission, page/slot reuse, and pool-pressure-gated
  admission (the dense exactness contract survives the memory-model swap).
* **Prefix reuse** — a shared page-aligned prompt prefix is prefilled once:
  the second request provably skips chunks (prefill-token accounting).
* **Chunked prefill** — a prompt longer than the dense engine's largest
  bucket completes (the old `submit` rejection is gone in paged mode).
* **Allocator invariants** — free-list/refcount round trips, trie
  leaf-first LRU eviction, immutability of shared pages.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import common
from repro.models import ModelConfig, build
from repro.serve import Engine, PagePool, PrefixTrie, Request, RequestState
from repro.serve.cache import NULL_PAGE, PagedCache

MAMBA = ModelConfig(name="mamba-tiny", n_layers=2, d_model=64, n_heads=4,
                    n_kv_heads=4, d_ff=128, vocab=96, pattern=("mamba",),
                    mpd_c=4)
ARCHS = ("olmo-1b", "rwkv6-3b", "mamba-tiny")


@functools.lru_cache(maxsize=None)
def _model(arch):
    """A smoke model; ``<arch>-3l`` is the same arch three layers deep, so
    K/V written to or read from another layer's pages shows."""
    if arch == "mamba-tiny":
        cfg = MAMBA
    elif arch.endswith("-3l"):
        cfg = common.get_config(arch[:-3], smoke=True, n_layers=3)
    else:
        cfg = common.get_config(arch, smoke=True)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _requests(cfg, n, seed=0, max_prompt=20, max_gen=10):
    rng = np.random.default_rng(seed)
    return [Request(id=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(3, max_prompt))),
                    max_new_tokens=int(rng.integers(2, max_gen)))
            for i in range(n)]


def _reference(m, p, req, max_len=64):
    """Static greedy decode of one request: exact-length batch-1 prefill +
    lockstep decode_step — the legacy serving path."""
    caches = m.init_caches(1, max_len)
    lg, caches = jax.jit(m.prefill)(p, jnp.asarray(req.prompt)[None], caches)
    toks = [int(jnp.argmax(lg, -1)[0])]
    decode = jax.jit(m.decode_step)
    while len(toks) < req.max_new_tokens:
        lg, caches = decode(p, jnp.asarray([toks[-1]]), caches)
        toks.append(int(jnp.argmax(lg, -1)[0]))
    return toks


# ------------------------------------------------------------------ exactness

@pytest.mark.parametrize("arch", ARCHS)
def test_paged_matches_static_greedy(arch):
    """More requests than slots: admission, eviction, page reuse, chunked
    prefill — paged greedy output must equal the static decode exactly."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 6, seed=1)
    eng = Engine(m, p, n_slots=2, max_len=64, paged=True, page_size=8)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), (arch, r.id)
    s = eng.metrics.summary()
    assert s["n_done"] == 6
    # partial occupancy: the paged pool must hold strictly fewer KV bytes
    # than the dense n_slots x max_len reservation (attn archs only)
    if arch == "olmo-1b":
        assert 0 < s["kv_bytes_allocated_peak"] < s["kv_bytes_reserved"]


def _watch_null_pages(eng):
    """Wrap the engine's decode and chunk programs to record, per call and
    per attention layer, which offsets of the null page (page 0) changed,
    and check each against the rows that may write there: a decode's
    non-live rows (at ``pos % page_size``) and a chunk's pages that the
    block table names null (its padded tail past the table, or a warm-up
    on an all-null table). Returns the (layers, page_size) mask of offsets
    the decodes wrote over the run."""
    ps = eng.cache.page_size
    seen = []

    def null_page(caches):
        return np.stack([np.asarray(c["kp"][:, NULL_PAGE])
                         for c in caches if "kp" in c])    # (blocks, L, ...)

    def changed(before, after):
        return (before != after).any(axis=(-2, -1))      # (blocks, L, ps)

    decode, chunk = eng._decode_paged, eng._chunk

    def watched_decode(params, caches, dev, bt, live, poison):
        before = null_page(caches)
        out = decode(params, caches, dev, bt, live, poison)
        pos = np.asarray(next(c for c in caches if "kp" in c)["pos"][0])
        allowed = np.zeros(ps, bool)
        allowed[pos[~np.asarray(live)] % ps] = True
        hit = changed(before, null_page(out[1]))
        assert not (hit & ~allowed).any(), "live K/V on the null page"
        seen.append(hit)
        return out

    def watched_chunk(params, caches, dev, toks, bt_row, slot, start, n,
                      *rest, **kw):
        before = null_page(caches)
        out = chunk(params, caches, dev, toks, bt_row, slot, start, n,
                    *rest, **kw)
        idx = int(start) // ps + np.arange(toks.shape[1] // ps)
        row = np.asarray(bt_row)
        named = np.where(idx < len(row), row[np.minimum(idx, len(row) - 1)],
                         NULL_PAGE)
        hit = changed(before, null_page(out[1]))
        assert (named == NULL_PAGE).any() or not hit.any(), \
            "chunk K/V on the null page"
        return out

    eng._decode_paged, eng._chunk = watched_decode, watched_chunk
    return lambda: np.logical_or.reduce(seen)


@pytest.mark.parametrize("arch,backend", [
    pytest.param("olmo-1b", "jnp", id="olmo-1b"),
    pytest.param("rwkv6-3b", "jnp", id="rwkv6-3b"),
    pytest.param("olmo-1b-3l", "jnp", id="olmo-1b-3l-jnp"),
    pytest.param("olmo-1b-3l", "interpret", id="olmo-1b-3l-interpret"),
])
def test_paged_staggered_admission(arch, backend):
    """Requests landing mid-decode of others (chunked prefill interleaved
    with running decodes) must not perturb anyone's tokens. Three layers
    deep, every layer's null page takes the non-live rows' writes and no
    live token's K/V."""
    from repro.kernels import ops
    m, p = _model(arch)
    reqs = _requests(m.cfg, 3, seed=2, max_gen=12)
    saved = ops._BACKEND
    ops.set_backend(backend)
    try:
        eng = Engine(m, p, n_slots=3, max_len=64, paged=True, page_size=8)
        written = _watch_null_pages(eng) if arch.endswith("-3l") else None
        eng.submit(reqs[0])
        for _ in range(3):
            eng.step()
        eng.submit(reqs[1])
        eng.step()
        eng.submit(reqs[2])
        while eng.has_work():
            eng.step()
    finally:
        ops._BACKEND = saved
    for r in reqs:
        assert list(r.generated) == _reference(m, p, r), (arch, r.id)
    if written is not None:
        # idle slots decode as non-live rows from the first step on: each
        # layer's own null page takes their writes
        assert written().any(axis=-1).all(), written()


def test_paged_page_reuse_single_slot():
    """n_slots=1 forces strict sequential reuse of slot and pages; a new
    occupant must never see the previous one's K/V or recurrent state."""
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 3, seed=3)
    eng = Engine(m, p, n_slots=1, max_len=64, paged=True, page_size=8)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), r.id


def test_paged_pool_pressure_admission():
    """A pool sized for ~2 requests forces serial admission of 4; strict
    FCFS holds (blocked head blocks the queue) and outputs stay exact."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(4)
    reqs = [Request(id=i, prompt=rng.integers(0, 96, size=12),
                    max_new_tokens=6) for i in range(4)]
    eng = Engine(m, p, n_slots=4, max_len=32, paged=True, page_size=8,
                 n_pages=8)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r, max_len=32), r.id
    # everything returned: only trie-cached prefix pages may remain held
    assert eng.cache.pool.free_count + len(eng.cache.trie) \
        == eng.cache.n_pages - 1
    assert eng.cache.reserved == 0


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_prefill_backend_greedy_parity(backend):
    """Serve-level parity rows for the chunked-prefill attention routes:
    the jnp oracle (bitwise vs dense) and the flash kernel in interpret
    mode must both keep paged greedy output identical to the static
    reference."""
    from repro.kernels import ops
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 3, seed=11, max_prompt=30, max_gen=8)
    saved = ops._PREFILL_BACKEND
    ops.set_prefill_backend(backend)
    try:
        eng = Engine(m, p, n_slots=2, max_len=64, paged=True, page_size=8,
                     prefill_chunk_tokens=16)
        out = eng.run(reqs)
    finally:
        ops.set_prefill_backend(saved)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), (backend, r.id)


# -------------------------------------------------------------- prefix reuse

def test_shared_prefix_skips_prefill():
    """Two requests sharing a page-aligned system prompt: the second's
    matched pages are reused from the trie, provably skipping prefill
    chunks, with token-identical output."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(0, 96, size=40)
    r1 = Request(id=0, prompt=np.concatenate([sys_prompt,
                                              rng.integers(0, 96, size=5)]),
                 max_new_tokens=4)
    r2 = Request(id=1, prompt=np.concatenate([sys_prompt,
                                              rng.integers(0, 96, size=7)]),
                 max_new_tokens=4)
    eng = Engine(m, p, n_slots=2, max_len=96, paged=True, page_size=8,
                 prefill_chunk_tokens=16)
    eng.submit(r1)
    while r1.state.value != "done":
        eng.step()
    chunks_r1, tokens_r1 = eng.n_prefill_chunks, eng.n_prefill_tokens
    assert tokens_r1 == len(r1.prompt)            # nothing cached yet
    eng.submit(r2)
    while eng.has_work():
        eng.step()
    chunks_r2 = eng.n_prefill_chunks - chunks_r1
    tokens_r2 = eng.n_prefill_tokens - tokens_r1
    assert eng.n_prefill_tokens_skipped == 40     # 5 shared pages reused
    assert tokens_r2 == len(r2.prompt) - 40
    assert chunks_r2 < chunks_r1                  # fewer chunks than a cold run
    assert list(r1.generated) == _reference(m, p, r1, max_len=96)
    assert list(r2.generated) == _reference(m, p, r2, max_len=96)


def test_identical_prompt_never_fully_matched():
    """An identical resubmitted prompt still computes its final page — the
    engine needs last-token logits — and still produces identical output."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(6)
    shared = rng.integers(0, 96, size=24)         # exactly 3 pages
    r1 = Request(id=0, prompt=shared, max_new_tokens=4)
    r2 = Request(id=1, prompt=shared.copy(), max_new_tokens=4)
    eng = Engine(m, p, n_slots=1, max_len=64, paged=True, page_size=8,
                 prefill_chunk_tokens=8)
    out = eng.run([r1, r2])
    exp = _reference(m, p, r1)
    assert out[0] == exp and out[1] == exp
    # match capped at 2 of 3 pages: 24 + (24 - 16) tokens computed
    assert eng.n_prefill_tokens == 32
    assert eng.n_prefill_tokens_skipped == 16


def test_prefix_reuse_disabled_for_recurrent():
    """Recurrent state cannot be reconstructed from matched pages, so
    hybrid/recurrent models never match (and still serve correctly)."""
    m, p = _model("mamba-tiny")
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 96, size=24)
    r1 = Request(id=0, prompt=shared, max_new_tokens=3)
    r2 = Request(id=1, prompt=shared.copy(), max_new_tokens=3)
    eng = Engine(m, p, n_slots=1, max_len=64, paged=True, page_size=8)
    assert not eng.cache.prefix_cache_enabled
    out = eng.run([r1, r2])
    assert eng.n_prefill_tokens_skipped == 0
    exp = _reference(m, p, r1)
    assert out[0] == exp and out[1] == exp


# ----------------------------------------------------------- chunked prefill

def test_long_prompt_beyond_buckets_completes():
    """The dense scheduler rejects prompts above its largest bucket; the
    paged engine runs them as chunks and matches the static decode."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(8)
    req = Request(id=0, prompt=rng.integers(0, 96, size=70), max_new_tokens=5)
    # dense path with buckets capped at 32: rejected outright
    dense = Engine(m, p, n_slots=2, max_len=96, buckets=[16, 32])
    with pytest.raises(ValueError):
        dense.submit(req)
    eng = Engine(m, p, n_slots=2, max_len=96, paged=True, page_size=8,
                 prefill_chunk_tokens=16)
    out = eng.run([req])
    assert out[0] == _reference(m, p, req, max_len=96)
    assert eng.n_prefill_chunks == 5              # ceil(70/16)


def test_decode_never_touches_mid_prefill_pages():
    """The decode batch always spans all slots; rows mid-chunked-prefill
    hold real block tables, so without the live mask a decode scatter's
    clipped page index aliases onto already-prefilled (possibly
    trie-shared) pages. The slot's first page must stay bit-identical
    across every decode that runs while it prefills."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(13)
    short = Request(id=0, prompt=rng.integers(0, 96, size=6),
                    max_new_tokens=20)
    long_ = Request(id=1, prompt=rng.integers(0, 96, size=64),
                    max_new_tokens=4)
    eng = Engine(m, p, n_slots=2, max_len=96, paged=True, page_size=8,
                 prefill_chunk_tokens=8)
    orig = eng._decode_paged
    deltas = []

    def traced(params, caches, dev, bt, live, poison):
        mid_prefill = (long_.slot is not None
                       and long_.state == RequestState.PREFILL
                       and long_.prefill_pos >= 8)
        if mid_prefill:
            pid = int(eng.cache.block_tables[long_.slot, 0])
            before = np.asarray(caches[0]["kp"][:, pid]).copy()
        out = orig(params, caches, dev, bt, live, poison)
        if mid_prefill:
            after = np.asarray(out[1][0]["kp"][:, pid])
            deltas.append(float(np.abs(after - before).max()))
        return out

    eng._decode_paged = traced
    eng.submit(short)
    eng.step()
    eng.submit(long_)
    while eng.has_work():
        eng.step()
    assert deltas and max(deltas) == 0.0, deltas
    assert list(long_.generated) == _reference(m, p, long_, max_len=96)
    assert list(short.generated) == _reference(m, p, short, max_len=96)


def test_decode_freezes_mid_prefill_recurrent_state():
    """Recurrent state carried between prefill chunks must be BITWISE the
    exact-prefill state even while another slot decodes — an unmasked
    decode would advance it by a garbage token between chunks (the SSM
    contraction damps the error, so only a bitwise check is reliable)."""
    m, p = _model("mamba-tiny")
    rng = np.random.default_rng(14)
    short = Request(id=0, prompt=rng.integers(0, 96, size=5),
                    max_new_tokens=20)
    long_ = Request(id=1, prompt=rng.integers(0, 96, size=40),
                    max_new_tokens=4)
    eng = Engine(m, p, n_slots=2, max_len=96, paged=True, page_size=8,
                 prefill_chunk_tokens=8)
    eng.submit(short)
    eng.step()
    eng.step()
    eng.submit(long_)
    eng.step()
    eng.step()                       # chunks at pos 8 and 16, decodes between
    assert long_.state == RequestState.PREFILL and long_.prefill_pos == 16
    _, rc = jax.jit(m.prefill)(p, jnp.asarray(long_.prompt[:16])[None],
                               m.init_caches(1, 96))
    np.testing.assert_array_equal(
        np.asarray(eng.cache.caches[0]["h"][:, long_.slot]),
        np.asarray(rc[0]["h"][:, 0]))
    while eng.has_work():
        eng.step()
    assert list(long_.generated) == _reference(m, p, long_, max_len=96)


def test_final_chunk_tail_past_table_end():
    """max_len NOT a multiple of chunk_tokens: the final chunk's padded
    tail reaches past the block table and must scatter to the null page —
    a clamped slice would alias (and corrupt) earlier real pages."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(12)
    req = Request(id=0, prompt=rng.integers(0, 96, size=70), max_new_tokens=2)
    eng = Engine(m, p, n_slots=1, max_len=72, paged=True, page_size=8,
                 prefill_chunk_tokens=32)    # table 9 pages; last chunk->96
    out = eng.run([req])
    assert out[0] == _reference(m, p, req, max_len=72)


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt admitted mid-decode must not stall the running
    request: decode steps keep landing while the newcomer prefills."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(9)
    short = Request(id=0, prompt=rng.integers(0, 96, size=6),
                    max_new_tokens=20)
    long_ = Request(id=1, prompt=rng.integers(0, 96, size=64),
                    max_new_tokens=4)
    eng = Engine(m, p, n_slots=2, max_len=96, paged=True, page_size=8,
                 prefill_chunk_tokens=8)   # 8 chunks to prefill long_
    eng.submit(short)
    eng.step()
    n0 = len(short.generated)
    eng.submit(long_)
    for _ in range(4):                      # long_ still mid-prefill
        eng.step()
    assert long_.state.value == "prefill"
    assert len(short.generated) >= n0 + 4   # short kept decoding
    while eng.has_work():
        eng.step()
    assert list(short.generated) == _reference(m, p, short, max_len=96)
    assert list(long_.generated) == _reference(m, p, long_, max_len=96)


# ------------------------------------------------------------ allocator units

def test_page_pool_refcounts():
    pool = PagePool(5)                      # null + 4 usable
    a, b = pool.alloc(), pool.alloc()
    assert a != NULL_PAGE and b != NULL_PAGE and a != b
    assert pool.free_count == 2 and pool.allocated_count == 2
    pool.retain(a)
    pool.release(a)
    assert pool.allocated_count == 2        # still held once
    pool.release(a)
    assert pool.free_count == 3
    pool.release(b)
    assert pool.free_count == 4 and pool.allocated_count == 0
    for _ in range(4):
        pool.alloc()
    with pytest.raises(RuntimeError):
        pool.alloc()


def test_prefix_trie_match_insert_evict():
    pool = PagePool(8)
    trie = PrefixTrie(pool, page_size=8)
    prompt = np.arange(20)
    p0, p1 = pool.alloc(), pool.alloc()
    trie.insert(prompt, 0, p0)
    trie.insert(prompt, 1, p1)
    assert pool.ref[p0] == 2 and pool.ref[p1] == 2
    # full match of both cached pages; a diverging prompt matches only one
    assert trie.match(prompt, 2) == [p0, p1]
    other = prompt.copy()
    other[12] += 1
    assert trie.match(other, 2) == [p0]
    # a capacity probe (touch=False) must not bump LRU recency
    tick_before = dict(trie._last_use)
    trie.match(prompt, 2, touch=False)
    assert trie._last_use == tick_before
    # while the request holds refs nothing is evictable
    assert trie.evictable_count() == 0
    pool.release(p0)
    pool.release(p1)
    # leaf-first: p1 (the deeper node) must go before p0
    assert trie.evictable_count() == 1
    assert trie.evict_one() == p1
    assert trie.evict_one() == p0
    assert trie.evict_one() is None
    assert pool.free_count == 7


def test_shared_pages_are_immutable():
    """COW contract: a sharer extending a cached prefix writes only into
    freshly allocated pages — the trie-cached page bytes never change."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(10)
    shared = rng.integers(0, 96, size=16)          # 2 full pages
    r1 = Request(id=0, prompt=np.concatenate([shared,
                                              rng.integers(0, 96, size=3)]),
                 max_new_tokens=3)
    eng = Engine(m, p, n_slots=2, max_len=64, paged=True, page_size=8)
    eng.run([r1])
    cached = {k: v for k, v in eng.cache.trie.nodes.items()}
    assert len(cached) == 2
    snap = [np.asarray(eng.cache.caches[0]["kp"][:, pid])
            for pid in cached.values()]
    r2 = Request(id=1, prompt=np.concatenate([shared,
                                              rng.integers(0, 96, size=5)]),
                 max_new_tokens=3)
    eng.run([r2])
    assert r2.n_matched == 16
    for pid, before in zip(cached.values(), snap):
        np.testing.assert_array_equal(
            np.asarray(eng.cache.caches[0]["kp"][:, pid]), before)
    assert list(r2.generated) == _reference(m, p, r2)


def test_paged_cache_reservation_accounting():
    """Reservations guarantee an admitted request can always finish:
    worst-case pages are promised at admission, materialized lazily, and
    returned on finish."""
    m, p = _model("olmo-1b")
    cache = PagedCache(m, n_slots=2, max_len=64, page_size=8, n_pages=9)
    prompt = np.arange(10, dtype=np.int32)
    assert cache.can_admit(10, 30, prompt=prompt)
    cache.admit_request(0, prompt, max_new_tokens=30)   # 5 pages total
    assert cache.pool.allocated_count == 2              # prompt pages only
    assert cache.reserved == 3
    # remaining capacity: 8 usable - 2 allocated - 3 reserved = 3 pages
    assert not cache.can_admit(10, 30, prompt=prompt)   # needs 5
    assert cache.can_admit(10, 8, prompt=prompt)        # needs 3
    cache.ensure_decode_page(0, 16)                     # page 2 materializes
    assert cache.pool.allocated_count == 3 and cache.reserved == 2
    cache.free_slot(0)
    assert cache.pool.allocated_count == 0 and cache.reserved == 0
    assert (cache.block_tables[0] == NULL_PAGE).all()


def test_deep_trie_chain_does_not_livelock_admission():
    """A deep cached chain has ONE evictable leaf but many reclaimable
    pages (cascading eviction drains it). Admission capacity must count
    the reclaimable set, or a request needing a few pages is refused
    forever while the pool sits full of discardable cache — a livelock."""
    m, p = _model("olmo-1b")
    rng = np.random.default_rng(15)
    # 15-page chain fills the 16-page pool after r1 finishes (free = 1)
    r1 = Request(id=0, prompt=rng.integers(0, 96, size=120), max_new_tokens=8)
    eng = Engine(m, p, n_slots=1, max_len=128, paged=True, page_size=8,
                 prefill_chunk_tokens=16)
    eng.run([r1])
    assert len(eng.cache.trie) == 15 and eng.cache.pool.free_count == 1
    assert eng.cache.trie.evictable_count() == 1          # deepest leaf only
    assert eng.cache.trie.reclaimable_count() == 15       # whole chain
    r2 = Request(id=1, prompt=rng.integers(0, 96, size=40), max_new_tokens=8)
    out = eng.run([r2])                                   # needs 6 pages
    assert out[1] == _reference(m, p, r2, max_len=128)
    assert list(r1.generated) == _reference(m, p, r1, max_len=128)


def test_paged_sampled_decode_runs():
    """Non-greedy decode end-to-end through the paged path: tokens stay
    in-vocab and the run drains."""
    from repro.serve import SamplingParams
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 3, seed=11)
    for i, r in enumerate(reqs):
        r.sampling = SamplingParams(temperature=0.8, top_k=8, seed=i)
    out = Engine(m, p, n_slots=2, max_len=64, paged=True,
                 page_size=8).run(reqs)
    for r in reqs:
        assert 1 <= len(out[r.id]) <= r.max_new_tokens
        assert all(0 <= t < m.cfg.vocab for t in out[r.id])


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("shape", [
    (4, 8, 4, 32, 16, 12, 3),    # GQA 2:1
    (2, 4, 4, 16, 8, 6, 4),      # MHA
    (3, 8, 2, 64, 16, 9, 2),     # GQA 4:1
])
def test_paged_attention_kernel_matches_ref(shape):
    """Pallas paged-attention (interpret mode) vs the jnp oracle across
    GQA ratios, page sizes, and ragged lengths."""
    from repro.kernels import ops
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    B, H, Kh, Dh, ps, n_pages, P = shape
    rng = np.random.default_rng(B * H)
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, ps, Kh, Dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, ps, Kh, Dh)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, n_pages, size=(B, P)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, P * ps + 1, size=(B,)), jnp.int32)
    want = paged_attention_ref(q, kp, vp, bt, lengths)
    got = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    # ops routing: interpret backend reaches the kernel
    old = ops.get_backend()
    try:
        ops.set_backend("interpret")
        got2 = ops.paged_attention(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                                   atol=2e-5, rtol=1e-5)
        ops.set_backend("jnp")
        np.testing.assert_array_equal(
            np.asarray(ops.paged_attention(q, kp, vp, bt, lengths)),
            np.asarray(want))
    finally:
        ops.set_backend(old)


# --------------------------------------------------------------- cache dtype

def test_cache_dtype_routes_through_config():
    """Satellite: cache leaves follow cfg.dtype — a f32-configured model
    must not silently get bf16 caches (the old init_cache default)."""
    import dataclasses
    from repro.models import attention as attn_lib

    m, _ = _model("olmo-1b")
    assert m.cfg.dtype == "float32"
    for c in m.init_caches(2, 16):
        for leaf in jax.tree.leaves(c):
            assert leaf.dtype in (jnp.float32, jnp.int32), leaf.dtype
    for c in m.init_paged_caches(2, 4, 8):
        for leaf in jax.tree.leaves(c):
            assert leaf.dtype in (jnp.float32, jnp.int32), leaf.dtype
    m_bf = build(dataclasses.replace(m.cfg, dtype="bfloat16"))
    k_leaf = m_bf.init_caches(2, 16)[0]["k"]
    assert k_leaf.dtype == jnp.bfloat16
    # leaf-level default is float32 now, not bfloat16
    spec = m.block_specs[0]["mixer"]
    assert attn_lib.init_cache(spec, 1, 8)["k"].dtype == jnp.float32
    assert attn_lib.init_paged_cache(spec, 1, 4, 8)["kp"].dtype == jnp.float32


def _scans(jaxpr):
    """The outermost ``scan`` equations of a jaxpr (the layer scans), under
    any other call; not the scans inside a layer."""
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            yield e
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


@pytest.mark.parametrize("arch", ["granite-8b", "rwkv6-3b", "mamba-tiny"])
def test_serving_programs_carry_the_residual_in_float32(arch):
    """In a bfloat16 model the paged decode step and the final prefill
    chunk carry the residual stream through the layer scan in float32;
    the caches keep the compute dtype and the logits come out in it."""
    import dataclasses

    cfg = MAMBA if arch == "mamba-tiny" else common.get_config(arch,
                                                               smoke=True)
    m = build(dataclasses.replace(cfg, dtype="bfloat16"))
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: m.init_paged_caches(2, 8, 8))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    decode = jax.make_jaxpr(lambda p, c, t, bt: m.decode_step(
        p, t, c, block_tables=bt, live=jnp.ones((2,), bool)))(
        params, caches, i32(2), i32(2, 4))
    chunk = jax.make_jaxpr(lambda p, c, t, bt: m.prefill_chunk(
        p, t, c, bt, 0, 0, 8, final=True))(params, caches, i32(1, 16), i32(4))
    for program in (decode, chunk):
        scans = list(_scans(program.jaxpr))
        assert scans
        for e in scans:
            x = e.invars[e.params["num_consts"]].aval
            assert x.dtype == jnp.float32 and x.shape[-1] == cfg.d_model
        logits, *new_caches = jax.tree.leaves(program.out_avals)
        assert logits.dtype == jnp.bfloat16
        assert [c.dtype for c in new_caches] == [
            c.dtype for c in jax.tree.leaves(caches)]


def _gathers(jaxpr, ndim=None):
    """``gather`` equations of a jaxpr, inside any call or scan; with
    ``ndim``, those whose operand is a float array of that many axes (1: a
    layer's bias permuted into packed order; the activations' take 2)."""
    for e in jaxpr.eqns:
        x = e.invars[0].aval if e.primitive.name == "gather" else None
        if x is not None and (ndim is None or (
                x.ndim == ndim and jnp.issubdtype(x.dtype, jnp.floating))):
            yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _gathers(inner, ndim)


@pytest.mark.parametrize("arch", ["granite-8b", "olmo-1b"])
def test_serving_programs_gather_no_bias_once_packed(arch):
    """On raw parameters every layer of the paged decode step and of a
    prefill chunk permutes its seven biases into packed order; on the
    engine's ``pack_biases`` form none does. A model without biases
    traces the same programs either way."""
    from repro.core.export import pack_biases

    m = build(common.get_config(arch, smoke=True))
    raw = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    packed = jax.eval_shape(lambda p: pack_biases(m, p)[0], raw)
    caches = jax.eval_shape(lambda: m.init_paged_caches(2, 8, 8))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)

    def programs(params):
        decode = jax.make_jaxpr(lambda p, c, t, bt: m.decode_step(
            p, t, c, block_tables=bt, live=jnp.ones((2,), bool)))(
            params, caches, i32(2), i32(2, 4))
        chunk = jax.make_jaxpr(lambda p, c, t, bt: m.prefill_chunk(
            p, t, c, bt, 0, 0, 8, final=True))(params, caches, i32(1, 16),
                                                i32(4))
        return decode, chunk

    for before, after in zip(programs(raw), programs(packed)):
        if not m.cfg.use_bias:
            assert str(after) == str(before)
            continue
        for program, per_body in ((before, 7), (after, 0)):
            scans = list(_scans(program.jaxpr))
            assert scans
            for e in scans:
                body = e.params["jaxpr"].jaxpr
                assert len(list(_gathers(body, ndim=1))) == per_body


@pytest.mark.parametrize("arch,per_layer", [("granite-8b", 7), ("olmo-1b", 0)])
def test_engine_serves_packed_biases(arch, per_layer):
    """The paged engine holds each packed bias in packed order too,
    reports how many it serves that way (also on /metrics), serves an edit
    of ``b`` assigned back through ``params``, and its greedy tokens equal
    the model's own decode on the raw parameters."""
    m = build(common.get_config(arch, smoke=True))
    key = jax.random.PRNGKey(42)   # per-index biases: zeros hide the order
    raw = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
        if getattr(path[-1], "key", None) == "b" else x,
        m.init(jax.random.PRNGKey(0)))
    eng = Engine(m, raw, n_slots=2, max_len=64, paged=True, page_size=8)
    want = m.cfg.n_layers * per_layer
    assert eng.stats_gauges()["repro_serve_packed_biases"] == want
    text = eng.metrics.prometheus(extra_gauges=eng.stats_gauges())
    assert f"\nrepro_serve_packed_biases {want:g}\n" in text
    n_bp = lambda: sum("bp" in str(k) for k, _ in
                       jax.tree_util.tree_leaves_with_path(eng.params))
    assert n_bp() == (7 if per_layer else 0)
    eng.params = raw                  # weights swapped in are packed too
    assert n_bp() == (7 if per_layer else 0)
    if not per_layer:
        return
    # ``b`` stays the source: zeroed and assigned back, it zeroes ``bp``
    eng.params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 0 if getattr(path[-1], "key", None) == "b"
        else x, eng.params)
    bps = [x for k, x in jax.tree_util.tree_leaves_with_path(eng.params)
           if "bp" in str(k)]
    assert len(bps) == 7 and not any(np.asarray(x).any() for x in bps)
    eng.params = raw
    reqs = _requests(m.cfg, 4, seed=3)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, raw, r), r.id
