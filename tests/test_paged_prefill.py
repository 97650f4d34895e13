"""Chunked-prefill flash attention over paged KV — exactness contract.

Three layers of the contract, mirroring the decode kernel's tests:

* the jnp oracle (``ref.paged_prefill_attention_ref``) is BITWISE equal to
  the dense gather + ``_attend`` path it replaced (masked columns are
  exact zeros, exact under any reduction order) — this is what keeps
  paged serving token-identical to the dense engine on CPU;
* the Pallas kernel (interpret mode) matches the oracle to float32
  online-softmax tolerance across GQA ratios, trie-hit offsets
  (``start > 0``), right-padded final chunks, and multi-tile query grids;
* engine-level: chunked-paged greedy decode equals the monolithic dense
  prefill reference across chunk sizes and under the interpret (kernel)
  prefill backend, including a prefix-trie hit that starts prefill past
  page 0.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import common
from repro.kernels import ops, ref
from repro.kernels import paged_prefill as pk
from repro.models import attention, build
from repro.serve import Engine, Request

# (H, Kh, Dh, page_size, n_pages, P, Tc, start, chunk_len, q_tile)
SHAPES = [
    (4, 4, 8, 4, 16, 8, 8, 0, 8, None),      # MHA, first chunk, full
    (8, 2, 16, 4, 32, 8, 8, 8, 8, None),     # GQA 4:1, start > 0
    (8, 2, 16, 4, 32, 8, 8, 16, 5, 2),       # right-padded final, tiled
    (6, 3, 8, 8, 24, 4, 16, 16, 16, 4),      # GQA 2:1, multi-tile
    (4, 1, 8, 4, 16, 8, 8, 4, 3, None),      # MQA, padded
]


def _case(H, Kh, Dh, ps, n_pages, P, Tc, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((Tc, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, ps, Kh, Dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, ps, Kh, Dh)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, n_pages, size=(P,)), jnp.int32)
    return q, kp, vp, bt


def _dense_attend(q, kp, vp, bt, start, chunk_len):
    """The pre-kernel prefill path: gather the full table width, run the
    dense ``_attend`` with causal + depth masks."""
    Tc, H, Dh = q.shape
    _, ps, Kh, _ = kp.shape
    P = bt.shape[0]
    kc = kp[bt].reshape(1, P * ps, Kh, Dh).astype(q.dtype)
    vc = vp[bt].reshape(1, P * ps, Kh, Dh).astype(q.dtype)
    q_pos = start + jnp.arange(Tc)
    kv_valid = jnp.arange(P * ps)[None, :] < start + chunk_len
    o = attention._attend(q[None], kc, vc, q_pos, kv_valid, causal=True)
    return o[0]


@pytest.mark.parametrize("shape", SHAPES)
def test_ref_bitwise_vs_dense_attend(shape):
    """The oracle must reproduce the dense gather + _attend path BITWISE —
    the serve exactness contract rides on this equality."""
    H, Kh, Dh, ps, n_pages, P, Tc, start, clen, _ = shape
    q, kp, vp, bt = _case(H, Kh, Dh, ps, n_pages, P, Tc, seed=1)
    r = ref.paged_prefill_attention_ref(q, kp, vp, bt, start, clen)
    d = _dense_attend(q, kp, vp, bt, start, clen)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(d))


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_ref(shape):
    """Pallas kernel (interpret mode) vs the oracle: online softmax is not
    bitwise vs one-shot, so f32 tolerance. Only the chunk_len real rows
    are compared — padded tail rows are garbage the model never reads."""
    H, Kh, Dh, ps, n_pages, P, Tc, start, clen, qt = shape
    q, kp, vp, bt = _case(H, Kh, Dh, ps, n_pages, P, Tc, seed=2)
    r = np.asarray(ref.paged_prefill_attention_ref(q, kp, vp, bt, start,
                                                   clen))[:clen]
    o = np.asarray(pk.paged_prefill_attention(
        q, kp, vp, bt, start, clen, interpret=True, q_tile=qt))[:clen]
    np.testing.assert_allclose(o, r, atol=2e-5, rtol=1e-5)


def test_kernel_reads_cold_pages_safely():
    """Pages past the causal horizon are skipped entirely: poisoning them
    with NaN must not leak into the output (the DMA-skip predicate is the
    ∝-depth read guarantee)."""
    H, Kh, Dh, ps, n_pages, P, Tc = 4, 2, 8, 4, 16, 8, 8
    q, kp, vp, bt = _case(H, Kh, Dh, ps, n_pages, P, Tc, seed=3)
    start, clen = 4, 8
    depth_pages = (start + clen + ps - 1) // ps
    # poison the pool pages the table maps beyond the depth
    bad = np.asarray(bt)[depth_pages:]
    kp = kp.at[bad].set(jnp.nan)
    vp = vp.at[bad].set(jnp.nan)
    o = np.asarray(pk.paged_prefill_attention(q, kp, vp, bt, start, clen,
                                              interpret=True))[:clen]
    assert np.isfinite(o).all()


def test_ops_routing():
    """jnp route == oracle bitwise; the prefill-backend override routes to
    the kernel independently of the global backend and restores cleanly."""
    H, Kh, Dh, ps, n_pages, P, Tc = 8, 2, 16, 4, 32, 8, 8
    q, kp, vp, bt = _case(H, Kh, Dh, ps, n_pages, P, Tc, seed=4)
    start, clen = 8, 8
    r = np.asarray(ref.paged_prefill_attention_ref(q, kp, vp, bt, start,
                                                   clen))
    saved = ops._PREFILL_BACKEND
    try:
        ops.set_prefill_backend("jnp")
        np.testing.assert_array_equal(
            np.asarray(ops.paged_prefill_attention(q, kp, vp, bt, start,
                                                   clen)), r)
        ops.set_prefill_backend("interpret")
        assert ops.prefill_backend() == "interpret"
        got = np.asarray(ops.paged_prefill_attention(q, kp, vp, bt, start,
                                                     clen))
        np.testing.assert_allclose(got, r, atol=2e-5, rtol=1e-5)
    finally:
        ops.set_prefill_backend(saved)
    # with no override, prefill follows the global backend
    assert ops.prefill_backend() == ops.get_backend()


# ------------------------------------------------------------- engine level

@functools.lru_cache(maxsize=None)
def _model():
    cfg = common.get_config("olmo-1b", smoke=True)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _reference(m, p, req, max_len=64):
    """Monolithic dense prefill + lockstep greedy decode of one request."""
    caches = m.init_caches(1, max_len)
    lg, caches = jax.jit(m.prefill)(p, jnp.asarray(req.prompt)[None], caches)
    toks = [int(jnp.argmax(lg, -1)[0])]
    decode = jax.jit(m.decode_step)
    while len(toks) < req.max_new_tokens:
        lg, caches = decode(p, jnp.asarray([toks[-1]]), caches)
        toks.append(int(jnp.argmax(lg, -1)[0]))
    return toks


@pytest.mark.parametrize("chunk_tokens", [8, 16, 24])
def test_chunked_equals_monolithic_across_chunk_sizes(chunk_tokens):
    """Greedy output is invariant to how prefill is chunked — including a
    prompt length that is not a chunk multiple (right-padded final
    chunk)."""
    m, p = _model()
    rng = np.random.default_rng(6)
    reqs = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=plen),
                    max_new_tokens=6)
            for i, plen in enumerate([21, 37, 8])]
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 prefill_chunk_tokens=chunk_tokens)
    out = eng.run(reqs)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), (chunk_tokens, r.id)


def test_interpret_kernel_engine_parity():
    """The full engine under the interpret (kernel) prefill backend stays
    token-identical to the monolithic dense reference — the serve-level
    proof the flash path can replace the gather path."""
    m, p = _model()
    rng = np.random.default_rng(7)
    reqs = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=plen),
                    max_new_tokens=5)
            for i, plen in enumerate([19, 33])]
    saved = ops._PREFILL_BACKEND
    ops.set_prefill_backend("interpret")
    try:
        eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                     prefill_chunk_tokens=16)
        out = eng.run(reqs)
    finally:
        ops.set_prefill_backend(saved)
    for r in reqs:
        assert out[r.id] == _reference(m, p, r), r.id


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_trie_hit_offsets_start_past_zero(backend):
    """Two requests sharing a page-aligned prefix: the second's prefill
    starts at the trie-matched depth (start > 0 in its FIRST chunk), and
    its output must still equal the full dense reference."""
    m, p = _model()
    rng = np.random.default_rng(8)
    sys_prompt = rng.integers(0, m.cfg.vocab, size=24)     # 3 full pages
    reqs = [Request(id=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(0, m.cfg.vocab, size=7 + 5 * i)]),
                    max_new_tokens=5)
            for i in range(2)]
    saved = ops._PREFILL_BACKEND
    ops.set_prefill_backend(backend)
    try:
        eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                     prefill_chunk_tokens=16)
        eng.submit(reqs[0])
        while eng.has_work():
            eng.step()
        skipped0 = eng.n_prefill_tokens_skipped
        eng.submit(reqs[1])
        while eng.has_work():
            eng.step()
    finally:
        ops.set_prefill_backend(saved)
    # the second request provably reused trie pages -> its first chunk ran
    # with start > 0
    assert eng.n_prefill_tokens_skipped - skipped0 >= 16
    for r in reqs:
        assert list(r.generated) == _reference(m, p, r), (backend, r.id)


def test_warmup_covers_prefill_ladder():
    """warmup() precompiles every (prefill width x final variant) the
    engine can dispatch; a post-warmup serve must add no new chunk
    compiles."""
    m, p = _model()
    eng = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                 prefill_chunk_tokens=16)
    assert eng.prefill_widths() == [w for w in eng.decode_widths() if w >= 2]
    eng.warmup()
    n0 = eng._chunk._cache_size()
    assert n0 == 2 * len(eng.prefill_widths())
    rng = np.random.default_rng(9)
    reqs = [Request(id=i, prompt=rng.integers(0, m.cfg.vocab, size=30),
                    max_new_tokens=4) for i in range(2)]
    eng.run(reqs)
    assert eng._chunk._cache_size() == n0
