"""Speculative decoding correctness.

The load-bearing properties:

* **Exactness** — greedy spec-decode is token-for-token identical to
  non-spec paged greedy (and hence to static decode), whatever the draft:
  a perfect draft (the target itself), the intended deployment (the
  folded int8 packed artifact), or an adversarial draft (different
  weights) whose frequent rejections exercise paged rollback every step.
  Staggered admission (more requests than slots) is included.
* **Fallback** — recurrent archs (mamba / rwkv) cannot re-score a
  k-token window in one dispatch, so the engine must drop to the plain
  decode loop (``spec_active == False``) and still produce exact output.
* **Sampling** — temperature > 0 rows run the rejection sampler without
  error; emitted ids stay in-vocab and lengths are honored.
* **Accounting** — per-request tokens_per_step / acceptance-rate metrics
  are consistent, both page pools conserve pages at drain, and a shared
  prompt prefix is prefilled once for the draft+target pair (trie hit
  counted once).
"""

import functools

import jax
import numpy as np
import pytest

from repro.configs import common
from repro.models import ModelConfig, build
from repro.serve import Engine, Request, RequestState, SamplingParams

MAMBA = ModelConfig(name="mamba-tiny", n_layers=2, d_model=64, n_heads=4,
                    n_kv_heads=4, d_ff=128, vocab=96, pattern=("mamba",),
                    mpd_c=4)


def _config(arch, **kw):
    """A smoke config; ``<arch>-3l`` is the same arch three layers deep."""
    if arch.endswith("-3l"):
        return common.get_config(arch[:-3], smoke=True, n_layers=3, **kw)
    return common.get_config(arch, smoke=True, **kw)


@functools.lru_cache(maxsize=None)
def _model(arch):
    cfg = MAMBA if arch == "mamba-tiny" else _config(arch)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _drafts(arch):
    """Draft zoo for ``arch``: perfect (the target itself), int8 (the
    MPD-compressed packed artifact — the intended deployment), and skewed
    (different weights — low acceptance, exercises rollback)."""
    m, p = _model(arch)
    cfg = _config(arch, mpd_mode="masked_dense")
    md = build(cfg)
    pd = md.init(jax.random.PRNGKey(0))
    return {"perfect": (m, p),
            "int8": md.to_packed(pd, fuse=True, quantize="int8"),
            "skewed": (m, m.init(jax.random.PRNGKey(7)))}


def _requests(cfg, n, seed=0, max_prompt=20, max_gen=10, sampled=False):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sp = SamplingParams(temperature=0.7 if sampled and i % 2 else 0.0,
                            top_k=8, seed=i)
        out.append(Request(
            id=i,
            prompt=rng.integers(0, cfg.vocab,
                                size=int(rng.integers(3, max_prompt))),
            max_new_tokens=int(rng.integers(2, max_gen)),
            sampling=sp))
    return out


def _run(m, p, reqs, *, spec_draft=None, spec_k=4, n_slots=2):
    eng = Engine(m, p, n_slots=n_slots, max_len=64, page_size=8,
                 spec_draft=spec_draft, spec_k=spec_k)
    return eng.run(reqs), eng


# ------------------------------------------------------------------ exactness

@pytest.mark.parametrize("arch,draft", [
    pytest.param("olmo-1b", "perfect", id="perfect"),
    pytest.param("olmo-1b", "int8", id="int8"),
    pytest.param("olmo-1b", "skewed", id="skewed"),
    pytest.param("olmo-1b-3l", "skewed", id="skewed-3l"),
])
def test_spec_greedy_matches_paged(arch, draft):
    """Greedy spec output == non-spec paged greedy, token for token, with
    staggered admission (6 requests, 2 slots). The skewed draft rejects
    often — every mismatch forces a paged rollback — yet exactness must
    hold; the perfect draft must accept everything. Three layers deep, a
    window's K/V written to or read from another layer's pages shows."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 6, seed=1)
    base, _ = _run(m, p, reqs)
    out, eng = _run(m, p, reqs, spec_draft=_drafts(arch)[draft])
    assert eng.spec_active
    assert out == base
    s = eng.metrics.summary()
    assert s["n_done"] == 6
    if draft == "perfect":
        # not exactly 1.0: the draft scores tokens through the one-query
        # decode path, the target through the batched verify path, and
        # XLA's differing reduction orders can flip a near-tie argmax —
        # which truncates a window but never breaks exactness
        assert s["draft_acceptance_rate"] > 0.9
    if draft == "skewed":
        # a disagreeing draft must actually get rejected sometimes,
        # otherwise this case isn't testing the rollback path
        assert s["draft_acceptance_rate"] < 1.0


def test_spec_various_k():
    """The acceptance rule is k-independent: k=1 and k=6 both reproduce
    the non-spec greedy stream."""
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 4, seed=3)
    base, _ = _run(m, p, reqs)
    for k in (1, 6):
        out, eng = _run(m, p, reqs, spec_draft=_drafts("olmo-1b")["int8"],
                        spec_k=k)
        assert eng.spec_active and out == base, k


# ------------------------------------------------------------------- fallback

@pytest.mark.parametrize("arch", ["rwkv6-3b", "mamba-tiny"])
def test_spec_recurrent_fallback(arch):
    """Recurrent archs silently fall back to the one-token decode loop and
    stay exact; no draft cache is built."""
    m, p = _model(arch)
    reqs = _requests(m.cfg, 3, seed=2)
    base, _ = _run(m, p, reqs)
    out, eng = _run(m, p, reqs, spec_draft=(m, p))
    assert not eng.spec_active
    assert eng.draft_cache is None
    assert out == base
    # fallback still counts decode steps: exactly one token per step
    s = eng.metrics.summary()
    assert s["tokens_per_step_mean"] == pytest.approx(1.0)
    assert s["draft_acceptance_rate"] == 0.0


def test_engine_rejects_slot_dense():
    """The engine has one memory model: asking for the removed slot-dense
    one is an error, with or without a draft."""
    m, p = _model("olmo-1b")
    for kw in ({}, {"spec_draft": (m, p)}):
        with pytest.raises(ValueError, match="slot-dense"):
            Engine(m, p, n_slots=2, max_len=64, paged=False, **kw)


# ------------------------------------------------------------------- sampling

def test_spec_sampled_runs():
    """Mixed greedy/temperature batches run the rejection sampler: correct
    lengths, in-vocab ids, and EOS-free termination at max_new_tokens."""
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 6, seed=5, sampled=True)
    out, eng = _run(m, p, reqs, spec_draft=_drafts("olmo-1b")["int8"])
    assert eng.spec_active
    for r in reqs:
        assert len(out[r.id]) == r.max_new_tokens
        assert all(0 <= t < m.cfg.vocab for t in out[r.id])


def test_spec_eos_inside_window():
    """EOS anywhere inside the accepted window stops the request there."""
    m, p = _model("olmo-1b")
    base, _ = _run(m, p, _requests(m.cfg, 4, seed=9, max_gen=12))
    eos = int(base[0][len(base[0]) // 2])       # a token mid-stream
    reqs = _requests(m.cfg, 4, seed=9, max_gen=12)
    for r in reqs:
        r.eos_id = eos
    b2, _ = _run(m, p, reqs)
    o2, eng = _run(m, p, reqs, spec_draft=_drafts("olmo-1b")["perfect"])
    assert eng.spec_active and o2 == b2
    done = [r for r in reqs if len(o2[r.id]) < r.max_new_tokens]
    assert any(o2[r.id][-1] == eos for r in done) or not done


# ----------------------------------------------------------------- accounting

def test_spec_metrics_and_pool_conservation():
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 6, seed=1)
    out, eng = _run(m, p, reqs, spec_draft=_drafts("olmo-1b")["perfect"])
    s = eng.metrics.summary()
    k = eng.spec_k
    assert 1.0 <= s["tokens_per_step_mean"] <= k + 1
    assert 0.0 <= s["draft_acceptance_rate"] <= 1.0
    for rm in eng.metrics.requests.values():
        assert rm.n_decode_steps >= 1 or rm.n_generated <= 1
        if rm.tokens_per_step is not None:
            assert rm.tokens_per_step <= k + 1
        assert rm.n_draft_accepted <= rm.n_draft_proposed
    # drain: both pools conserve pages (free + trie-held == everything)
    for cache in (eng.cache, eng.draft_cache):
        assert cache.reserved == 0
        assert (cache.pool.free_count + len(cache.trie)
                == cache.pool.n_pages - 1)
        assert (cache.block_tables == 0).all()


def test_spec_shared_prefix_prefilled_once():
    """Two requests with the same long prompt: the second's prefix comes
    from the shared trie — counted once, reused by BOTH pools (target and
    draft block tables point at their own pool's cached pages)."""
    m, p = _model("olmo-1b")
    prompt = np.arange(17, dtype=np.int32) % m.cfg.vocab
    reqs = [Request(id=i, prompt=prompt.copy(), max_new_tokens=3)
            for i in range(2)]
    out, eng = _run(m, p, reqs, spec_draft=_drafts("olmo-1b")["perfect"],
                    n_slots=1)
    assert out[0] == out[1]
    # page_size=8, 17 tokens -> 2 full pages = 16 tokens reused
    assert eng.metrics.prefill_tokens_computed == len(prompt) + 1
    trie = eng.cache.trie
    assert trie is eng.draft_cache.trie and len(trie) == 2
    for value in trie.nodes.values():
        assert isinstance(value, tuple) and len(value) == 2


def test_spec_rollback_restores_reservation():
    """After a run with a skewed (often-rejected) draft, every freed page
    went back through the reservation path — nothing leaked in either
    pool despite per-step rollbacks."""
    m, p = _model("olmo-1b")
    reqs = _requests(m.cfg, 5, seed=11, max_gen=12)
    out, eng = _run(m, p, reqs, spec_draft=_drafts("olmo-1b")["skewed"])
    assert eng.metrics.summary()["draft_acceptance_rate"] < 1.0
    for cache in (eng.cache, eng.draft_cache):
        assert cache.reserved == 0
        assert (cache.pool.free_count + len(cache.trie)
                == cache.pool.n_pages - 1)
