"""Distributed serving: TP-sharded paged kernels and the replica router.

Two layers of exactness guarantees:

* **TP bit-identity** — head-parallel ``shard_map`` sharding of the paged
  attention ops, and a whole Engine running under a ``model`` mesh, must
  produce *bit-identical* greedy tokens vs the single-device path (MHA
  and GQA). Runs in a subprocess with forced host devices, per repo
  convention.
* **Router semantics** — least-loaded dispatch, prefix-affinity override,
  disaggregated prefill->decode handoff parity, and dead-replica drain
  all preserve the single-engine token streams; the fleet metrics merge
  never double-counts.
"""

import functools
import re

import jax
import numpy as np
import pytest

from conftest import run_forced_device_subprocess as _run_subprocess
from repro.configs import common
from repro.models import build
from repro.serve import Engine, Request, Router, RouterMetrics, ServeMetrics
from repro.serve.router import prefix_affinity_key


@functools.lru_cache(maxsize=None)
def _model():
    cfg = common.get_config("olmo-1b", smoke=True)
    m = build(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _engine(**kw):
    _, m, p = _model()
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    return Engine(m, p, **kw)


def _requests(n, seed=0, max_prompt=20, max_gen=10, prefix=None):
    cfg, _, _ = _model()
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, cfg.vocab,
                              size=int(rng.integers(3, max_prompt)))
        if prefix is not None:
            prompt = np.concatenate([prefix, prompt])
        out.append(Request(id=i, prompt=prompt,
                           max_new_tokens=int(rng.integers(2, max_gen))))
    return out


def _run(engine, reqs):
    done = {}
    engine.done_cb = lambda r: done.setdefault(r.id, list(r.generated))
    for r in reqs:
        engine.submit(r)
    steps = 0
    while engine.has_work():
        assert engine.step() or not engine.has_work()
        steps += 1
        assert steps < 5000, "engine wedged"
    return done


# ----------------------------------------------------- TP bit-identity

def test_tp_sharded_paged_ops_bit_identical():
    """Op level: paged decode / verify / prefill attention under a 2-way
    model mesh return bit-identical outputs to the unsharded ops, for MHA
    (Kh=4) and GQA (Kh=2, 4 q heads); an indivisible Kh falls back to the
    unsharded path with correct results."""
    _run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.dist import sharding as sh
from repro.kernels import ops

from repro.dist.mesh import make_mesh
mesh = make_mesh((2,), ("model",))

def pools(kh, dh=8, n_pages=6, ps=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(ks[0], (n_pages, ps, kh, dh), jnp.float32)
    v = jax.random.normal(ks[1], (n_pages, ps, kh, dh), jnp.float32)
    return k, v

for kh, qh in ((4, 4), (2, 4), (3, 3)):   # MHA, GQA, indivisible->fallback
    kp, vp = pools(kh)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, qh, 8), jnp.float32)
    bt = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0]], jnp.int32)
    lengths = jnp.asarray([6, 11], jnp.int32)
    base = ops.paged_attention(q, kp, vp, bt, lengths)
    with sh.use_mesh(mesh):
        tp = jax.jit(ops.paged_attention)(q, kp, vp, bt, lengths)
    assert np.array_equal(np.asarray(base), np.asarray(tp)), kh

    # verify window
    qw = jax.random.normal(jax.random.PRNGKey(8), (2, 3, qh, 8), jnp.float32)
    pos0 = jnp.asarray([5, 9], jnp.int32)
    basew = ops.paged_attention_verify(qw, kp, vp, bt, pos0)
    with sh.use_mesh(mesh):
        tpw = jax.jit(ops.paged_attention_verify)(qw, kp, vp, bt, pos0)
    assert np.array_equal(np.asarray(basew), np.asarray(tpw)), kh

    # prefill chunk
    qc = jax.random.normal(jax.random.PRNGKey(9), (4, qh, 8), jnp.float32)
    row = jnp.asarray([1, 2, 3, 0], jnp.int32)
    basec = ops.paged_prefill_attention(qc, kp, vp, row,
                                        jnp.asarray(4, jnp.int32),
                                        jnp.asarray(4, jnp.int32))
    with sh.use_mesh(mesh):
        tpc = jax.jit(ops.paged_prefill_attention)(
            qc, kp, vp, row, jnp.asarray(4, jnp.int32),
            jnp.asarray(4, jnp.int32))
    assert np.array_equal(np.asarray(basec), np.asarray(tpc)), kh
print("OK")
""", n_devices=4)


def test_tp_engine_greedy_token_identical():
    """Engine level: the full paged serve loop (chunked prefill + decode +
    prefix trie) under a 2-way model mesh emits token-for-token identical
    greedy output to the single-device engine, for an MHA and a GQA
    config. The engine captures the mesh at construction and re-enters it
    around warmup and every step, so the comparison covers the exact
    closure the production pump compiles."""
    _run_subprocess("""
import jax, numpy as np
from repro.dist import sharding as sh
from repro.models import ModelConfig, build
from repro.serve import Engine, Request

def run(m, p, mesh):
    import contextlib
    reqs = []
    rng = np.random.default_rng(0)
    for i in range(6):
        reqs.append(Request(id=i,
            prompt=rng.integers(0, m.cfg.vocab, size=int(rng.integers(3, 20))),
            max_new_tokens=int(rng.integers(2, 10))))
    ctx = sh.use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        eng = Engine(m, p, n_slots=2, max_len=64, paged=True, page_size=8)
    done = {}
    eng.done_cb = lambda r: done.setdefault(r.id, list(r.generated))
    for r in reqs:
        eng.submit(r)
    while eng.has_work():
        eng.step()
    return done

for kwargs in (dict(n_heads=4, n_kv_heads=4), dict(n_heads=4, n_kv_heads=2)):
    cfg = ModelConfig(name="tp-test", n_layers=2, d_model=32, d_ff=64,
                      vocab=96, pattern=("attn",), mpd_c=4, **kwargs)
    m = build(cfg)
    p = m.init(jax.random.PRNGKey(0))
    base = run(m, p, None)
    from repro.dist.mesh import make_mesh
    mesh = make_mesh((2,), ("model",))
    tp = run(m, p, mesh)
    assert tp == base, (kwargs, tp, base)
print("OK")
""", n_devices=4)


# ------------------------------------------------------- router dispatch

def test_router_least_loaded_round_robins_fresh_replicas():
    r = Router([_engine(), _engine()])
    # prompts shorter than a page carry no affinity key -> pure least-loaded
    reqs = _requests(4, max_prompt=6)
    for q in reqs:
        r.submit(q)
    assert [r._owner[q.id] for q in reqs] == [0, 1, 0, 1]
    assert r.metrics.affinity_hit_rate == 0.0
    _run(r, [])          # drain


def test_router_prefix_affinity_overrides_load():
    r = Router([_engine(), _engine()])
    cfg, _, _ = _model()
    prefix = np.arange(24) % cfg.vocab       # 3 pages of shared prefix
    reqs = _requests(4, seed=3, max_prompt=8, prefix=prefix)
    done = _run(r, reqs)
    owners = {r._owner[q.id] for q in reqs}
    assert len(owners) == 1, "shared-prefix requests split across replicas"
    assert r.metrics.n_affinity_hits > 0
    assert len(done) == 4
    # the stuck-together replica really reused the prefix
    owner = owners.pop()
    assert r.replicas[owner].n_prefill_tokens_skipped > 0


def test_router_matches_single_engine_tokens():
    reqs = _requests(6, seed=1)
    base = _run(_engine(), _requests(6, seed=1))
    got = _run(Router([_engine(), _engine()]), reqs)
    assert got == base


def test_router_warmup_compiles_every_replica():
    """Router.warmup warms all replicas (concurrently): serving afterwards
    compiles no decode or prefill-chunk program on any of them."""
    engines = [_engine(), _engine()]
    r = Router(engines)
    r.warmup()

    def sizes():
        return [(e._decode_paged._cache_size(), e._chunk._cache_size())
                for e in engines]

    warm = sizes()
    assert warm == [(len(e.decode_widths()), 2 * len(e.prefill_widths()))
                    for e in engines]
    _run(r, _requests(6, seed=3))
    assert sizes() == warm


def test_replicas_placed_one_per_device():
    """``--replicas 4`` on four devices: replica i's params and KV pages
    live on ``jax.devices()[i]``, and the fleet's greedy tokens equal one
    engine's."""
    _run_subprocess("""
import jax
from repro.launch import serve

flags = ["--arch", "olmo-1b", "--smoke", "--paged", "--page-size", "8",
         "--slots", "2", "--requests", "6", "--rate", "50",
         "--prompt-len", "16", "--gen", "4"]

def tokens(engine, args, cfg):
    reqs = serve.make_requests(cfg, n_requests=args.requests, rate=args.rate,
                               prompt_len=args.prompt_len, gen=args.gen)
    serve.serve_stream(engine, reqs)
    return [list(r.generated) for r in reqs]

args = serve.build_parser().parse_args(flags + ["--replicas", "4"])
cfg, model, params = serve._load_model(args)
router, _ = serve._build_serving(args, model, params, chaos=False)
for i, eng in enumerate(router.replicas):
    held = {d for leaf in jax.tree.leaves((eng.params, eng.cache.caches))
            for d in leaf.devices()}
    assert held == {jax.devices()[i]}, (i, held)
router.warmup()
got = tokens(router, args, cfg)
one, _ = serve._build_serving(serve.build_parser().parse_args(flags),
                              model, params, chaos=False)
assert got == tokens(one, args, cfg)
print("OK")
""", n_devices=4)


def test_router_disagg_handoff_token_identical():
    base = _run(_engine(), _requests(6, seed=2))
    r = Router([_engine(), _engine()], disagg=True, n_prefill=1)
    got = _run(r, _requests(6, seed=2))
    assert got == base
    assert r.metrics.n_handoffs > 0
    assert r.replicas[0].n_handoffs_out == r.replicas[1].n_handoffs_in \
        == r.metrics.n_handoffs
    # fleet accounting stays exact across the migration: every request
    # counted done exactly once, token totals match the baseline
    s = r.metrics.summary()
    assert s["n_done"] == 6
    assert s["total_tokens"] == sum(len(t) for t in base.values())


def test_router_disagg_rejects_unsuitable_engines():
    with pytest.raises(ValueError):
        Router([_engine(paged=False), _engine(paged=False)], disagg=True)
    with pytest.raises(ValueError):
        Router([_engine()], disagg=True)


def test_router_dead_replica_drains_to_survivor():
    reqs = _requests(6, seed=4)
    base = _run(_engine(), _requests(6, seed=4))
    r = Router([_engine(), _engine()])
    done = {}
    r.done_cb = lambda q: done.setdefault(q.id, list(q.generated))
    for q in reqs:
        r.submit(q)
    victims = [q.id for q in reqs if r._owner[q.id] == 0]
    assert victims, "least-loaded should have placed work on replica 0"
    r.replicas[0].step()                     # some in-flight progress
    orig_step = type(r.replicas[0]).step

    def boom(self):
        raise RuntimeError("injected replica death")

    r.replicas[0].step = boom.__get__(r.replicas[0])
    steps = 0
    while r.has_work():
        r.step()
        steps += 1
        assert steps < 5000, "router wedged after replica death"
    r.replicas[0].step = orig_step.__get__(r.replicas[0])
    assert r.live == [False, True]
    assert r.metrics.n_replica_deaths == 1
    assert r.metrics.n_drained >= len(victims)
    assert {q: done[q] for q in sorted(done)} == base
    # drained requests now belong to the survivor
    assert all(r._owner[v] == 1 for v in victims)
    # merged metrics don't double-count regenerated tokens
    s = r.metrics.summary()
    assert s["total_tokens"] == sum(len(t) for t in base.values())


def test_router_last_replica_death_propagates():
    r = Router([_engine()])
    r.submit(_requests(1)[0])

    def boom(self):
        raise RuntimeError("injected replica death")

    r.replicas[0].step = boom.__get__(r.replicas[0])
    with pytest.raises(RuntimeError, match="injected replica death"):
        r.step()
    assert r.live == [False]


def test_router_cancel_routes_to_owner():
    r = Router([_engine(), _engine()])
    reqs = _requests(2, max_prompt=6)
    for q in reqs:
        r.submit(q)
    r.cancel(reqs[0])
    assert r.replicas[0].metrics.n_cancelled == 1
    assert r.replicas[1].metrics.n_cancelled == 0
    _run(r, [])


# ------------------------------------------------------- metrics merging

def test_affinity_key_page_aligned_and_capped():
    p = np.arange(40, dtype=np.int32)
    assert prefix_affinity_key(p[:7], 8, 4) is None          # < one page
    assert prefix_affinity_key(p[:16], 8, 4) == \
        prefix_affinity_key(p[:23], 8, 4)                     # page-aligned
    assert prefix_affinity_key(p, 8, 2) == \
        prefix_affinity_key(p[:16], 8, 2)                     # capped
    q = p.copy()
    q[0] += 1
    assert prefix_affinity_key(p[:16], 8, 4) != \
        prefix_affinity_key(q[:16], 8, 4)


def test_router_metrics_one_scrape_per_family():
    a, b = ServeMetrics(clock=lambda: 1.0), ServeMetrics(clock=lambda: 2.0)
    a.on_submit(1, 4)
    a.on_token(1)
    a.on_done(1)
    b.on_submit(2, 4)
    rm = RouterMetrics([a, b])
    rm.on_reject()
    text = rm.prometheus({"repro_serve_slots_total": 4.0})
    # every family renders exactly one HELP/TYPE header...
    for fam in ("repro_serve_requests_total", "repro_serve_tokens_generated"
                "_total", "repro_serve_router_agg_tok_s",
                "repro_serve_compiles_total"):
        assert text.count(f"# TYPE {fam} ") == 1, fam
    # compiles are per process: one fleet sample, not one per replica
    assert len(re.findall(r"^repro_serve_compiles_total", text, re.M)) == 1
    # ...with per-replica samples distinguished by label
    assert 'replica="0"' in text and 'replica="1"' in text
    assert "repro_serve_router_replica_occupancy" in text
    s = rm.summary()
    assert s["n_requests"] == 2 and s["n_rejected"] == 1
    assert s["n_replicas"] == 2


def test_router_metrics_clock_fans_out():
    a, b = ServeMetrics(), ServeMetrics()
    rm = RouterMetrics([a, b])
    fake = lambda: 42.0                                       # noqa: E731
    rm.clock = fake
    assert a.clock is fake and b.clock is fake
