"""HTTP/SSE frontend correctness.

The load-bearing properties:

* **Stream exactness** — tokens streamed over SSE are identical to the
  direct Engine greedy output, including staggered admission and with
  speculative decoding enabled (the frontend only observes the engine;
  it never perturbs it).
* **Backpressure** — beyond ``queue_limit`` waiting requests, new
  generates get 429 + ``Retry-After`` and are never admitted.
* **Cancellation** — a client disconnect mid-stream cancels the request
  and returns its pages to the pool within one engine step.
* **Observability** — ``/metrics`` speaks Prometheus text and carries the
  per-class SLO attainment series; ``/healthz`` reports engine config.

All tests drive a real server on an ephemeral port inside one asyncio
loop (``auto_pump=False`` where step ordering must be pinned down).
"""

import asyncio
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import common
from repro.models import build
from repro.serve import Engine, GenerateServer, Request
from repro.serve.cache import NULL_PAGE


@functools.lru_cache(maxsize=None)
def _model():
    cfg = common.get_config("olmo-1b", smoke=True)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _reference(m, p, prompt, n, max_len=64):
    caches = m.init_caches(1, max_len)
    lg, caches = jax.jit(m.prefill)(p, jnp.asarray(prompt)[None], caches)
    toks = [int(jnp.argmax(lg, -1)[0])]
    decode = jax.jit(m.decode_step)
    while len(toks) < n:
        lg, caches = decode(p, jnp.asarray([toks[-1]]), caches)
        toks.append(int(jnp.argmax(lg, -1)[0]))
    return toks


# ----------------------------------------------------------- client helpers

def _parse_sse(data: bytes):
    events = []
    body = data.split(b"\r\n\r\n", 1)[1] if b"\r\n\r\n" in data else data
    for block in body.split(b"\n\n"):
        lines = block.split(b"\n")
        ev = next((l[7:].decode() for l in lines
                   if l.startswith(b"event: ")), None)
        payload = next((l[6:] for l in lines if l.startswith(b"data: ")), None)
        if ev is not None and payload is not None:
            events.append((ev, json.loads(payload)))
    return events


def _post(path: str, spec: dict) -> bytes:
    body = json.dumps(spec).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


async def _generate(port, spec):
    """Stream one generate call to completion; returns (tokens, done)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(_post("/v1/generate", spec))
    await writer.drain()
    data = b""
    while True:
        chunk = await reader.read(65536)
        if not chunk:
            break
        data += chunk
    writer.close()
    events = _parse_sse(data)
    toks = [e["token"] for ev, e in events if ev == "token"]
    done = next((e for ev, e in events if ev == "done"), None)
    return toks, done


async def _get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    data = b""
    while True:
        chunk = await reader.read(65536)
        if not chunk:
            break
        data += chunk
    writer.close()
    return data.decode()


async def _drive(engine, server, until, limit=400):
    """Manual pump (auto_pump=False): step the engine between event-loop
    turns until ``until()`` holds."""
    for _ in range(limit):
        if until():
            return
        if engine.has_work():
            engine.step()
        await asyncio.sleep(0.002)
    raise AssertionError("drive loop did not converge")


# ------------------------------------------------------------------ exactness

def test_sse_stream_matches_direct_engine():
    """Three staggered clients (mixed priorities) against 2 slots: every
    SSE stream must be token-identical to the direct-engine greedy run."""
    m, p = _model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, m.cfg.vocab, size=int(n)).tolist()
               for n in (9, 13, 7)]
    engine = Engine(m, p, n_slots=2, max_len=64, page_size=8)

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=8)
        await server.start()
        async def delayed(i, prio, delay):
            await asyncio.sleep(delay)
            return await _generate(server.port, {
                "prompt": prompts[i], "max_new_tokens": 6, "priority": prio,
                "ttft_slo_ms": 60_000, "e2e_slo_ms": 60_000})
        results = await asyncio.gather(
            delayed(0, "interactive", 0.0),
            delayed(1, "batch", 0.03),
            delayed(2, "interactive", 0.06))
        await server.close()
        return results

    results = asyncio.run(main())
    for i, (toks, done) in enumerate(results):
        assert toks == _reference(m, p, prompts[i], 6), i
        assert done is not None and done["n_tokens"] == 6
        assert done["finish_reason"] == "length"
    s = engine.metrics.summary()
    assert s["n_done"] == 3
    assert s["interactive_ttft_slo_attainment"] == 1.0
    assert s["interactive_e2e_slo_attainment"] == 1.0


def test_sse_stream_matches_with_spec_draft():
    """--spec-draft composes with the frontend: a perfect draft (the
    target itself) streams token-identical output over SSE."""
    m, p = _model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, m.cfg.vocab, size=int(n)).tolist()
               for n in (10, 12)]
    engine = Engine(m, p, n_slots=2, max_len=64, page_size=8,
                    spec_draft=(m, p), spec_k=3)
    assert engine.spec_active

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=8)
        await server.start()
        results = await asyncio.gather(
            _generate(server.port, {"prompt": prompts[0],
                                    "max_new_tokens": 7}),
            _generate(server.port, {"prompt": prompts[1],
                                    "max_new_tokens": 7,
                                    "priority": "batch"}))
        await server.close()
        return results

    results = asyncio.run(main())
    for i, (toks, done) in enumerate(results):
        assert toks == _reference(m, p, prompts[i], 7), i
        assert done["n_tokens"] == 7


# --------------------------------------------------------------- backpressure

def test_backpressure_429_retry_after():
    """With the pump paused nothing drains: queue_limit=1 admits one
    waiting request and turns the next away with 429 + Retry-After."""
    m, p = _model()
    engine = Engine(m, p, n_slots=1, max_len=64, page_size=8)

    async def main():
        server = GenerateServer(engine, port=0, queue_limit=1,
                                auto_pump=False)
        await server.start()
        first = asyncio.create_task(_generate(server.port, {
            "prompt": [1, 2, 3, 4], "max_new_tokens": 4}))
        await _drive(engine, server,
                     lambda: engine.scheduler.n_running >= 1)
        # the only slot is now busy: the next request parks in the
        # waiting queue, filling it to queue_limit
        second = asyncio.create_task(_generate(server.port, {
            "prompt": [5, 6, 7, 8], "max_new_tokens": 4}))
        await _drive(engine, server,
                     lambda: len(engine.scheduler.waiting) >= 1)

        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        writer.write(_post("/v1/generate", {"prompt": [9, 9],
                                            "max_new_tokens": 2}))
        await writer.drain()
        data = await reader.read(65536)
        writer.close()
        status = data.split(b"\r\n", 1)[0].decode()
        headers = data.split(b"\r\n\r\n", 1)[0].decode()
        assert "429" in status, status
        assert "Retry-After:" in headers, headers
        assert engine.metrics.n_rejected == 1

        # the parked requests still finish once the pump resumes
        await asyncio.gather(
            _drive(engine, server, lambda: not engine.has_work()),
            first, second)
        await server.close()

    asyncio.run(main())
    assert engine.metrics.summary()["n_rejected"] == 1


# --------------------------------------------------------------- cancellation

def test_disconnect_cancels_and_returns_pages():
    """Dropping the connection mid-stream cancels the request: it leaves
    the scheduler and its non-shared pages return to the pool within one
    engine step; a concurrent stream is unperturbed."""
    m, p = _model()
    rng = np.random.default_rng(2)
    keep_prompt = rng.integers(0, m.cfg.vocab, size=9).tolist()
    drop_prompt = rng.integers(0, m.cfg.vocab, size=11).tolist()
    engine = Engine(m, p, n_slots=2, max_len=64, page_size=8)

    async def main():
        server = GenerateServer(engine, port=0, auto_pump=False)
        await server.start()
        keeper = asyncio.create_task(_generate(server.port, {
            "prompt": keep_prompt, "max_new_tokens": 10}))

        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        writer.write(_post("/v1/generate", {"prompt": drop_prompt,
                                            "max_new_tokens": 10}))
        await writer.drain()
        got = b""
        while b"event: token" not in got:        # first token arrives
            if engine.has_work():
                engine.step()
            await asyncio.sleep(0.002)
            got += await asyncio.wait_for(reader.read(4096), 1)
        victim = next(r for r in engine.scheduler.running.values()
                      if list(r.prompt) == drop_prompt)
        held_before = int((engine.cache.block_tables[victim.slot]
                           != NULL_PAGE).sum())
        assert held_before > 0
        writer.close()                           # abrupt disconnect
        await writer.wait_closed()

        # within one engine step the cancel lands and the slot is free
        await _drive(engine, server,
                     lambda: victim.slot is None, limit=50)
        assert victim.id not in {r.id for r in
                                 engine.scheduler.running.values()}
        assert engine.metrics.n_cancelled == 1

        toks, done = await asyncio.gather(
            _drive(engine, server, lambda: not engine.has_work()),
            keeper)
        await server.close()
        return keeper.result()

    toks, done = asyncio.run(main())
    assert toks == _reference(m, p, keep_prompt, 10)
    # every page is back: only trie-cached prefix pages stay allocated,
    # and each of those is exactly trie-held (ref == 1)
    pool = engine.cache.pool
    trie_held = sum(len(engine.cache.trie._as_tuple(v))
                    for v in engine.cache.trie.nodes.values())
    assert pool.allocated_count == trie_held
    assert (pool.ref[1:] <= 1).all()


# ------------------------------------------------------------- observability

def test_metrics_and_healthz_endpoints():
    m, p = _model()
    engine = Engine(m, p, n_slots=2, max_len=64, page_size=8)

    async def main():
        server = GenerateServer(engine, port=0)
        await server.start()
        toks, _ = await _generate(server.port, {
            "prompt": [3, 1, 4, 1, 5], "max_new_tokens": 4,
            "priority": "batch", "ttft_slo_ms": 60_000})
        metrics = await _get(server.port, "/metrics")
        health = await _get(server.port, "/healthz")
        missing = await _get(server.port, "/nope")
        bad = await _get(server.port, "/v1/generate")   # GET on POST route
        await server.close()
        return toks, metrics, health, missing, bad

    toks, metrics, health, missing, bad = asyncio.run(main())
    assert len(toks) == 4
    assert "text/plain" in metrics.splitlines()[1]
    for series in ("repro_serve_requests_total{priority=\"batch\"} 1",
                   "repro_serve_slo_attainment{priority=\"batch\","
                   "slo=\"ttft\"} 1",
                   "repro_serve_queue_depth",
                   "repro_serve_preemptions_total",
                   "repro_serve_kv_pages_free",
                   "repro_serve_packed_biases 0",
                   "# TYPE repro_serve_ttft_seconds summary"):
        assert series in metrics, series
    assert json.loads(health.split("\r\n\r\n", 1)[1])["n_slots"] == 2
    assert missing.startswith("HTTP/1.1 404")
    assert bad.startswith("HTTP/1.1 405")


def test_bad_request_400():
    m, p = _model()
    engine = Engine(m, p, n_slots=1, max_len=32, page_size=8)

    async def main():
        server = GenerateServer(engine, port=0, auto_pump=False)
        await server.start()
        outs = []
        for spec in ({"prompt": [1, 2], "priority": "bulk"},
                     {"prompt": []},
                     {"prompt": [1, 2], "max_new_tokens": 99}):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.port)
            writer.write(_post("/v1/generate", spec))
            await writer.drain()
            outs.append(await reader.read(65536))
            writer.close()
        await server.close()
        return outs

    for data in asyncio.run(main()):
        assert data.startswith(b"HTTP/1.1 400"), data[:60]
    assert not engine.has_work()
