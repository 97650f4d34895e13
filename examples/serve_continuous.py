"""Continuous-batching twin of serve_batched.py: a stream of variable-length
requests flows through the ``repro.serve`` engine — FCFS admission into cache
slots, chunked prefill into a KV page pool, per-request stops — instead of one
lockstep batch.

The closing section re-decodes the greedy requests one at a time on the
static path (``prefill`` + ``decode_step`` over a contiguous cache) and checks
the engine's rows match token-for-token.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, build
from repro.serve import Engine, Request, SamplingParams

cfg = ModelConfig(name="server", n_layers=4, d_model=256, n_heads=8,
                  n_kv_heads=4, d_ff=512, vocab=1024, mpd_c=8, q_chunk=1024)
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
print(f"serving {model.param_count():,} packed params (c={cfg.mpd_c})")

# a mixed workload: 12 requests, varying prompt/output lengths, two sampling
# policies — more requests than the 4 slots, so the engine recycles slots
rng = np.random.default_rng(0)
requests = [
    Request(id=i,
            prompt=rng.integers(0, cfg.vocab, size=int(rng.integers(4, 33))),
            max_new_tokens=int(rng.integers(4, 17)),
            sampling=(SamplingParams()                     # greedy
                      if i % 2 == 0 else
                      SamplingParams(temperature=0.7, top_k=20, seed=i)))
    for i in range(12)
]

engine = Engine(model, params, n_slots=4, max_len=64, page_size=8)
outputs = engine.run(requests)          # submit + step until drained

for req in requests:
    toks = outputs[req.id]
    print(f"req {req.id}: prompt {len(req.prompt):2d} toks -> "
          f"{len(toks):2d} generated  {toks[:8]}...")

s = engine.metrics.summary()
print(f"{s['n_done']} requests, {s['total_tokens']} tokens, "
      f"{s['agg_tok_s']:.0f} tok/s aggregate, "
      f"ttft p50 {s['ttft_p50_s']*1e3:.0f} ms, "
      f"occupancy {s['occupancy_mean']*100:.0f}%")

# greedy rows are deterministic: the static lockstep decode of each one alone
# gives the same tokens
prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
for req in requests:
    if req.sampling.temperature > 0:
        continue
    caches = model.init_caches(1, 64)
    logits, caches = prefill(params, jnp.asarray(req.prompt)[None], caches)
    toks = [int(jnp.argmax(logits[0]))]
    while len(toks) < len(outputs[req.id]):
        logits, caches = decode(params, jnp.asarray(toks[-1:]), caches)
        toks.append(int(jnp.argmax(logits[0])))
    assert toks == outputs[req.id], req.id
print("greedy rows identical to the static decode")
print("serve_continuous OK")
