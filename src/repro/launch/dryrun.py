import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (architecture × input shape)
cell on the production meshes, and extract the roofline inputs.

MUST be run as its own process (`python -m repro.launch.dryrun ...`) — the
first two lines above force 512 host devices before jax initializes, which is
why they precede every other import (including `from repro...`).

Per cell this produces a JSON record with:
  * compile proof (ok/error) for the requested mesh,
  * ``memory_analysis()``  — per-device bytes (args/temps/outputs): fits?
  * ``cost_analysis()``    — per-device HLO FLOPs/bytes of the *production*
                             (scanned, chunked) program — loop bodies counted
                             once (XLA semantics), kept for reference,
  * **calibrated** FLOPs/bytes — the honest totals: small-(L,T) variants of
    the same program (loops unrolled away) are compiled and a multilinear
    model  f(L,T) = δ + ε·T + L·(α + β·T + γ·T²)  is fit and evaluated at the
    full depth/length (see EXPERIMENTS.md §Methodology; recurrent-scan
    step costs are added analytically),
  * collective bytes by kind, parsed from the optimized HLO with while-loop
    trip scaling (:mod:`repro.launch.hlo`).
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.configs.common import ARCHS, SHAPES, cell_status, get_config
from repro.dist import mesh as mesh_lib
from repro.launch import hlo as hlo_lib
from repro.launch import specs as specs_lib


def _lower_compile(cell) -> Dict[str, Any]:
    t0 = time.time()
    lowered = jax.jit(
        cell.fn,
        in_shardings=cell.in_shardings,
        out_shardings=cell.out_shardings,
    ).lower(*cell.args_sds)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    coll = hlo_lib.collective_summary(txt)
    return {
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device_bytes": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        },
        "cost_raw": {"flops": ca.get("flops", 0.0),
                     "bytes": ca.get("bytes accessed", 0.0)},
        "collectives": coll,
        "hlo_bytes": len(txt),
    }


# ------------------------------------------------------------- calibration

def _cal_cost(arch, shape_name, mesh, scheme, mpd_mode, mpd_c,
              n_layers, seqlen, mpd_fuse=False) -> Dict[str, float]:
    """Compile one small calibration variant (loops unrolled away: q_chunk
    and loss_chunk >= T; layer count n_layers) and return per-device costs."""
    import repro.configs.common as cc
    from repro.models.model import build
    from repro.optim import OptConfig, optimizer as opt_lib
    from repro.dist import sharding as sh

    shape = SHAPES[shape_name]
    cfg = get_config(arch, mpd_c=mpd_c, mpd_mode=mpd_mode, mpd_fuse=mpd_fuse)
    pat = len(cfg.pattern)
    cfg = dataclasses.replace(cfg, n_layers=n_layers,
                              q_chunk=max(seqlen, 8192),
                              loss_chunk=max(seqlen, 8192),
                              remat="none")
    model = build(cfg)
    rules = specs_lib._rules_for(cfg, mesh, shape, scheme)
    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    params_shard = specs_lib.tree_shardings_for(mesh, rules, model.axes(),
                                                params_sds)
    B = shape.global_batch
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt_cfg = OptConfig(kind="adamw", lr=1e-4)
        opt_sds = jax.eval_shape(lambda: opt_lib.init_state(opt_cfg, params_sds))
        opt_shard = specs_lib.tree_shardings_for(
            mesh, rules, opt_lib.state_axes(opt_cfg, model.axes()), opt_sds)
        b_sds = specs_lib.batch_specs(cfg, dataclasses.replace(
            shape, seq_len=seqlen))
        b_shard = specs_lib.tree_shardings_for(
            mesh, rules, specs_lib.batch_axes(cfg), b_sds)

        def step(params, opt_state, batch):
            with sh.use_mesh_rules(mesh, rules):
                loss, grads = jax.value_and_grad(model.train_loss)(params, batch)
                params, opt_state, _ = opt_lib.apply_updates(
                    opt_cfg, params, grads, opt_state)
            return params, opt_state, loss

        c = jax.jit(step, in_shardings=(params_shard, opt_shard, b_shard),
                    out_shardings=(params_shard, opt_shard, repl)
                    ).lower(params_sds, opt_sds, b_sds).compile()
    elif shape.kind == "prefill":
        sh_small = dataclasses.replace(shape, seq_len=seqlen)
        b_sds = specs_lib.batch_specs(cfg, sh_small)["inputs"]
        b_shard = specs_lib.tree_shardings_for(
            mesh, rules, {"x": specs_lib.batch_axes(cfg)["inputs"]},
            {"x": b_sds})["x"]
        cache_sds = jax.eval_shape(lambda: model.init_caches(B, seqlen))
        cache_shard = specs_lib.tree_shardings_for(
            mesh, rules, model.cache_axes(), cache_sds)

        def step(params, inputs, caches):
            with sh.use_mesh_rules(mesh, rules):
                return model.prefill(params, inputs, caches)

        c = jax.jit(step, in_shardings=(params_shard, b_shard, cache_shard),
                    out_shardings=(repl, cache_shard)
                    ).lower(params_sds, b_sds, cache_sds).compile()
    else:  # decode: seqlen plays the CACHE length role
        sh_small = dataclasses.replace(shape, seq_len=seqlen)
        tok_sds, cache_sds = specs_lib.decode_specs(model, sh_small)
        cache_shard = specs_lib.tree_shardings_for(
            mesh, rules, model.cache_axes(), cache_sds)
        tok_shard = specs_lib.tree_shardings_for(
            mesh, rules, {"t": specs_lib.token_axes(cfg)}, {"t": tok_sds})["t"]

        def step(params, tokens, caches):
            with sh.use_mesh_rules(mesh, rules):
                return model.decode_step(params, tokens, caches)

        c = jax.jit(step, in_shardings=(params_shard, tok_shard, cache_shard),
                    out_shardings=(repl, cache_shard)
                    ).lower(params_sds, tok_sds, cache_sds).compile()

    ca = c.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "L": n_layers, "T": seqlen}


def _fit_and_eval(samples, L_full, T_full, quadratic_T: bool):
    """Fit f(L,T) = d + e*T + L*(a + b*T [+ g*T^2]) and evaluate at full.

    Returns the value AND the coefficients — the roofline reader uses the
    quadratic (attention-traffic) coefficient for the flash-bytes
    substitution (see EXPERIMENTS.md §Methodology)."""
    names = (["1", "T", "L", "LT", "LT2"] if quadratic_T
             else ["1", "T", "L", "LT"])
    feats = lambda L, T: ([1.0, T, L, L * T, L * T * T] if quadratic_T
                          else [1.0, T, L, L * T])
    A = np.array([feats(s["L"], s["T"]) for s in samples])
    out = {"features": names, "L_full": L_full, "T_full": T_full}
    for key in ("flops", "bytes"):
        y = np.array([s[key] for s in samples])
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        val = float(np.dot(feats(L_full, T_full), coef))
        out[key] = max(val, 0.0)
        out[f"coef_{key}"] = [float(c) for c in coef]
    return out


def _recurrence_correction(cfg, shape, chips: int) -> float:
    """Analytic FLOPs for recurrent-scan steps (counted once by HLO cost
    analysis regardless of T). Per-device; fwd ~3 MACs per state element per
    step, bwd ~2x fwd for train. See EXPERIMENTS.md §Methodology."""
    B, T = shape.global_batch, shape.seq_len
    steps = T if shape.kind != "decode" else 1
    mult = 3.0 if shape.kind == "train" else 1.0  # fwd+bwd vs fwd
    per_layer = {"rwkv": 0.0, "mamba": 0.0}
    D = cfg.d_model
    if "rwkv" in cfg.pattern:
        H, N = D // cfg.rwkv_head_dim, cfg.rwkv_head_dim
        per_layer["rwkv"] = 6.0 * B * H * N * N  # S update + readout MACs
    if any(k.startswith("mamba") for k in cfg.pattern):
        di, ds = cfg.mamba_expand * D, 16
        per_layer["mamba"] = 7.0 * B * di * ds
    n_rwkv = sum(1 for k in cfg.pattern if k == "rwkv")
    n_mamba = sum(1 for k in cfg.pattern if k.startswith("mamba"))
    periods = cfg.n_layers // len(cfg.pattern)
    total = periods * (n_rwkv * per_layer["rwkv"] + n_mamba * per_layer["mamba"])
    return total * steps * mult / chips


def calibrate(arch, shape_name, mesh, scheme, mpd_mode, mpd_c,
              mpd_fuse=False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pat = len(cfg.pattern)
    has_attn = any(k.startswith("attn") for k in cfg.pattern)
    quad = has_attn and shape.kind != "decode"
    Ts = ([512, 1024, 2048] if shape.kind != "decode" else [2048, 4096])
    Ls = [pat, 2 * pat]
    samples = []
    for L in Ls:
        for T in (Ts if L == pat else Ts[:2] if quad else Ts[:1]):
            samples.append(_cal_cost(arch, shape_name, mesh, scheme, mpd_mode,
                                     mpd_c, L, T, mpd_fuse))
    fitted = _fit_and_eval(samples, cfg.n_layers, shape.seq_len, quad)
    chips = int(np.prod(list(mesh.devices.shape)))
    fitted["flops"] += _recurrence_correction(cfg, shape, chips)
    fitted["samples"] = samples
    return fitted


# --------------------------------------------------------------------- main

def run_cell(arch: str, shape_name: str, multi_pod: bool, scheme: str,
             mpd_mode: str, mpd_c: int, skip_calibration: bool = False,
             grad_accum: int = 16, mpd_fuse: bool = False) -> Dict[str, Any]:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "scheme": scheme, "mpd_mode": mpd_mode, "mpd_c": mpd_c,
        "mpd_fuse": mpd_fuse,
    }
    ok, why = cell_status(arch, shape_name)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    try:
        cell = specs_lib.make_cell(arch, shape_name, mesh, scheme=scheme,
                                   mpd_c=mpd_c, mpd_mode=mpd_mode,
                                   grad_accum=grad_accum, mpd_fuse=mpd_fuse)
        rec["meta"] = cell.meta
        rec.update(_lower_compile(cell))
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        return rec
    if not skip_calibration and not multi_pod:
        try:
            rec["calibrated"] = calibrate(arch, shape_name, mesh, scheme,
                                          mpd_mode, mpd_c, mpd_fuse)
        except Exception as e:  # noqa: BLE001
            rec["calibration_error"] = f"{type(e).__name__}: {e}"
    return rec


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=ARCHS, required=True)
    p.add_argument("--shape", choices=list(SHAPES), required=True)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--scheme", choices=("tp", "block"), default="tp")
    p.add_argument("--mpd-mode", choices=("packed", "masked_dense"),
                   default="packed")
    p.add_argument("--mpd-c", type=int, default=8)
    p.add_argument("--skip-calibration", action="store_true")
    p.add_argument("--mpd-fuse", action="store_true")
    p.add_argument("--grad-accum", type=int, default=16)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    rec = run_cell(args.arch, args.shape, args.multi_pod, args.scheme,
                   args.mpd_mode, args.mpd_c, args.skip_calibration,
                   args.grad_accum, args.mpd_fuse)
    js = json.dumps(rec, indent=2, default=str)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    print(js)
    if rec["status"] == "error":
        sys.exit(1)


if __name__ == "__main__":
    main()
