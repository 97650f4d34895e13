"""Operations and bytes of the kernels the served path calls, from shapes.

Each function returns ``(flops, bytes)`` for one call: the operations the
algorithm needs and the least HBM traffic it needs (weights, scales and
activations read once, outputs written once, no padding). ``program_calls``
lists the calls one execution of a program makes, derived from the
configuration file alone.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

ACT_BYTES = 2                      # bf16 activations in and out


def w_bytes(quantize) -> int:
    return 1 if quantize == "int8" else 2


def bdmm(m: int, nb: int, bi: int, bo: int, wb: int) -> Tuple[float, float]:
    """Block-diagonal matmul (m, nb*bi) x (nb, bi, bo) -> (m, nb*bo)."""
    flops = 2.0 * m * nb * bi * bo
    byts = nb * bi * bo * wb + m * nb * (bi + bo) * ACT_BYTES
    if wb == 1:
        byts += nb * bo * 4                       # float32 column scales
    return flops, float(byts)


def fused_ffn(m: int, nb: int, d: int, f: int, gated: bool,
              wb: int) -> Tuple[float, float]:
    """Fused packed MLP: up (and gate) d->f and down f->d, blockwise."""
    n_in = 2 if gated else 1
    flops = 2.0 * m * (n_in + 1) * d * f / nb
    byts = (n_in + 1) * d * f / nb * wb + 2 * m * d * ACT_BYTES
    if wb == 1:
        byts += (n_in * f + d) * 4
    return flops, float(byts)


def _projections(model: Dict) -> List[Tuple[str, int, int]]:
    d, f = model["d_model"], model["d_ff"]
    hd = d // model["n_heads"]
    dq, dkv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    out = [("q", d, dq), ("k", d, dkv), ("v", d, dkv), ("o", dq, d)]
    if not model.get("mpd_fuse"):
        out += [("up", d, f), ("down", f, d)]
        if model["ffn_kind"] == "swiglu":
            out.append(("gate", d, f))
    return out


def program_calls(model: Dict, quantize, program: str,
                  m: int) -> Dict[str, List[Tuple[float, float]]]:
    """Kernel calls of one execution: ``program`` is ``decode`` (m = slots),
    ``chunk`` or ``chunk_final`` (m = chunk tokens; the final chunk also
    unembeds its last row). Returns {kernel: [(flops, bytes), ...]}."""
    nb, wb, n_l = model["mpd_c"], w_bytes(quantize), model["n_layers"]
    calls: Dict[str, List[Tuple[float, float]]] = {"bdmm": [], "fused_ffn": []}
    for _ in range(n_l):
        for _, di, do in _projections(model):
            calls["bdmm"].append(bdmm(m, nb, di // nb, do // nb, wb))
        if model.get("mpd_fuse"):
            calls["fused_ffn"].append(fused_ffn(
                m, nb, model["d_model"], model["d_ff"],
                model["ffn_kind"] == "swiglu", wb))
    if program in ("decode", "chunk_final"):
        rows = m if program == "decode" else 1
        d, v = model["d_model"], model["vocab"]
        calls["bdmm"].append(bdmm(rows, nb, d // nb, v // nb, wb))
    return calls


def matmul_params(model: Dict) -> float:
    """Packed matmul parameters touched per token (projections and the
    unembedding; the embedding gather has no FLOPs)."""
    nb = model["mpd_c"]
    d, f = model["d_model"], model["d_ff"]
    hd = d // model["n_heads"]
    dq, dkv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    per_layer = d * dq + 2 * d * dkv + dq * d
    per_layer += (3 if model["ffn_kind"] == "swiglu" else 2) * d * f
    return (model["n_layers"] * per_layer + d * model["vocab"]) / nb


def token_flops(model: Dict, context: int) -> float:
    """Model FLOPs of one token at ``context`` keys: the packed matmuls plus
    QK^T and PV over the context in every layer."""
    attn = 4.0 * model["n_layers"] * context * model["d_model"]
    return 2.0 * matmul_params(model) + attn
