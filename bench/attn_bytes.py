"""Operations and least HBM bytes of one paged decode attention step, from
what the step attends.

A decode step hands the attention kernel, in every layer, one query row
per slot and each live row's KV length (``kv_tokens`` on the engine's
``engine.decode.prep`` span is the sum of those lengths over live rows).
The least the kernel can move is every attended key and value once, each
live row's query read once and its output written once; pages are read
whole and dead rows are read too, so the kernel moves at least this much.
"""

from __future__ import annotations

from typing import Dict, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def decode_step(model: Dict, kv_dtype: str, kv_tokens: int,
                live: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step's attention over all layers:
    ``kv_tokens`` summed KV lengths of the ``live`` rows."""
    n_l, h, kh = model["n_layers"], model["n_heads"], model["n_kv_heads"]
    dh = model["d_model"] // h
    act = DTYPE_BYTES[model["dtype"]]
    kv = kv_tokens * n_l * 2 * kh * dh * DTYPE_BYTES[kv_dtype]
    q_out = live * n_l * 2 * h * dh * act
    flops = 4.0 * kv_tokens * n_l * h * dh        # q.k and p.v per head
    return flops, float(kv + q_out)
