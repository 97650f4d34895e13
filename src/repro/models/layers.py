"""Shared model-zoo building blocks: norms, embeddings, rotary encodings.

Everything is functional: ``init_*`` builds a params pytree, ``*_apply``
consumes it. Norms cover the assigned-architecture variety: RMSNorm
(llama-family), LayerNorm (hubert), and OLMo's *non-parametric* LayerNorm.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x, weight, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def nonparametric_layernorm(x, eps: float = 1e-5):
    """OLMo-style LN without learnable affine (arXiv:2402.00838)."""
    return layernorm(x, None, None, eps)


def init_norm(kind: str, dim: int, dtype=jnp.float32):
    if kind == "rms":
        return {"w": jnp.ones((dim,), dtype)}
    if kind == "ln":
        return {"w": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}
    if kind == "none":  # non-parametric
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, params, x, eps: float = 1e-5):
    if kind == "rms":
        return rmsnorm(x, params["w"], eps)
    if kind == "ln":
        return layernorm(x, params["w"], params["b"], eps)
    if kind == "none":
        return nonparametric_layernorm(x, eps)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# Rotary position embeddings (RoPE + Qwen2-VL's multimodal M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_cos_sin(positions, head_dim: int, theta: float = 10000.0):
    """positions: (..., T) int -> cos/sin of shape (..., T, head_dim/2)."""
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(head_dim, theta)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, T, H, D); cos/sin: (B, T, D/2) (broadcast over heads)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


def mrope_cos_sin(positions3, head_dim: int, sections=(16, 24, 24),
                  theta: float = 1_000_000.0):
    """Qwen2-VL M-RoPE (arXiv:2409.12191): the rotary dims are split into
    (temporal, height, width) sections, each rotated by its own position id.

    positions3: (3, B, T) int32. ``sections`` counts are in *half-dim* units
    and must sum to head_dim/2.
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_freqs(head_dim, theta)  # (D/2,)
    # section id of each frequency slot
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=head_dim // 2
    )
    pos = positions3[sec_id, :, :]                      # (D/2, B, T)
    ang = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # (B, T, D/2)
    return jnp.cos(ang), jnp.sin(ang)


# --------------------------------------------------------------------------
# Embeddings
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, dim: int, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, dim), dtype) * 0.02}


def embed(params, ids):
    """Token embedding lookup, vocab-parallel when a mesh is active.

    A plain gather over a vocab-sharded table is lowered by GSPMD as a
    one-hot contraction — (tokens × vocab/shard) one-hot buffers, measured
    at 268 GB/device for command-r prefill_32k. The Megatron formulation
    (masked local gather + psum over the vocab axis) is explicit here via
    shard_map.
    """
    from repro.dist import sharding as sh_lib

    mesh, rules = sh_lib.current()
    vocab_axes = (rules or {}).get("vocab", ())
    if mesh is None or not vocab_axes or params["table"].shape[0] % mesh.shape[vocab_axes[0]]:
        return jnp.take(params["table"], ids, axis=0)
    vax = vocab_axes[0]
    batch_axes = tuple((rules or {}).get("batch", ()))
    ways = 1
    for a in batch_axes:
        ways *= mesh.shape[a]
    # replicate ids when the (micro)batch doesn't divide the batch axes
    bspec = batch_axes if (batch_axes and ids.shape[0] % ways == 0) else None
    from jax.sharding import PartitionSpec as P

    def local(table, ids_l):
        size = table.shape[0]
        start = jax.lax.axis_index(vax) * size
        off = ids_l - start
        ok = (off >= 0) & (off < size)
        vals = jnp.take(table, jnp.clip(off, 0, size - 1), axis=0)
        vals = jnp.where(ok[..., None], vals, jnp.zeros((), table.dtype))
        return jax.lax.psum(vals, vax)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(vax, None), P(bspec, None)),
        out_specs=P(bspec, None, None),
    )(params["table"], ids)
