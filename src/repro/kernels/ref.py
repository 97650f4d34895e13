"""Pure-jnp oracles for the MPDCompress kernels.

These are the correctness references the Pallas kernels are tested against
(interpret mode on CPU, real lowering on TPU), and also the fast CPU
execution path used by the examples/benchmarks in this container.

``ACTIVATIONS`` is the single registry both the fused kernel epilogues and
the unfused model graph draw from — every entry delegates to the same
``jax.nn`` function the model code used to call directly, so fusing an
epilogue into a kernel is bit-consistent with computing it as a separate
XLA op (the old hand-rolled tanh-gelu constant drifted from
``jax.nn.gelu``; see tests/test_export_fused.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

ACTIVATIONS = {
    None: lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0),
    # tanh approximation — matches what models/ffn.py computes unfused
    # (jax.nn.gelu defaults to approximate=True)
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "softplus": jax.nn.softplus,
    # RWKV channel-mix: squared ReLU
    "sqrelu": lambda x: jnp.square(jnp.maximum(x, 0)),
}


def gated(activation: Optional[str]):
    """The two-operand gated epilogue ``act(gate) * up`` used by fused MLPs
    (``activation="silu"`` is SwiGLU). Returns a callable ``(gate, up) -> h``."""
    act = ACTIVATIONS[activation]
    return lambda g, u: act(g) * u


def bdmm_ref(x, wp, bias=None, activation: Optional[str] = None, precision=None):
    """Block-diagonal matmul oracle.

    Args:
      x:  ``(..., nb*bi)`` packed inputs (already input-permuted).
      wp: ``(nb, bi, bo)`` packed diagonal blocks.
      bias: optional ``(nb*bo,)`` packed bias.
      activation: optional fused activation name.

    Returns ``(..., nb*bo)`` packed outputs.
    """
    nb, bi, bo = wp.shape
    lead = x.shape[:-1]
    xb = x.reshape(*lead, nb, bi)
    y = jnp.einsum("...nk,nko->...no", xb, wp, precision=precision)
    y = y.reshape(*lead, nb * bo)
    if bias is not None:
        y = y + bias
    return ACTIVATIONS[activation](y)


def masked_matmul_ref(x, w, mask, bias=None, activation: Optional[str] = None, precision=None):
    """Paper-faithful masked matmul oracle: ``y = x @ (mask ∘ w)``.

    ``x: (..., d_in)``, ``w/mask: (d_in, d_out)``.
    """
    y = jnp.dot(x, w * mask.astype(w.dtype), precision=precision)
    if bias is not None:
        y = y + bias
    return ACTIVATIONS[activation](y)


def matmul_masked_grad_ref(x, g, mask, precision=None):
    """Oracle for the weight-gradient of the masked matmul:
    ``dW = (x^T @ g) ∘ mask`` (an SDDMM — output sampled by the mask)."""
    return jnp.einsum("...i,...o->io", x, g, precision=precision) * mask


def bdmm_quant_ref(x, wq, scale, bias=None, activation: Optional[str] = None,
                   precision=None):
    """Int8-weight block-diagonal matmul oracle, mirroring the kernel's
    computation order: raw int-product accumulation in f32, then one
    per-output-channel ``* scale`` rescale in the epilogue, then bias and
    activation.

    ``wq: (nb, bi, bo)`` int8; ``scale: (nb, bo)`` f32 (from
    :func:`repro.kernels.quant.quantize_blocks`).
    """
    nb, bi, bo = wq.shape
    lead = x.shape[:-1]
    xb = x.reshape(*lead, nb, bi)
    y = jnp.einsum("...nk,nko->...no", xb, wq.astype(x.dtype),
                   precision=precision,
                   preferred_element_type=jnp.float32)
    y = y * scale
    if bias is not None:
        y = y + bias.reshape(nb, bo)
    y = ACTIVATIONS[activation](y).astype(x.dtype)
    return y.reshape(*lead, nb * bo)


def fused_ffn_ref(x, w_up, w_down, w_gate=None, b_up=None, b_gate=None,
                  b_down=None, activation: Optional[str] = "silu",
                  precision=None):
    """Block-diagonal fused-MLP oracle (perm-fused packed FFN, hidden never
    leaves block order).

    ``x: (..., nb*bi)``; ``w_up/w_gate: (nb, bi, f)``; ``w_down: (nb, f, bo)``;
    biases packed (``(nb*f,)`` / ``(nb*bo,)``). Gated (SwiGLU-family) when
    ``w_gate`` is given: ``h = act(x@Wg + bg) * (x@Wu + bu)``; otherwise
    ``h = act(x@Wu + bu)``. Returns ``act_down-free`` ``h @ Wd + bd``.
    """
    if w_gate is None and b_gate is not None:
        raise ValueError("fused_ffn_ref: b_gate given but w_gate is None")
    u = bdmm_ref(x, w_up, b_up, precision=precision)
    if w_gate is not None:
        g = bdmm_ref(x, w_gate, b_gate, precision=precision)
        h = gated(activation)(g, u)
    else:
        h = ACTIVATIONS[activation](u)
    return bdmm_ref(h, w_down, b_down, precision=precision)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths):
    """Paged-attention decode oracle.

    Gathers each row's pages into a contiguous KV view and runs exactly the
    dense decode computation (same einsum contraction order, f32 softmax,
    ``-1e30`` masking) — so on the jnp route a paged decode is bitwise
    identical to the contiguous decode of the same sequences: masked columns
    exp-underflow to exact zeros, which are exact under any reduction order.

    ``q: (B, H, Dh)``; ``k_pages/v_pages: (n_pages, page_size, Kh, Dh)``;
    ``block_tables: (B, P)`` int32; ``lengths: (B,)`` valid KV depth per
    row. Returns ``(B, H, Dh)``.
    """
    B, H, Dh = q.shape
    _, page_size, n_kv, _ = k_pages.shape
    P = block_tables.shape[1]
    k = k_pages[block_tables].reshape(B, P * page_size, n_kv, Dh)
    v = v_pages[block_tables].reshape(B, P * page_size, n_kv, Dh)
    g = H // n_kv
    q5 = q.reshape(B, 1, n_kv, g, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q5,
                        k.astype(q.dtype)).astype(jnp.float32)
    logits *= Dh ** -0.5
    valid = jnp.arange(P * page_size)[None, :] < lengths[:, None]
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), v.astype(q.dtype))
    return o.reshape(B, 1, H, Dh)[:, 0]


def paged_attention_verify_ref(q, k_pages, v_pages, block_tables, lengths):
    """Paged-attention *verify* oracle: a short window of ``Tq`` query
    positions per row against the paged pool (speculative decoding's
    draft-window verification).

    Query ``t`` (0-indexed within the window) sits at absolute position
    ``lengths[b] - Tq + t`` and attends to ``kv_pos < lengths[b] - (Tq-1-t)``
    — the cached context plus the window tokens up to and including itself
    (the window's K/V are scattered into the pool before this is called,
    exactly like the decode step). With ``Tq == 1`` this is
    :func:`paged_attention_ref` verbatim; the contraction order, f32
    softmax, and ``-1e30`` masking are identical, so greedy verification
    reproduces the decode path's argmax.

    ``q: (B, Tq, H, Dh)``; ``lengths: (B,)`` valid KV depth at the *last*
    query. Returns ``(B, Tq, H, Dh)``.
    """
    B, Tq, H, Dh = q.shape
    _, page_size, n_kv, _ = k_pages.shape
    P = block_tables.shape[1]
    k = k_pages[block_tables].reshape(B, P * page_size, n_kv, Dh)
    v = v_pages[block_tables].reshape(B, P * page_size, n_kv, Dh)
    g = H // n_kv
    q5 = q.reshape(B, Tq, n_kv, g, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q5,
                        k.astype(q.dtype)).astype(jnp.float32)
    logits *= Dh ** -0.5
    kv_pos = jnp.arange(P * page_size)
    per_q_len = lengths[:, None] - (Tq - 1 - jnp.arange(Tq))[None, :]
    valid = kv_pos[None, None, :] < per_q_len[:, :, None]      # (B, Tq, S)
    logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), v.astype(q.dtype))
    return o.reshape(B, Tq, H, Dh)


def paged_prefill_attention_ref(q, k_pages, v_pages, bt_row, start, chunk_len):
    """Chunked-prefill attention oracle for ONE request's chunk against its
    paged context.

    Gathers the row's pages into a contiguous KV view and runs exactly the
    dense ``_attend`` computation from ``models/attention.py`` (same gather
    -> astype order, same einsum contraction, f32 softmax, causal-then-valid
    ``-1e30`` masking sequence) — so on the jnp route a flash-routed prefill
    chunk is bitwise identical to the dense gather path it replaces: the
    extra fully-masked columns exp-underflow to exact zeros, which are exact
    under any reduction order.

    ``q: (Tc, H, Dh)`` — the chunk's queries at global positions
    ``start + t``; ``k_pages/v_pages: (n_pages, page_size, Kh, Dh)``;
    ``bt_row: (P,)`` int32; ``chunk_len`` real tokens (``< Tc`` on the
    right-padded final chunk; padded rows produce garbage the caller never
    reads). The chunk's own K/V must already be scattered into the pool.
    Returns ``(Tc, H, Dh)``.
    """
    Tc, H, Dh = q.shape
    _, page_size, n_kv, _ = k_pages.shape
    P = bt_row.shape[0]
    S = P * page_size
    k = k_pages[bt_row].reshape(1, S, n_kv, Dh).astype(q.dtype)
    v = v_pages[bt_row].reshape(1, S, n_kv, Dh).astype(q.dtype)
    g = H // n_kv
    q5 = q.reshape(1, Tc, n_kv, g, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q5, k).astype(jnp.float32)
    logits *= Dh ** -0.5
    q_pos = start + jnp.arange(Tc)
    kv_pos = jnp.arange(S)
    cmask = q_pos[:, None] >= kv_pos[None, :]
    logits = jnp.where(cmask[None, None, None], logits, -1e30)
    kv_valid = kv_pos < start + chunk_len
    logits = jnp.where(kv_valid[None, None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(1, Tc, H, Dh)[0]


def fused_ffn_quant_ref(x, w_up, w_down, w_gate=None, b_up=None, b_gate=None,
                        b_down=None, s_up=None, s_gate=None, s_down=None,
                        activation: Optional[str] = "silu", precision=None):
    """Int8-weight fused-MLP oracle: each projection is a
    :func:`bdmm_quant_ref` (scale applied right after its dot, before bias
    and the hidden epilogue), mirroring the kernel's in-register dequant."""
    if w_gate is None and (b_gate is not None or s_gate is not None):
        raise ValueError(
            "fused_ffn_quant_ref: gate bias/scale given but w_gate is None")
    u = bdmm_quant_ref(x, w_up, s_up, b_up, precision=precision)
    if w_gate is not None:
        g = bdmm_quant_ref(x, w_gate, s_gate, b_gate, precision=precision)
        h = gated(activation)(g, u)
    else:
        h = ACTIVATIONS[activation](u)
    return bdmm_quant_ref(h, w_down, s_down, b_down, precision=precision)
