"""Pallas TPU kernel: paged-attention decode step.

Serving-side dual of the paper's thesis: MPDCompress molds *weights* into
fixed-size hardware-friendly blocks; the paged KV cache applies the same
idea to *activations*. K/V live in a global pool of ``(page_size, Kh, Dh)``
pages and each sequence owns an ordered list of page ids (its block table).
This kernel computes one decode step of attention for a batch of sequences
directly against the pool — no gather materialization — by streaming each
row's pages through VMEM and combining them with an online softmax.

Layout
------
* ``q``            ``(B, H, Dh)``      — one query token per sequence
* ``k_pages``      ``(n_pages, page_size, Kh, Dh)``
* ``v_pages``      ``(n_pages, page_size, Kh, Dh)``
* ``block_tables`` ``(B, P)`` int32    — physical page id per logical page
* ``lengths``      ``(B,)`` int32      — valid KV depth per row (>= 1)

Each page is cast to ``q``'s dtype as it is read, so a pool of another
dtype is never copied whole.

TPU mapping
-----------
Grid ``(B, P)`` with the page axis innermost ("arbitrary" semantics).
``block_tables``/``lengths`` ride as *scalar prefetch* operands
(:class:`pltpu.PrefetchScalarGridSpec`): the page id is known before the
kernel body runs, so the index map DMAs exactly the page the row needs —
the block table is the only indexing metadata, mirroring how the packed
weight kernels carry none at all. Pages past ``lengths[b]`` are skipped
(``pl.when``); block-table entries there point at the reserved null page 0,
so the prefetch slot is always a valid pool index.

Per page the kernel runs the standard streaming-softmax update in f32
scratch (running max ``m``, normalizer ``l``, unnormalized accumulator) and
divides once on the last page. GQA is a static loop over KV heads with
``g = H // Kh`` query rows per group — head counts are small and static.

Numerics: the online combine is mathematically identical to a full softmax
but not bitwise identical to the one-shot reference; the jnp route
(:func:`repro.kernels.ref.paged_attention_ref`) IS bitwise-stable against
the dense decode path and is what CPU serving uses. Tests compare the
kernel (interpret mode) against the reference to ~1e-5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_attn_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, page_size: int, n_kv: int,
                       n_pages_per_row: int):
    b, p = pl.program_id(0), pl.program_id(1)
    H, Dh = q_ref.shape[1], q_ref.shape[2]
    g = H // n_kv

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    base = p * page_size

    @pl.when(base < length)
    def _page():
        q = q_ref[0]                             # (H, Dh)
        k = k_ref[0].astype(q.dtype)             # (page_size, Kh, Dh)
        v = v_ref[0].astype(q.dtype)
        kv_pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        valid = kv_pos < length                  # (1, page_size)
        scale = Dh ** -0.5
        for h in range(n_kv):
            hs = slice(h * g, (h + 1) * g)
            qh = q[hs]                           # (g, Dh)
            kh = k[:, h, :]                      # (page_size, Dh)
            vh = v[:, h, :]
            s = jax.lax.dot_general(
                qh, kh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (g, page_size)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[hs, :1]               # (g, 1)
            l_prev = l_ref[hs, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new)              # masked entries underflow to 0
            l_new = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(vh.dtype), vh,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (g, Dh)
            acc_ref[hs, :] = acc_ref[hs, :] * alpha + pv
            m_ref[hs, :] = jnp.broadcast_to(m_new, m_ref[hs, :].shape)
            l_ref[hs, :] = jnp.broadcast_to(l_new, l_ref[hs, :].shape)

    @pl.when(p == n_pages_per_row - 1)
    def _final():
        l = jnp.maximum(l_ref[:, :1], 1e-30)     # length >= 1 keeps l > 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_verify_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int, n_kv: int,
                         n_pages_per_row: int, n_q: int):
    """Multi-query variant: ``n_q`` window positions per row (speculative
    verify). Query ``t`` attends to ``kv_pos < length - (n_q-1) + t`` — the
    per-row causal window. The query axis folds into the GQA group axis so
    every dot stays a 2-D ``(n_q*g, ·)`` matmul."""
    b, p = pl.program_id(0), pl.program_id(1)
    H, Dh = q_ref.shape[2], q_ref.shape[3]
    g = H // n_kv
    rows = n_q * g

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]                          # depth at the LAST query
    base = p * page_size

    @pl.when(base < length)
    def _page():
        q = q_ref[0]                             # (n_q, H, Dh)
        k = k_ref[0].astype(q.dtype)             # (page_size, Kh, Dh)
        v = v_ref[0].astype(q.dtype)
        kv_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        t_row = jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // g
        valid = kv_pos < length - (n_q - 1) + t_row
        scale = Dh ** -0.5
        for h in range(n_kv):
            hs = slice(h * g, (h + 1) * g)
            qh = q[:, hs, :].reshape(rows, Dh)   # (n_q*g, Dh)
            kh = k[:, h, :]                      # (page_size, Dh)
            vh = v[:, h, :]
            s = jax.lax.dot_general(
                qh, kh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h, :, :1]             # (n_q*g, 1)
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(vh.dtype), vh,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (n_q*g, Dh)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref[h].shape)
            l_ref[h] = jnp.broadcast_to(l_new, l_ref[h].shape)

    @pl.when(p == n_pages_per_row - 1)
    def _final():
        for h in range(n_kv):
            l = jnp.maximum(l_ref[h, :, :1], 1e-30)
            o = (acc_ref[h] / l).reshape(n_q, g, Dh)
            o_ref[0, :, h * g:(h + 1) * g, :] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_verify(q, k_pages, v_pages, block_tables, lengths, *,
                           interpret: bool = False):
    """Speculative-verify attention: ``(B, Tq, H, Dh)`` out for a ``Tq``-token
    window per row. ``lengths[b]`` is the valid KV depth at the row's *last*
    window position (so the first sees ``lengths[b] - Tq + 1``); it must be
    >= ``Tq``. The window K/V must already be scattered into the pool."""
    B, Tq, H, Dh = q.shape
    n_pages, page_size, n_kv, _ = k_pages.shape
    P = block_tables.shape[1]
    assert block_tables.shape == (B, P), (block_tables.shape, B)
    assert H % n_kv == 0, (H, n_kv)
    g = H // n_kv

    kernel = functools.partial(
        _paged_verify_kernel, page_size=page_size, n_kv=n_kv,
        n_pages_per_row=P, n_q=Tq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, Tq, H, Dh), lambda b, p, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, Dh),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, Dh),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tq, H, Dh),
                               lambda b, p, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, Tq * g, Dh), jnp.float32),
            pltpu.VMEM((n_kv, Tq * g, 128), jnp.float32),
            pltpu.VMEM((n_kv, Tq * g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tq, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret: bool = False):
    """One decode step of paged attention: ``(B, H, Dh)`` out.

    ``lengths[b]`` must be >= 1 (a live row always holds at least the token
    just written); block-table entries past the used depth must point at a
    valid (e.g. the null) page.
    """
    B, H, Dh = q.shape
    n_pages, page_size, n_kv, _ = k_pages.shape
    P = block_tables.shape[1]
    assert block_tables.shape == (B, P), (block_tables.shape, B)
    assert H % n_kv == 0, (H, n_kv)

    kernel = functools.partial(
        _paged_attn_kernel, page_size=page_size, n_kv=n_kv,
        n_pages_per_row=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, p, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, Dh),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, Dh),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda b, p, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),    # unnormalized accumulator
            pltpu.VMEM((H, 128), jnp.float32),   # running max (lane-broadcast)
            pltpu.VMEM((H, 128), jnp.float32),   # running normalizer
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)
