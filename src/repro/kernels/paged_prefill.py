"""Pallas TPU kernel: flash-style chunked-prefill attention over paged KV.

The decode kernel (:mod:`repro.kernels.paged_attention`) streams one query
per sequence through the page pool; this kernel is its prefill dual — a
whole page-aligned chunk of ``Tc`` queries from ONE request attends
causally over the request's cached context (trie-reused prefix pages
included) plus the chunk itself, without ever materializing the
``(Tc, P*page_size)`` score matrix the dense gather path builds.

Layout
------
* ``q``         ``(Tc, H, Dh)``                  — the chunk's queries
* ``k_pages``   ``(n_pages, page_size, Kh, Dh)`` — global K pool
* ``v_pages``   ``(n_pages, page_size, Kh, Dh)`` — global V pool
* ``bt_row``    ``(P,)`` int32                   — the request's block table
* ``start``     scalar int32 — global position of the chunk's first token
                (page-aligned; > 0 on trie prefix hits and later chunks)
* ``chunk_len`` scalar int32 — real tokens in the chunk (< ``Tc`` on the
                right-padded final chunk)

Each page is cast to ``q``'s dtype as it is read, so a pool of another
dtype is never copied whole.

TPU mapping
-----------
Grid ``(Tc // q_tile, P)`` — query-row tiles "parallel", the page axis
innermost "arbitrary". ``bt_row`` and ``(start, chunk_len)`` ride as
scalar prefetch (:class:`pltpu.PrefetchScalarGridSpec`), so the index map
DMAs exactly the page each step needs, same as the decode kernel. A page
is skipped (``pl.when``) unless it holds keys some query in the tile may
attend to: ``base < start + chunk_len`` (the chunk's end depth — this is
what makes KV read ∝ actual depth, not the laddered table width) AND
``base <= start + (qt+1)*q_tile - 1`` (entirely-future pages are fully
causally masked). Per active page the standard online-softmax update runs
in f32 scratch; queries fold into the GQA group axis (static loop over KV
heads) so every dot stays 2-D, exactly like the verify kernel.

Masking is per position: query ``t`` (global position ``start + t``)
attends to ``kv_pos <= start + t`` and ``kv_pos < start + chunk_len``.
Padded tail queries (``t >= chunk_len``) see the full real context, so
their normalizer stays positive — their outputs are garbage the model
never reads (logits come from the last *real* token).

Numerics: the online combine is mathematically identical to a one-shot
softmax but not bitwise; the jnp route
(:func:`repro.kernels.ref.paged_prefill_attention_ref`) IS bitwise-stable
against the dense ``_attend`` path and is what CPU serving uses. Tests
compare the kernel (interpret mode) against the reference to ~1e-5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def q_tile_for(Tc: int, cap: int = 128) -> int:
    """Query-row tile size: the largest divisor of ``Tc`` at most ``cap``
    (chunk lengths are page multiples, so this is nearly always a power of
    two; the fallback scan keeps odd shapes correct in interpret mode)."""
    for t in range(min(Tc, cap), 0, -1):
        if Tc % t == 0:
            return t
    return 1


def _paged_prefill_kernel(bt_ref, info_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, page_size: int,
                          n_kv: int, n_pages_per_row: int, q_tile: int):
    qt, p = pl.program_id(0), pl.program_id(1)
    H, Dh = q_ref.shape[1], q_ref.shape[2]
    g = H // n_kv
    rows = q_tile * g
    start = info_ref[0]
    depth = info_ref[0] + info_ref[1]            # start + chunk_len

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    base = p * page_size
    # last query position in this tile: pages past it are fully masked
    q_hi = start + (qt + 1) * q_tile - 1

    @pl.when((base < depth) & (base <= q_hi))
    def _page():
        q = q_ref[...]                           # (q_tile, H, Dh)
        k = k_ref[0].astype(q.dtype)             # (page_size, Kh, Dh)
        v = v_ref[0].astype(q.dtype)
        kv_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        t_row = jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // g
        q_pos = start + qt * q_tile + t_row
        valid = (kv_pos <= q_pos) & (kv_pos < depth)
        scale = Dh ** -0.5
        for h in range(n_kv):
            hs = slice(h * g, (h + 1) * g)
            qh = q[:, hs, :].reshape(rows, Dh)   # (q_tile*g, Dh)
            kh = k[:, h, :]                      # (page_size, Dh)
            vh = v[:, h, :]
            s = jax.lax.dot_general(
                qh, kh, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h, :, :1]             # (q_tile*g, 1)
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new)              # masked entries underflow to 0
            l_new = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(vh.dtype), vh,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (q_tile*g, Dh)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref[h].shape)
            l_ref[h] = jnp.broadcast_to(l_new, l_ref[h].shape)

    @pl.when(p == n_pages_per_row - 1)
    def _final():
        for h in range(n_kv):
            # every row attends at least kv_pos 0 (page 0 always runs), so
            # l > 0; the clamp only guards the fp edge
            l = jnp.maximum(l_ref[h, :, :1], 1e-30)
            o = (acc_ref[h] / l).reshape(q_tile, g, Dh)
            o_ref[:, h * g:(h + 1) * g, :] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "q_tile"))
def paged_prefill_attention(q, k_pages, v_pages, bt_row, start, chunk_len, *,
                            interpret: bool = False, q_tile=None):
    """Flash-style prefill-chunk attention: ``(Tc, H, Dh)`` out for one
    request's chunk against its paged context (see module docstring for
    layout and masking). The chunk's own K/V must already be scattered
    into the pool; ``start + chunk_len >= 1``."""
    Tc, H, Dh = q.shape
    n_pages, page_size, n_kv, _ = k_pages.shape
    P = bt_row.shape[0]
    assert bt_row.ndim == 1, bt_row.shape
    assert H % n_kv == 0, (H, n_kv)
    g = H // n_kv
    if q_tile is None:
        q_tile = q_tile_for(Tc)
    assert Tc % q_tile == 0, (Tc, q_tile)
    info = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(chunk_len, jnp.int32)])

    kernel = functools.partial(
        _paged_prefill_kernel, page_size=page_size, n_kv=n_kv,
        n_pages_per_row=P, q_tile=q_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Tc // q_tile, P),
        in_specs=[
            pl.BlockSpec((q_tile, H, Dh),
                         lambda qt, p, bt, info: (qt, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, Dh),
                         lambda qt, p, bt, info: (bt[p], 0, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, Dh),
                         lambda qt, p, bt, info: (bt[p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((q_tile, H, Dh),
                               lambda qt, p, bt, info: (qt, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, q_tile * g, Dh), jnp.float32),
            pltpu.VMEM((n_kv, q_tile * g, 128), jnp.float32),
            pltpu.VMEM((n_kv, q_tile * g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tc, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bt_row.astype(jnp.int32), info, q, k_pages, v_pages)
