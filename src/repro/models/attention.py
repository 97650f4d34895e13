"""Attention for the model zoo: GQA/MHA, RoPE/M-RoPE, chunked (memory-
efficient) training attention, KV-cache decode, and sequence-parallel-
friendly softmax (partial reductions are plain jnp reductions, so GSPMD
inserts the log-sum-exp combine collectives when the KV sequence axis is
sharded — used by the ``long_500k`` cells).

All projections route through :class:`repro.models.linear.Linear`, i.e. they
are MPD-compressible (paper's FC layers). Projection biases (``use_bias``
archs) execute inside the kernel dispatch as fused epilogues — ``Linear
.apply`` pushes them down; nothing composes bias/activation outside here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import CompressionPolicy
from repro.dist.sharding import shard
from . import layers
from .linear import Linear


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    rope: str = "rope"  # rope | mrope | none
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    q_chunk: int = 128
    use_bias: bool = False
    wq: Linear = None
    wk: Linear = None
    wv: Linear = None
    wo: Linear = None

    @staticmethod
    def make(policy: CompressionPolicy, d_model, n_heads, n_kv_heads, head_dim,
             *, causal=True, rope="rope", rope_theta=1e4,
             mrope_sections=(16, 24, 24), q_chunk=128, use_bias=False,
             seed_salt=0, fuse_perms=False) -> "AttentionSpec":
        mk = functools.partial(Linear.make, policy, use_bias=use_bias)
        kw_q = dict(seed_salt=seed_salt * 4 + 0, axes=("embed", "heads"))
        kw_k = dict(seed_salt=seed_salt * 4 + 1, axes=("embed", "heads"))
        kw_v = dict(seed_salt=seed_salt * 4 + 2, axes=("embed", "heads"))
        if fuse_perms:
            # share the INPUT permutation across q/k/v so the three pack
            # gathers CSE into one (output perms stay independent; rope and
            # head structure need natural output order, so no skip there).
            from repro.core.mask import make_mask_spec
            mq = policy.plan(d_model, n_heads * head_dim, "attn_qkv",
                             seed_salt=seed_salt * 4 + 0)
            if mq is not None:
                for kw, d_out, salt in ((kw_k, n_kv_heads * head_dim, 1),
                                        (kw_v, n_kv_heads * head_dim, 2)):
                    m = policy.plan(d_model, d_out, "attn_qkv",
                                    seed_salt=seed_salt * 4 + salt)
                    if m is not None and m.nb == mq.nb:
                        kw["mask_override"] = make_mask_spec(
                            d_model, d_out, m.nb, seed=m.seed,
                            in_perm=mq.in_perm, out_perm=m.out_perm)
        return AttentionSpec(
            d_model, n_heads, n_kv_heads, head_dim, causal, rope, rope_theta,
            tuple(mrope_sections), q_chunk, use_bias,
            wq=mk(d_model, n_heads * head_dim, "attn_qkv", **kw_q),
            wk=mk(d_model, n_kv_heads * head_dim, "attn_qkv", **kw_k),
            wv=mk(d_model, n_kv_heads * head_dim, "attn_qkv", **kw_v),
            wo=mk(n_heads * head_dim, d_model, "attn_out",
                  seed_salt=seed_salt * 4 + 3, axes=("heads", "embed")),
        )

    def init(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 4)
        return {
            "wq": self.wq.init(ks[0], dtype), "wk": self.wk.init(ks[1], dtype),
            "wv": self.wv.init(ks[2], dtype), "wo": self.wo.init(ks[3], dtype),
        }

    def axes(self):
        return {"wq": self.wq.axes(), "wk": self.wk.axes(),
                "wv": self.wv.axes(), "wo": self.wo.axes()}


def _cos_sin(spec: AttentionSpec, positions):
    if spec.rope == "mrope":
        return layers.mrope_cos_sin(positions, spec.head_dim, spec.mrope_sections,
                                    spec.rope_theta)
    if spec.rope == "rope":
        return layers.rope_cos_sin(positions, spec.head_dim, spec.rope_theta)
    return None, None


def _qkv(spec: AttentionSpec, params, x, positions):
    B, T, _ = x.shape
    q = spec.wq.apply(params["wq"], x).reshape(B, T, spec.n_heads, spec.head_dim)
    k = spec.wk.apply(params["wk"], x).reshape(B, T, spec.n_kv_heads, spec.head_dim)
    v = spec.wv.apply(params["wv"], x).reshape(B, T, spec.n_kv_heads, spec.head_dim)
    cos, sin = _cos_sin(spec, positions)
    if cos is not None:
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
    # anchor head sharding after the reshape (MPD unpack gathers otherwise
    # leave the propagation unsharded and attention runs model-replicated);
    # shard() drops indivisible assignments (e.g. 8 KV heads on 16 devices),
    # i.e. GQA KV is replicated across TP — standard practice.
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _attend(q, k, v, q_pos, kv_valid, causal):
    """Core attention for one query block against the full K/V.

    q: (B, Tq, H, Dh); k/v: (B, S, Kh, Dh); q_pos: (Tq,) global positions;
    kv_valid: (B, S) bool or None. Softmax in f32. GQA via head grouping.
    """
    B, Tq, H, Dh = q.shape
    S, Kh = k.shape[1], k.shape[2]
    g = H // Kh
    q5 = q.reshape(B, Tq, Kh, g, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q5, k).astype(jnp.float32)
    logits *= Dh ** -0.5
    if causal:
        kv_pos = jnp.arange(S)
        cmask = q_pos[:, None] >= kv_pos[None, :]  # (Tq, S)
        logits = jnp.where(cmask[None, None, None], logits, -1e30)
    if kv_valid is not None:
        logits = jnp.where(kv_valid[:, None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    o = o.reshape(B, Tq, H, Dh)
    return shard(o, "batch", None, "heads", None)


def attend_full(spec: AttentionSpec, q, k, v, *, base_pos: int = 0):
    """Training/prefill attention, chunked over the query axis.

    The chunk loop is a carry-free ``lax.map`` with a rematerialized body, so
    peak activation memory is O(Tq_chunk × S) instead of O(T²) and the
    backward pass recomputes per-chunk logits (flash-style dataflow in pure
    JAX — the TPU adaptation of memory-efficient attention).
    """
    B, T, H, Dh = q.shape
    cq = spec.q_chunk
    if T <= cq or T % cq != 0:
        return _attend(q, k, v, base_pos + jnp.arange(T), None, spec.causal)
    nq = T // cq
    qc = jnp.moveaxis(q.reshape(B, nq, cq, H, Dh), 1, 0)  # (nq, B, cq, H, Dh)

    @jax.checkpoint
    def body(args):
        qi, i = args
        pos = base_pos + i * cq + jnp.arange(cq)
        return _attend(qi, k, v, pos, None, spec.causal)

    oc = jax.lax.map(body, (qc, jnp.arange(nq)))
    return jnp.moveaxis(oc, 0, 1).reshape(B, T, H, Dh)


def apply_train(spec: AttentionSpec, params, x, positions=None):
    """Full-sequence attention (training / prefill). x: (B, T, D)."""
    B, T, _ = x.shape
    if positions is None:
        if spec.rope == "mrope":
            p1 = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            positions = jnp.stack([p1, p1, p1])  # text-only: t==h==w ids
        else:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q, k, v = _qkv(spec, params, x, positions)
    o = attend_full(spec, q, k, v)
    return spec.wo.apply(params["wo"], o.reshape(B, T, spec.n_heads * spec.head_dim))


def init_cache(spec: AttentionSpec, batch: int, max_len: int, dtype=None):
    """Dense decode cache. ``dtype=None`` falls back to float32; the model
    layer always passes its config dtype (``cfg.jdtype``) explicitly —
    the old hardcoded bfloat16 default silently downcast f32-configured
    models when this leaf was called directly."""
    if dtype is None:
        dtype = jnp.float32
    shape = (batch, max_len, spec.n_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def init_paged_cache(spec: AttentionSpec, n_slots: int, n_pages: int,
                     page_size: int, dtype=None):
    """Paged decode cache: a global K/V page pool plus a per-slot ``pos``.

    Page 0 is the reserved *null* page — block-table entries past a
    request's used depth point at it, so padded scatters and gathers always
    hit a valid pool index (their values are masked out by ``pos``)."""
    if dtype is None:
        dtype = jnp.float32
    shape = (n_pages, page_size, spec.n_kv_heads, spec.head_dim)
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((n_slots,), jnp.int32)}


def _update_rows(cache, new, pos):
    """Write ``new`` (B, 1, Kh, Dh) into ``cache`` (B, S, Kh, Dh) at a
    *per-row* sequence position ``pos`` (B,) — the slot-cache write used by
    continuous batching, where every slot sits at its own depth."""
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
    )(cache, new, pos)


def _flat_pool(pool):
    """The stacked page pool ``(L, n_pages, page_size, Kh, Dh)`` as one pool
    of ``L * n_pages`` pages, in which layer ``l``'s page ``i`` is page
    ``l * n_pages + i``: the kernels read a layer's pages where they lie
    from its block-table entries plus that offset. Merging the two major
    axes is a bitcast on the TPU's tiled layout (the tiles span the two
    minor axes only)."""
    return pool.reshape((-1,) + pool.shape[2:])


def apply_decode(spec: AttentionSpec, params, x, cache):
    """One decode step. x: (B, 1, D); cache K/V: (B, S, Kh, Dh).

    ``cache["pos"]`` is either a scalar (lockstep static batch — every row at
    the same depth) or a (B,) vector (slot-based continuous batching — every
    row advances independently; RoPE, the K/V write, and the validity mask
    are all per-row).

    When the cache's S axis is sharded (long-context cells), the f32 softmax
    reductions below are partitioned by GSPMD into per-shard partials plus an
    all-reduce — the flash-decoding combine, derived not hand-rolled.
    """
    B, T, _ = x.shape
    assert T == 1
    pos = cache["pos"]
    per_row = jnp.ndim(pos) == 1
    pos_b = pos if per_row else jnp.broadcast_to(pos[None], (B,))
    if spec.rope == "mrope":
        p = pos_b[:, None]
        positions = jnp.stack([p, p, p])
    else:
        positions = pos_b[:, None]
    q, k_new, v_new = _qkv(spec, params, x, positions)
    if per_row:
        k = _update_rows(cache["k"], k_new.astype(cache["k"].dtype), pos)
        v = _update_rows(cache["v"], v_new.astype(cache["v"].dtype), pos)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    S = k.shape[1]
    kv_valid = jnp.arange(S)[None, :] <= pos_b[:, None]
    o = _attend(q, k.astype(q.dtype), v.astype(q.dtype),
                jnp.zeros((1,), jnp.int32), kv_valid, causal=False)
    y = spec.wo.apply(params["wo"], o.reshape(B, 1, spec.n_heads * spec.head_dim))
    new_cache = {"k": k, "v": v, "pos": pos + 1}
    return y, new_cache


def apply_decode_paged(spec: AttentionSpec, params, x, cache, layer,
                       block_tables, live=None):
    """One decode step against the paged KV pool. x: (B, 1, D).

    ``cache``: {"kp"/"vp": (L, n_pages, page_size, Kh, Dh), "pos": (B,)} —
    the pools of every layer, of which this call reads and writes layer
    ``layer``'s; each slot's pages are named by its ``block_tables`` row
    (B, P), the same for every layer. The new K/V is scattered into
    ``(layer, page, offset)`` derived from the per-row ``pos``, then
    attention runs through :func:`repro.kernels.ops.paged_attention` —
    jnp-route bitwise-identical to :func:`apply_decode` on the same
    sequences, Pallas-route an online-softmax page stream.

    ``live`` (B,) bool masks rows that are actually decoding. This is
    load-bearing, not hygiene: unlike the slot-dense cache (where a dead
    row scatters harmlessly into its own reservation), the pool is shared
    — a non-live row (mid-chunked-prefill, or freshly admitted with a
    stale ``pos``) holds a real block table, and its clipped page index
    can alias onto an already-prefilled (possibly trie-shared) page.
    Non-live rows scatter to the layer's null page and their ``pos``
    freezes.
    """
    from repro.kernels import ops

    B, T, _ = x.shape
    assert T == 1
    kp, vp = cache["kp"], cache["vp"]
    page_size = kp.shape[2]
    P = block_tables.shape[1]
    pos = cache["pos"]                                        # (B,)
    if spec.rope == "mrope":
        p = pos[:, None]
        positions = jnp.stack([p, p, p])
    else:
        positions = pos[:, None]
    q, k_new, v_new = _qkv(spec, params, x, positions)
    pidx = jnp.clip(pos // page_size, 0, P - 1)               # logical page
    pages = jnp.take_along_axis(block_tables, pidx[:, None], axis=1)[:, 0]
    if live is not None:
        pages = jnp.where(live, pages, 0)                     # -> null page
    offs = pos % page_size
    kp = kp.at[layer, pages, offs].set(k_new[:, 0].astype(kp.dtype))
    vp = vp.at[layer, pages, offs].set(v_new[:, 0].astype(vp.dtype))
    base = layer * kp.shape[1]             # the layer's page 0, flattened
    o = ops.paged_attention(q[:, 0], _flat_pool(kp), _flat_pool(vp),
                            block_tables + base, pos + 1)
    o = shard(o[:, None], "batch", None, "heads", None)
    y = spec.wo.apply(params["wo"], o.reshape(B, 1, spec.n_heads * spec.head_dim))
    new_pos = pos + 1 if live is None else pos + live.astype(pos.dtype)
    return y, {"kp": kp, "vp": vp, "pos": new_pos}


def apply_verify_paged(spec: AttentionSpec, params, x, cache, layer,
                       block_tables, live=None):
    """Speculative-verify window against the paged KV pool. x: (B, Tq, D).

    The window's ``Tq`` tokens sit at absolute positions ``pos .. pos+Tq-1``
    where ``pos = cache["pos"]`` (B,) is the *accepted* depth — the engine
    sets it host-authoritatively before each spec step, which is also what
    makes rollback free: rejected tokens are simply re-scattered over next
    step. Each window token's K/V is scattered to its ``(layer, page,
    offset)`` (non-live rows to the layer's null page, same aliasing
    argument as :func:`apply_decode_paged`), then all ``Tq`` queries
    attend in one
    :func:`repro.kernels.ops.paged_attention_verify` dispatch, causally
    masked inside the window. Returns ``pos`` UNCHANGED — in spec mode the
    host owns the depth (the engine learns the accepted count and rolls
    forward/back itself).
    """
    from repro.kernels import ops

    B, Tq, _ = x.shape
    kp, vp = cache["kp"], cache["vp"]
    page_size = kp.shape[2]
    P = block_tables.shape[1]
    pos = cache["pos"]                                        # (B,)
    pos_bt = pos[:, None] + jnp.arange(Tq)[None, :]           # (B, Tq)
    if spec.rope == "mrope":
        positions = jnp.stack([pos_bt, pos_bt, pos_bt])
    else:
        positions = pos_bt
    q, k_new, v_new = _qkv(spec, params, x, positions)
    pidx = jnp.clip(pos_bt // page_size, 0, P - 1)            # (B, Tq)
    pages = jnp.take_along_axis(block_tables, pidx, axis=1)   # (B, Tq)
    if live is not None:
        pages = jnp.where(live[:, None], pages, 0)            # -> null page
    offs = pos_bt % page_size
    kp = kp.at[layer, pages, offs].set(k_new.astype(kp.dtype))
    vp = vp.at[layer, pages, offs].set(v_new.astype(vp.dtype))
    base = layer * kp.shape[1]             # the layer's page 0, flattened
    o = ops.paged_attention_verify(q, _flat_pool(kp), _flat_pool(vp),
                                   block_tables + base, pos + Tq)
    o = shard(o, "batch", None, "heads", None)
    y = spec.wo.apply(params["wo"],
                      o.reshape(B, Tq, spec.n_heads * spec.head_dim))
    return y, {"kp": kp, "vp": vp, "pos": pos}


def prefill_chunk_paged(spec: AttentionSpec, params, x, cache, layer, bt_row,
                        slot, start, chunk_len):
    """One page-aligned prefill chunk of a single request (batch 1).

    ``x: (1, Tc, D)`` with ``Tc`` a page multiple and ``start`` (the global
    position of the chunk's first token) page-aligned; ``chunk_len <= Tc``
    is the number of real tokens (the final chunk is right-padded).
    ``bt_row: (P,)`` is the request's block-table row and ``cache``'s pools
    hold every layer, as in :func:`apply_decode_paged`. The chunk's K/V is
    scattered into layer ``layer``'s pages, then the chunk queries attend
    causally over the request's whole cached context (reused prefix pages
    included)
    through :func:`repro.kernels.ops.paged_prefill_attention` — the jnp
    oracle reproduces the old block-table gather + dense ``_attend``
    bitwise (masked columns are exact zeros), so the result stays bitwise
    what a monolithic prefill produces; the flash kernel routes stream
    only the pages at or below the causal horizon instead of the full
    table width.
    """
    from repro.kernels import ops
    B, Tc, _ = x.shape
    assert B == 1
    kp, vp = cache["kp"], cache["vp"]
    page_size = kp.shape[2]
    P = bt_row.shape[0]
    n_chunk_pages = Tc // page_size
    assert Tc % page_size == 0, (Tc, page_size)
    q_pos = start + jnp.arange(Tc)
    if spec.rope == "mrope":
        p1 = jnp.broadcast_to(q_pos[None], (1, Tc))
        positions = jnp.stack([p1, p1, p1])
    else:
        positions = jnp.broadcast_to(q_pos[None], (1, Tc))
    q, k, v = _qkv(spec, params, x, positions)
    # chunk-page ids via masked gather, NOT dynamic_slice: a final chunk
    # whose padded tail reaches past the table (max_len not a chunk
    # multiple) must scatter that tail to the null page — a clamped slice
    # would alias earlier entries and overwrite real K/V with garbage
    idx = start // page_size + jnp.arange(n_chunk_pages)
    page_ids = jnp.where(idx < P, bt_row[jnp.clip(idx, 0, P - 1)], 0)
    Kh, Dh = spec.n_kv_heads, spec.head_dim
    kp = kp.at[layer, page_ids].set(
        k[0].reshape(n_chunk_pages, page_size, Kh, Dh).astype(kp.dtype))
    vp = vp.at[layer, page_ids].set(
        v[0].reshape(n_chunk_pages, page_size, Kh, Dh).astype(vp.dtype))
    # chunk queries attend over this request's full context (prefix + the
    # chunk just written) straight off the page pool — no gathered view
    base = layer * kp.shape[1]             # the layer's page 0, flattened
    o = ops.paged_prefill_attention(q[0], _flat_pool(kp), _flat_pool(vp),
                                    bt_row + base, start, chunk_len)
    o = shard(o[None], "batch", None, "heads", None)
    y = spec.wo.apply(params["wo"], o.reshape(1, Tc, spec.n_heads * spec.head_dim))
    pos = cache["pos"].at[slot].set(start + chunk_len)
    return y, {"kp": kp, "vp": vp, "pos": pos}
