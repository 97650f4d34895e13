"""Public kernel entry points with backend routing + custom VJPs.

Routing policy (override with ``repro.kernels.ops.set_backend``):

* ``"pallas"``  — real Pallas lowering (TPU target).
* ``"interpret"`` — Pallas interpret mode (CPU correctness checks; slow).
* ``"jnp"``     — pure-jnp reference path (fast on CPU). Default off-TPU.

Every entry point is *fused and differentiable*: ``bias`` and ``activation``
execute inside the kernel epilogue (Pallas routes) or inside the jnp
reference (where XLA fuses them), and the custom VJPs extend to the fused
forms. Outside differentiation (serving) the primal runs as ONE fused
dispatch. Under ``grad``, the bdmm/masked_matmul fwd rules instead emit the
pre-activation ``z`` (kernel dispatch + an elementwise activation) and save
it as a residual, so the backward composes the activation gradient with the
upstream cotangent and reuses the existing bdmm/SDDMM transposes without
re-running the matmul — ``masked_matmul``'s forward is full dense FLOPs, a
recompute there would cost a fourth matmul per step. ``fused_ffn``'s
backward does recompute its pre-activations: those are block-local bdmms at
1/c cost, cheaper than carrying two ``(tokens, d_ff)`` residuals. Training
and serving therefore share one fused path; nothing calls the raw kernels
directly anymore.

All three backends honor ``bias``/``activation`` identically. ``precision``
only selects the einsum/dot precision on the ``jnp`` route; the Pallas
kernels always accumulate in float32 via ``preferred_element_type``
(equivalent to HIGHEST), so it is intentionally — and now explicitly — a
no-op there.

The masked-dense training invariant (off-mask grads are exact zeros) holds
by construction on every route.

``bdmm_quant``/``fused_ffn_quant`` are the int8-weight serving forms
(deployment artifacts from :mod:`repro.kernels.quant`): same routing, same
epilogues, per-output-channel scales dequantized in-register — but
inference-only, so they carry no custom VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import bdmm as bdmm_kernel
from . import fused_ffn as ffn_kernel
from . import masked_matmul as mm_kernel
from . import paged_attention as paged_attn_kernel
from . import paged_prefill as paged_prefill_kernel
from . import ref

# None until first read: the default is resolved from the platform then,
# not at import (importing must not initialise a JAX backend).
_BACKEND: Optional[str] = None

# Prefill-attention override: when None, chunked prefill follows get_backend().
# Settable independently (``--prefill-kernel``) because the flash prefill
# kernel's interpret mode is the CPU-testable route while the rest of the
# serve loop stays on the fast jnp oracle. Read at trace time — set it
# before the engine builds/warms its jits, or their caches go stale.
_PREFILL_BACKEND: Optional[str] = None


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("pallas", "interpret", "jnp"), name
    _BACKEND = name


def get_backend() -> str:
    """The kernel route: as last set, else ``"pallas"`` on a TPU and
    ``"jnp"`` elsewhere. Read at trace time."""
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = "pallas" if jax.default_backend() == "tpu" else "jnp"
    return _BACKEND


def set_prefill_backend(name: Optional[str]) -> None:
    global _PREFILL_BACKEND
    assert name in (None, "pallas", "interpret", "jnp"), name
    _PREFILL_BACKEND = name


def prefill_backend() -> str:
    return (_PREFILL_BACKEND if _PREFILL_BACKEND is not None
            else get_backend())


def _act_bwd(activation: Optional[str], z, g):
    """Compose the upstream cotangent with the activation gradient at the
    (recomputed) pre-activation ``z`` — via jax.vjp of the registry entry, so
    the backward can never drift from the forward's definition."""
    if activation is None:
        return g
    _, vjp = jax.vjp(ref.ACTIVATIONS[activation], z)
    return vjp(g)[0]


def _mesh_axes(logical: str, dim: int):
    """The active mesh and the mesh axes its rule table maps ``logical`` to,
    as ``(mesh, axes)``. ``axes`` is ``()`` when there are none or their
    combined size does not divide ``dim`` (4 KV heads on an 8-way model
    axis replicate — the policy of :func:`repro.dist.sharding
    .sanitize_spec`); ``mesh`` is ``None`` outside any mesh."""
    from repro.dist import sharding as _sh
    mesh, rules = _sh.current()
    if mesh is None:
        return None, ()
    axes = tuple((rules or {}).get(logical) or ())
    if any(a not in mesh.shape for a in axes):
        return mesh, ()
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return mesh, (axes if size > 1 and dim % size == 0 else ())


def _block_parallel(fn, x, *wargs):
    """Run a Pallas block-diagonal kernel ``fn(x, *wargs)`` under the active
    mesh.

    Mosaic kernels cannot be partitioned by GSPMD, so under a mesh the call
    runs inside ``shard_map``: each device runs its share of the ``nb``
    diagonal blocks when the rule table's ``"blocks"`` axes divide ``nb``
    (blocks are independent — the paper's parallel-speedup property — so
    nothing is exchanged inside), and all of them on replicated operands
    otherwise. ``x (..., nb*bi)`` and the output ``(..., nb*bo)`` split on
    their last dim; each of ``wargs`` (weights, scales, packed biases, or
    ``None``) carries the block axis first, ``wargs[0]`` being ``(nb, ...)``.
    A plain call outside any mesh.
    """
    mesh, axes = _mesh_axes("blocks", wargs[0].shape[0])
    if mesh is None:
        return fn(x, *wargs)
    from jax.sharding import PartitionSpec as P

    ax = axes or None
    held = [i for i, w in enumerate(wargs) if w is not None]
    x_spec = P(*([None] * (x.ndim - 1)), ax)
    w_specs = tuple(P(ax, *([None] * (wargs[i].ndim - 1))) for i in held)

    def inner(x_, *ws):
        full = list(wargs)
        for i, w in zip(held, ws):
            full[i] = w
        return fn(x_, *full)

    return jax.shard_map(inner, mesh=mesh, in_specs=(x_spec,) + w_specs,
                         out_specs=x_spec, check_vma=False,
                         )(x, *(wargs[i] for i in held))


# --------------------------------------------------------------------------
# bdmm — block-diagonal matmul (packed inference/training form)
# --------------------------------------------------------------------------

def _bdmm_raw(x, wp, bias, activation, precision):
    """Backend-routed fused forward (no custom VJP — used by fwd and bwd)."""
    if get_backend() == "jnp":
        return ref.bdmm_ref(x, wp, bias, activation=activation,
                            precision=precision)
    kernel = functools.partial(bdmm_kernel.bdmm, activation=activation,
                               interpret=(get_backend() == "interpret"))
    return _block_parallel(kernel, x, wp, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bdmm(x, wp, bias, activation, precision):
    return _bdmm_raw(x, wp, bias, activation, precision)


def _bdmm_fwd(x, wp, bias, activation, precision):
    if activation is None:
        return _bdmm(x, wp, bias, None, precision), (x, wp, bias, None)
    # under grad: emit pre-activation z and save it, so bwd needs no recompute
    z = _bdmm_raw(x, wp, bias, None, precision)
    return ref.ACTIVATIONS[activation](z), (x, wp, bias, z)


def _bdmm_bwd(activation, precision, res, g):
    x, wp, bias, z = res
    nb, bi, bo = wp.shape
    lead = x.shape[:-1]
    if activation is not None:
        g = _act_bwd(activation, z, g)
    # dx[:, n, :] = g[:, n, :] @ wp[n]^T    (another bdmm with transposed blocks)
    dx = _bdmm_raw(g, jnp.swapaxes(wp, 1, 2), None, None,
                   precision).reshape(*lead, nb * bi)
    # dwp[n] = x[:, n, :]^T @ g[:, n, :]    (per-block SDDMM-free dense grad)
    xb = x.reshape(-1, nb, bi)
    gb = g.reshape(-1, nb, bo)
    dwp = jnp.einsum("tnk,tno->nko", xb, gb, precision=precision).astype(wp.dtype)
    db = None if bias is None else g.reshape(-1, nb * bo).sum(0).astype(bias.dtype)
    return dx, dwp, db


_bdmm.defvjp(_bdmm_fwd, _bdmm_bwd)


def bdmm(x, wp, bias=None, *, activation: Optional[str] = None, precision=None):
    """Differentiable fused block-diagonal matmul
    ``(..., nb*bi) -> act(x @ blockdiag(wp) + bias)`` with packed outputs
    ``(..., nb*bo)``.

    ``bias`` is packed ``(nb*bo,)``; ``activation`` names an entry of
    :data:`repro.kernels.ref.ACTIVATIONS`. Both run inside the kernel
    epilogue on the Pallas routes and fuse under XLA on the jnp route.
    """
    return _bdmm(x, wp, bias, activation, precision)


def bdmm_quant(x, wq, scale, bias=None, *, activation: Optional[str] = None,
               precision=None, small_m: Optional[bool] = None):
    """Int8-weight fused block-diagonal matmul
    ``(..., nb*bi) -> act((x @ blockdiag(dequant(wq))) + bias)``.

    ``wq: (nb, bi, bo)`` int8 with per-output-channel ``scale: (nb, bo)``
    (:func:`repro.kernels.quant.quantize_blocks`). Inference-only — no
    custom VJP: quantized weights are a deployment artifact, never trained
    through. The Pallas routes stream int8 weight tiles and dequantize
    in-register against the f32 accumulator; ``precision`` selects the jnp
    einsum precision only.
    """
    if get_backend() == "jnp":
        return ref.bdmm_quant_ref(x, wq, scale, bias, activation=activation,
                                  precision=precision)
    kernel = functools.partial(bdmm_kernel.bdmm, activation=activation,
                               interpret=(get_backend() == "interpret"),
                               small_m=small_m)
    return _block_parallel(kernel, x, wq, bias, scale)


# --------------------------------------------------------------------------
# masked matmul — paper-faithful training op
# --------------------------------------------------------------------------

def _masked_matmul_raw(x, w, mask, bias, activation, precision):
    if get_backend() == "jnp":
        return ref.masked_matmul_ref(x, w, mask, bias, activation=activation,
                                     precision=precision)
    return mm_kernel.masked_matmul(x, w, mask, bias, activation=activation,
                                   interpret=(get_backend() == "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _masked_matmul(x, w, mask, bias, activation, precision):
    return _masked_matmul_raw(x, w, mask, bias, activation, precision)


def _masked_matmul_fwd(x, w, mask, bias, activation, precision):
    if activation is None:
        return (_masked_matmul(x, w, mask, bias, None, precision),
                (x, w, mask, bias, None))
    # under grad: save the pre-activation — recomputing it in bwd would be a
    # fourth full-dense matmul on the masked_dense training hot path
    z = _masked_matmul_raw(x, w, mask, bias, None, precision)
    return ref.ACTIVATIONS[activation](z), (x, w, mask, bias, z)


def _masked_matmul_bwd(activation, precision, res, g):
    x, w, mask, bias, z = res
    if activation is not None:
        g = _act_bwd(activation, z, g)
    if get_backend() == "jnp":
        dx = jnp.dot(g, (w * mask.astype(w.dtype)).T, precision=precision)
        dw = ref.matmul_masked_grad_ref(
            x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1]), mask,
            precision=precision,
        ).astype(w.dtype)
    else:
        interp = get_backend() == "interpret"
        dx = mm_kernel.masked_matmul(g, w, mask, transpose_rhs=True,
                                     interpret=interp)
        dw = mm_kernel.sddmm_masked(x, g, mask, interpret=interp).astype(w.dtype)
    db = (None if bias is None
          else g.reshape(-1, g.shape[-1]).sum(0).astype(bias.dtype))
    return dx, dw, jnp.zeros_like(mask), db


_masked_matmul.defvjp(_masked_matmul_fwd, _masked_matmul_bwd)


def masked_matmul(x, w, mask, bias=None, *, activation: Optional[str] = None,
                  precision=None):
    """Differentiable ``y = act(x @ (mask ∘ w) + b)`` with masked gradients
    and the bias/activation epilogue fused into the kernel."""
    return _masked_matmul(x, w, jax.lax.stop_gradient(mask), bias, activation,
                          precision)


# --------------------------------------------------------------------------
# fused block-diagonal MLP — the packed+perm-fused FFN hot path
# --------------------------------------------------------------------------

def _fused_ffn_kernel(x, w_up, w_down, w_gate, b_up, b_gate, b_down, s_up,
                      s_gate, s_down, activation):
    """The Pallas fused-FFN call (fp or int8), block-parallel under a
    mesh."""
    def kernel(x_, *w):
        return ffn_kernel.fused_ffn(
            x_, w[0], w[1], w_gate=w[2], b_up=w[3], b_gate=w[4], b_down=w[5],
            s_up=w[6], s_gate=w[7], s_down=w[8], activation=activation,
            interpret=(get_backend() == "interpret"))
    return _block_parallel(kernel, x, w_up, w_down, w_gate, b_up, b_gate,
                           b_down, s_up, s_gate, s_down)


def _fused_ffn_raw(x, w_up, w_gate, w_down, b_up, b_gate, b_down, activation,
                   precision):
    if get_backend() == "jnp":
        return ref.fused_ffn_ref(x, w_up, w_down, w_gate=w_gate, b_up=b_up,
                                 b_gate=b_gate, b_down=b_down,
                                 activation=activation, precision=precision)
    return _fused_ffn_kernel(x, w_up, w_down, w_gate, b_up, b_gate, b_down,
                             None, None, None, activation)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _fused_ffn(x, w_up, w_gate, w_down, b_up, b_gate, b_down, activation,
               precision):
    return _fused_ffn_raw(x, w_up, w_gate, w_down, b_up, b_gate, b_down,
                          activation, precision)


def _fused_ffn_fwd(x, w_up, w_gate, w_down, b_up, b_gate, b_down, activation,
                   precision):
    y = _fused_ffn(x, w_up, w_gate, w_down, b_up, b_gate, b_down, activation,
                   precision)
    return y, (x, w_up, w_gate, w_down, b_up, b_gate, b_down)


def _fused_ffn_bwd(activation, precision, res, g):
    """Backward decomposes into the bdmm transposes: recompute the (cheap,
    block-local) pre-activations, vjp through the elementwise hidden
    epilogue, then standard per-block matmul gradients."""
    x, w_up, w_gate, w_down, b_up, b_gate, b_down = res
    nb, bi, f = w_up.shape
    bo = w_down.shape[2]
    lead = x.shape[:-1]

    z_u = _bdmm_raw(x, w_up, b_up, None, precision)
    if w_gate is not None:
        z_g = _bdmm_raw(x, w_gate, b_gate, None, precision)
        h, epi_vjp = jax.vjp(ref.gated(activation), z_g, z_u)
    else:
        z_g = None
        h, epi_vjp = jax.vjp(ref.ACTIVATIONS[activation], z_u)

    # down projection grads
    dh = _bdmm_raw(g, jnp.swapaxes(w_down, 1, 2), None, None, precision)
    hb = h.reshape(-1, nb, f)
    gb = g.reshape(-1, nb, bo)
    dw_down = jnp.einsum("tnk,tno->nko", hb, gb,
                         precision=precision).astype(w_down.dtype)
    db_down = (None if b_down is None
               else g.reshape(-1, nb * bo).sum(0).astype(b_down.dtype))

    # hidden epilogue grads -> up/gate pre-activation cotangents
    if w_gate is not None:
        dz_g, dz_u = epi_vjp(dh)
    else:
        (dz_u,) = epi_vjp(dh)
        dz_g = None

    def proj_bwd(dz, w, b):
        dx = _bdmm_raw(dz, jnp.swapaxes(w, 1, 2), None, None, precision)
        dzb = dz.reshape(-1, nb, f)
        xb = x.reshape(-1, nb, bi)
        dw = jnp.einsum("tnk,tno->nko", xb, dzb,
                        precision=precision).astype(w.dtype)
        db = None if b is None else dz.reshape(-1, nb * f).sum(0).astype(b.dtype)
        return dx, dw, db

    dx, dw_up, db_up = proj_bwd(dz_u, w_up, b_up)
    if w_gate is not None:
        dx_g, dw_gate, db_gate = proj_bwd(dz_g, w_gate, b_gate)
        dx = dx + dx_g
    else:
        dw_gate = db_gate = None
    return (dx.reshape(*lead, nb * bi), dw_up, dw_gate, dw_down, db_up,
            db_gate, db_down)


_fused_ffn.defvjp(_fused_ffn_fwd, _fused_ffn_bwd)


def fused_ffn(x, w_up, w_down, *, w_gate=None, b_up=None, b_gate=None,
              b_down=None, activation: Optional[str] = "silu", precision=None):
    """Differentiable fused block-diagonal MLP (one dispatch on the Pallas
    routes): ``y = (act(x@Wg+bg) * (x@Wu+bu)) @ Wd + bd`` when gated, else
    ``y = act(x@Wu+bu) @ Wd + bd``.

    Shapes: ``x (..., nb*bi)``; ``w_up/w_gate (nb, bi, f)``;
    ``w_down (nb, f, bo)``; biases packed. The ``(tokens, nb*f)`` hidden
    lives only in VMEM on the Pallas routes.
    """
    if w_gate is None and b_gate is not None:
        raise ValueError("fused_ffn: b_gate given but w_gate is None — the "
                         "non-gated form has no gate bias to apply")
    return _fused_ffn(x, w_up, w_gate, w_down, b_up, b_gate, b_down,
                      activation, precision)


def fused_ffn_quant(x, w_up, w_down, *, s_up, s_down, w_gate=None,
                    s_gate=None, b_up=None, b_gate=None, b_down=None,
                    activation: Optional[str] = "silu", precision=None):
    """Int8-weight fused block-diagonal MLP (one dispatch on the Pallas
    routes). Weights int8 ``(nb, bi, f)`` / ``(nb, f, bo)`` with
    per-output-channel scales ``s_up/s_gate: (nb, f)``,
    ``s_down: (nb, bo)``; biases in true (dequantized) scale. Inference-only
    — no custom VJP.
    """
    if w_gate is None and (b_gate is not None or s_gate is not None):
        raise ValueError("fused_ffn_quant: gate bias/scale given but w_gate "
                         "is None")
    if get_backend() == "jnp":
        return ref.fused_ffn_quant_ref(
            x, w_up, w_down, w_gate=w_gate, b_up=b_up, b_gate=b_gate,
            b_down=b_down, s_up=s_up, s_gate=s_gate, s_down=s_down,
            activation=activation, precision=precision)
    return _fused_ffn_kernel(x, w_up, w_down, w_gate, b_up, b_gate, b_down,
                             s_up, s_gate, s_down, activation)


# --------------------------------------------------------------------------
# paged attention — decode step against the paged KV pool
# --------------------------------------------------------------------------

def _tp_head_parallel(fn, head_axis, kernel, q, k_pages, v_pages, *rest):
    """Head-parallel ``shard_map`` wrapper shared by the three paged ops.

    Queries shard on ``head_axis``; the K/V pools shard on their KV-head
    axis (dim 2 — dim 3 of the layer-stacked pool ``paged_cache_axes``
    places, whose two major axes the model merges, so sharded pools are
    consumed in place with zero resharding); block tables, lengths, and
    chunk offsets are host-authoritative and replicated. Each shard runs
    the routed kernel on its local head group — per-head arithmetic is
    identical to the single-device dispatch, so after the output
    ``all_gather`` over the head dim the result is *bit-identical* to the
    unsharded path (the serve exactness contract extends to TP). One
    collective per attention output; the packed projections around it run
    block-parallel (:func:`_block_parallel`).

    The pools shard exactly when ``paged_cache_axes`` places them sharded:
    the rule table maps ``"kv_heads"`` to mesh axes whose size divides the
    KV-head count. Otherwise the ``jnp`` route runs unsharded and a Pallas
    route (``kernel``; Mosaic kernels cannot be partitioned by GSPMD) runs
    on replicated operands on every device.
    """
    mesh, axes = _mesh_axes("kv_heads", k_pages.shape[2])
    if mesh is None or not (axes or kernel):
        return fn(q, k_pages, v_pages, *rest)
    from jax.sharding import PartitionSpec as P

    axes = axes or None

    rest = tuple(r if hasattr(r, "ndim") else jnp.asarray(r) for r in rest)
    q_spec = P(*(axes if i == head_axis else None for i in range(q.ndim)))
    kv_spec = P(None, None, axes, None)
    rest_specs = tuple(P(*([None] * r.ndim)) for r in rest)
    out_spec = P(*([None] * q.ndim))

    def inner(q_, kp_, vp_, *rest_):
        o = fn(q_, kp_, vp_, *rest_)
        if axes is None:
            return o
        return jax.lax.all_gather(o, axes, axis=head_axis, tiled=True)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec) + rest_specs,
        out_specs=out_spec, check_vma=False,
    )(q, k_pages, v_pages, *rest)


def paged_attention(q, k_pages, v_pages, block_tables, lengths):
    """One decode step of attention against the paged KV pool (see
    :mod:`repro.kernels.paged_attention` for layout). Inference-only — no
    custom VJP: decode never differentiates.

    On the jnp route the oracle is bitwise-stable against the contiguous
    decode path (the serve exactness contract); the Pallas routes stream
    pages via scalar-prefetched block tables with an online-softmax combine.
    Under an active mesh whose rules shard ``"kv_heads"``, the dispatch runs
    head-parallel across the mesh via :func:`_tp_head_parallel` —
    bit-identical output, sharded pools.
    """
    def routed(q_, kp_, vp_, bt_, len_):
        if get_backend() == "jnp":
            return ref.paged_attention_ref(q_, kp_, vp_, bt_, len_)
        return paged_attn_kernel.paged_attention(
            q_, kp_, vp_, bt_, len_, interpret=(get_backend() == "interpret"))

    return _tp_head_parallel(routed, 1, get_backend() != "jnp", q, k_pages,
                             v_pages, block_tables, lengths)


def paged_attention_verify(q, k_pages, v_pages, block_tables, lengths):
    """Speculative-verify attention: a ``(B, Tq, H, Dh)`` window of query
    positions per row against the paged KV pool, causally masked inside the
    window (``lengths`` is the depth at the last window position). The jnp
    oracle keeps the decode path's contraction order so greedy verification
    reproduces decode argmax; the Pallas route folds the window into the
    GQA group axis of the streaming kernel. Inference-only — no custom VJP.
    TP-sharded head-parallel under an active mesh, like
    :func:`paged_attention`.
    """
    def routed(q_, kp_, vp_, bt_, len_):
        if get_backend() == "jnp":
            return ref.paged_attention_verify_ref(q_, kp_, vp_, bt_, len_)
        return paged_attn_kernel.paged_attention_verify(
            q_, kp_, vp_, bt_, len_, interpret=(get_backend() == "interpret"))

    return _tp_head_parallel(routed, 2, get_backend() != "jnp", q, k_pages,
                             v_pages, block_tables, lengths)


def paged_prefill_attention(q, k_pages, v_pages, bt_row, start, chunk_len):
    """Chunked-prefill attention for one request's ``(Tc, H, Dh)`` chunk
    against its paged context (chunk K/V already scattered into the pool).
    Causal per position, valid depth ``start + chunk_len``. Inference-only
    — no custom VJP.

    Routed on :func:`prefill_backend` (independently overridable via
    :func:`set_prefill_backend`): the jnp oracle is bitwise-stable against
    the dense gather+``_attend`` path it replaces (the serve exactness
    contract); the Pallas routes stream only the pages at or below each
    query tile's causal horizon, so prefill KV read scales with actual
    depth instead of the laddered block-table width.

    TP-sharded head-parallel under an active mesh, like
    :func:`paged_attention`.
    """
    def routed(q_, kp_, vp_, bt_, start_, clen_):
        backend = prefill_backend()
        if backend == "jnp":
            return ref.paged_prefill_attention_ref(q_, kp_, vp_, bt_,
                                                   start_, clen_)
        return paged_prefill_kernel.paged_prefill_attention(
            q_, kp_, vp_, bt_, start_, clen_,
            interpret=(backend == "interpret"))

    return _tp_head_parallel(routed, 1, prefill_backend() != "jnp", q,
                             k_pages, v_pages, bt_row, start, chunk_len)
