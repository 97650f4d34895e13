"""Pallas TPU kernel: block-diagonal matmul (the MPDCompress inference op).

Computes, for packed inputs ``x: (M, nb*bi)`` and packed diagonal blocks
``wp: (nb, bi, bo)``::

    y[:, n*bo:(n+1)*bo] = x[:, n*bi:(n+1)*bi] @ wp[n]        for n in range(nb)

with an optional fused bias + activation epilogue. This is the paper's
"hardware-desirable block matrix" form: every grid step is a dense
MXU-aligned tile, there is no indexing metadata, and blocks are fully
independent (the property the paper exploits for parallel speedup — here it
additionally makes the ``nb`` axis shardable across chips).

Quantized weights
-----------------
``wp`` may be int8 (symmetric per-output-channel quantization from
:mod:`repro.kernels.quant`) with ``scale: (nb, bo)`` riding in as one extra
operand. Weight tiles stream from HBM at 1 byte/element and are widened
in-register; because the scale is per *output channel* it commutes with the
K-accumulation, so the f32 accumulator holds raw int-products and the
single ``acc * scale`` rescale runs once in the epilogue — the memory-bound
decode path pays int8 HBM bandwidth, not fp32.

TPU mapping
-----------
Grid ``(m_tiles, nb, o_tiles, k_tiles)`` with K innermost ("arbitrary"
semantics) accumulating into a f32 VMEM scratch tile; the epilogue runs on
the last K step. Block shapes default to MXU-native ``128×128`` output tiles
with a ``512``-deep K stream. Activations, outputs, scales and biases are
indexed as flat ``(rows, nb*width)`` arrays — the index map picks block
``n``'s lane tiles — so every block is lane-aligned as Mosaic requires
(a ``(bm, 1, bk)`` block over ``(m, nb, bi)`` is not). Awkward (prime/odd)
dims are padded to the next aligned tile multiple instead of degrading the
tile search (zero rows/cols are exact; see :mod:`repro.kernels.tiling`).

Decode-shaped path
------------------
Steady-state serve decode runs ``m = n_slots`` (≈8) rows — a 128-row m-tile
wastes 15/16 of the MXU feed and the K-innermost revisiting grid re-reads
the tiny activation every step. When ``m`` is small the wrapper switches to
a weight-stationary variant: ``m`` padded to the dtype's sublane multiple, a flat
``(nb, o_tiles)`` grid with the full K depth resident per step (decode-side
``bi = d_in/c`` is small by construction), no scratch accumulator, and the
same epilogue. Selected automatically (``small_m=None``); both fp and int8
weights take it. Result is bit-identical to the general path for shapes
whose K fits one tile (same single-dot accumulation order).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import ACTIVATIONS
from .quant import widen_in_register as _widen
from .tiling import LANE, pad_axis, pick_tile, round_up, sublane

# auto decode-path thresholds: m at or below this uses the flat grid, as
# long as the full K depth fits comfortably in VMEM alongside one out tile
SMALL_M_MAX = 32
SMALL_M_K_MAX = 4096


def _epilogue(acc, s_ref, b_ref, activation, out_dtype):
    """Per-output-channel dequant scale, bias and activation on the f32
    accumulator; ``s_ref``/``b_ref`` are ``(1, bn)`` tiles."""
    if s_ref is not None:
        acc = acc * s_ref[...].astype(jnp.float32)
    if b_ref is not None:
        acc = acc + b_ref[...].astype(jnp.float32)
    return ACTIVATIONS[activation](acc).astype(out_dtype)


def _bdmm_kernel(*refs, n_k: int, activation, out_dtype, has_bias: bool,
                 has_scale: bool):
    """One (bm, bn) output tile of one diagonal block; accumulates over K."""
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scale else None
    b_ref = next(it) if has_bias else None
    o_ref, acc_ref = next(it), next(it)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x tile: (bm, bk) ; w tile: (1, bk, bn)
    x = x_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, _widen(w_ref[0], x),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = _epilogue(acc_ref[...], s_ref, b_ref, activation,
                               out_dtype)


def _bdmm_decode_kernel(*refs, activation, out_dtype, has_bias: bool,
                        has_scale: bool):
    """Weight-stationary small-m step: one (m_pad, bn) out tile per grid
    cell, full K resident — no K loop, no scratch accumulator."""
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scale else None
    b_ref = next(it) if has_bias else None
    o_ref = next(it)

    x = x_ref[...]
    acc = jax.lax.dot_general(
        x, _widen(w_ref[0], x),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = _epilogue(acc, s_ref, b_ref, activation, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "bm", "bn", "bk", "interpret", "out_dtype",
                     "small_m"),
)
def bdmm(
    x: jax.Array,
    wp: jax.Array,
    bias: Optional[jax.Array] = None,
    scale: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=None,
    small_m: Optional[bool] = None,
) -> jax.Array:
    """Block-diagonal matmul ``(..., nb*bi) x (nb, bi, bo) -> (..., nb*bo)``.

    ``bias`` (if given) is packed ``(nb*bo,)``. An int8 ``wp`` requires
    ``scale: (nb, bo)`` (per-output-channel dequant, applied in the
    epilogue). Tiles follow :mod:`repro.kernels.tiling` (lane- and
    dtype-sublane-aligned; awkward dims are padded), so any shape works.
    ``small_m`` forces (True) / forbids (False) the decode-shaped
    weight-stationary path; ``None`` selects it automatically for small row
    counts.
    """
    nb, bi, bo = wp.shape
    lead = x.shape[:-1]
    assert x.shape[-1] == nb * bi, (x.shape, wp.shape)
    if jnp.issubdtype(wp.dtype, jnp.integer):
        assert scale is not None, "int8 wp needs a (nb, bo) scale operand"
    if scale is not None:
        assert scale.shape == (nb, bo), (scale.shape, wp.shape)
    m = 1
    for d in lead:
        m *= d
    out_dtype = out_dtype or x.dtype

    if small_m is None:
        small_m = m <= SMALL_M_MAX and bi <= SMALL_M_K_MAX

    m_align = max(sublane(x.dtype), sublane(out_dtype))
    bn_, bo_p = pick_tile(bo, bn, LANE)
    jt = bo_p // bn_                      # out tiles per diagonal block
    if small_m:
        # weight-stationary flat grid: full K per step, m padded to sublane
        bm_ = m_p = round_up(m, m_align)
        bk_ = bi_p = round_up(bi, LANE)
        grid = (nb, jt)
        x_idx = lambda n, j: (0, n)
        w_idx = lambda n, j: (n, 0, j)
        o_idx = lambda n, j: (0, n * jt + j)
        kernel_fn, dims = _bdmm_decode_kernel, ("parallel", "parallel")
    else:
        bm_, m_p = pick_tile(m, bm, m_align)
        bk_, bi_p = pick_tile(bi, bk, LANE)
        n_k = bi_p // bk_
        grid = (m_p // bm_, nb, jt, n_k)
        x_idx = lambda i, n, j, k: (i, n * n_k + k)
        w_idx = lambda i, n, j, k: (n, k, j)
        o_idx = lambda i, n, j, k: (i, n * jt + j)
        kernel_fn = _bdmm_kernel
        dims = ("parallel", "parallel", "parallel", "arbitrary")
    v_idx = lambda *g: (0, o_idx(*g)[1])

    # zero-padding is exact: padded K rows/cols contribute nothing, padded
    # m/bo rows are sliced off below
    x2 = pad_axis(pad_axis(x.reshape(m, nb, bi), 0, m_p), 2, bi_p)
    x2 = x2.reshape(m_p, nb * bi_p)
    wp = pad_axis(pad_axis(wp, 1, bi_p), 2, bo_p)

    has_bias, has_scale = bias is not None, scale is not None
    kw = dict(activation=activation, out_dtype=out_dtype, has_bias=has_bias,
              has_scale=has_scale)
    if not small_m:
        kw["n_k"] = n_k
    kernel = functools.partial(kernel_fn, **kw)

    in_specs = [
        pl.BlockSpec((bm_, bk_), x_idx),
        pl.BlockSpec((1, bk_, bn_), w_idx),
    ]
    args = [x2, wp]
    # per-output-channel operands ride as one (1, nb*bo_p) row
    for v in (scale, bias):
        if v is not None:
            in_specs.append(pl.BlockSpec((1, bn_), v_idx))
            args.append(pad_axis(v.reshape(nb, bo), 1, bo_p)
                        .reshape(1, nb * bo_p))

    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm_, bn_), o_idx),
        out_shape=jax.ShapeDtypeStruct((m_p, nb * bo_p), out_dtype),
        scratch_shapes=([] if small_m
                        else [pltpu.VMEM((bm_, bn_), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=dims),
        interpret=interpret,
    )(*args)
    y = y.reshape(m_p, nb, bo_p)[:m, :, :bo]
    return y.reshape(*lead, nb * bo)
