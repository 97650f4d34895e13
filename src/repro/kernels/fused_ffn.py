"""Pallas TPU kernel: block-diagonal fused MLP (packed + perm-fused FFN).

For a perm-fused packed FFN (paper Fig 3: inner permutations cancelled, the
hidden activation stays in block order) the three projections share one
block structure — block ``n`` of the MLP is completely independent:

    u_n = x_n @ Wu[n] + bu_n                       (bi -> f slice of d_ff)
    h_n = act(x_n @ Wg[n] + bg_n) * u_n            (gated; or act(u_n))
    y_n = h_n @ Wd[n] + bd_n                       (f -> bo)

Executed as separate ``bdmm`` calls this is 3 matmul dispatches plus 2
elementwise passes, with the ``(tokens, d_ff)`` hidden written to and read
back from HBM twice. Here one grid step computes the whole pipeline for one
``(m_tile, block, f_tile)`` cell with the hidden slice held in VMEM: a
single dispatch, and the hidden never touches HBM.

Quantized weights: all three projections may be int8
(:mod:`repro.kernels.quant`) with per-output-channel scales riding in as
extra operands — ``s_up``/``s_gate: (nb, f)`` rescale the hidden slice
in-register right after its dot (the hidden epilogue needs true-scale
values), while ``s_down: (nb, bo)`` commutes with the f-accumulation and is
applied once in the epilogue against the f32 accumulator. Weight tiles
stream from HBM at 1 byte/element.

TPU mapping
-----------
Grid ``(m_tiles, nb, f_tiles)`` with the f (hidden) axis innermost
("arbitrary" semantics) accumulating the down-projection into a f32 VMEM
scratch tile; up/gate biases index per f-tile, the down bias + store run on
the last f step. Working set per step (bm=128, bf=512, bi=bo=256, f32):

    x (bm·bi) + Wu,Wg (bi·bf ×2) + Wd (bf·bo) + h (bm·bf) + acc (bm·bo)
    ≈ 128KB + 512KB×3 + 256KB + 128KB ≈ 2 MB

— comfortably inside ~16 MB VMEM with double-buffering headroom.
Activations, outputs, scales and biases are indexed as flat
``(rows, nb*width)`` arrays so every block is lane-aligned. Awkward
(prime/odd) dims are padded to the next aligned tile multiple instead of
degrading the tile search (zero f-channels are exact: their ``w_down`` rows
are zero, so whatever the hidden epilogue produces there contributes
nothing).
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


def _ffn_kernel(*refs, n_f: int, activation, out_dtype, gated: bool,
                has_scale: bool, has_b_up: bool, has_b_gate: bool,
                has_b_down: bool):
    """One (bm, block, bf) cell: hidden slice in VMEM, fused epilogues."""
    it = iter(refs)
    x_ref = next(it)
    wu_ref = next(it)
    wg_ref = next(it) if gated else None
    wd_ref = next(it)
    su_ref = next(it) if has_scale else None
    sg_ref = next(it) if has_scale and gated else None
    sd_ref = next(it) if has_scale else None
    bu_ref = next(it) if has_b_up else None
    bg_ref = next(it) if has_b_gate else None
    bd_ref = next(it) if has_b_down else None
    o_ref = next(it)
    acc_ref = next(it)
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, bi)

    def proj(w_ref, s_ref, b_ref):
        z = jax.lax.dot_general(x, _widen(w_ref[0], x),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if s_ref is not None:
            z = z * s_ref[...].astype(jnp.float32)
        if b_ref is not None:
            z = z + b_ref[...].astype(jnp.float32)
        return z

    u = proj(wu_ref, su_ref, bu_ref)
    if gated:
        h = ACTIVATIONS[activation](proj(wg_ref, sg_ref, bg_ref)) * u
    else:
        h = ACTIVATIONS[activation](u)

    acc_ref[...] += jax.lax.dot_general(
        h, wd_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(fi == n_f - 1)
    def _epilogue():
        out = acc_ref[...]
        if sd_ref is not None:
            out = out * sd_ref[...].astype(jnp.float32)
        if bd_ref is not None:
            out = out + bd_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "bm", "bf", "interpret", "out_dtype"),
)
def fused_ffn(
    x: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    w_gate: Optional[jax.Array] = None,
    b_up: Optional[jax.Array] = None,
    b_gate: Optional[jax.Array] = None,
    b_down: Optional[jax.Array] = None,
    s_up: Optional[jax.Array] = None,
    s_gate: Optional[jax.Array] = None,
    s_down: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = "silu",
    bm: int = 128,
    bf: int = 512,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Fused block-diagonal MLP ``(..., nb*bi) -> (..., nb*bo)``.

    ``w_up/w_gate: (nb, bi, f)``; ``w_down: (nb, f, bo)``; biases packed
    (``(nb*f,)`` up/gate, ``(nb*bo,)`` down). Gated when ``w_gate`` is given
    (``h = act(gate) * up``), plain ``h = act(up)`` otherwise. Int8 weights
    require their scales (``s_up/s_gate: (nb, f)``, ``s_down: (nb, bo)``).
    Tile sizes clamp to the actual dims and awkward remainders are padded,
    so smoke shapes work unchanged.
    """
    nb, bi, f = w_up.shape
    nb_d, f_d, bo = w_down.shape
    assert (nb_d, f_d) == (nb, f), (w_up.shape, w_down.shape)
    if w_gate is None:
        # a gate bias/scale without a gate projection is a caller bug — the
        # kernel would silently stream an operand it never reads
        if b_gate is not None:
            raise ValueError("fused_ffn: b_gate given but w_gate is None")
        if s_gate is not None:
            raise ValueError("fused_ffn: s_gate given but w_gate is None")
    quant = jnp.issubdtype(w_up.dtype, jnp.integer)
    if quant:
        if s_up is None or s_down is None or (w_gate is not None
                                              and s_gate is None):
            raise ValueError("fused_ffn: int8 weights need s_up/s_down "
                             "(and s_gate when gated)")
        assert s_up.shape == (nb, f), (s_up.shape, w_up.shape)
        assert s_down.shape == (nb, bo), (s_down.shape, w_down.shape)
    elif s_up is not None or s_down is not None:
        raise ValueError("fused_ffn: scales passed with fp weights")
    lead = x.shape[:-1]
    assert x.shape[-1] == nb * bi, (x.shape, w_up.shape)
    m = 1
    for d in lead:
        m *= d
    out_dtype = out_dtype or x.dtype
    gated_ = w_gate is not None

    bm_, m_p = pick_tile(m, bm, max(sublane(x.dtype), sublane(out_dtype)))
    bf_, f_p = pick_tile(f, bf, LANE)
    bi_p, bo_p = round_up(bi, LANE), round_up(bo, LANE)
    n_f = f_p // bf_
    grid = (m_p // bm_, nb, n_f)

    # pad m rows and bo cols (sliced off below), bi (exact: zero K) and f
    # channels (exact: padded w_down rows are zero, so padded hidden
    # channels contribute nothing); activations, outputs and the
    # per-channel rows are flat (rows, nb*width) so every block is
    # lane-aligned
    x2 = pad_axis(pad_axis(x.reshape(m, nb, bi), 0, m_p), 2, bi_p)
    x2 = x2.reshape(m_p, nb * bi_p)

    def row(v, width, width_p):      # (nb*width,) or (nb, width) -> (1, nb*width_p)
        return pad_axis(v.reshape(nb, width), 1, width_p).reshape(1, -1)

    kernel = functools.partial(
        _ffn_kernel, n_f=n_f, activation=activation, out_dtype=out_dtype,
        gated=gated_, has_scale=bool(quant), has_b_up=b_up is not None,
        has_b_gate=b_gate is not None, has_b_down=b_down is not None,
    )

    w_in_spec = pl.BlockSpec((1, bi_p, bf_), lambda i, n, fi: (n, 0, fi))
    f_row_spec = pl.BlockSpec((1, bf_), lambda i, n, fi: (0, n * n_f + fi))
    o_row_spec = pl.BlockSpec((1, bo_p), lambda i, n, fi: (0, n))
    in_specs = [pl.BlockSpec((bm_, bi_p), lambda i, n, fi: (i, n)), w_in_spec]
    args = [x2, pad_axis(pad_axis(w_up, 1, bi_p), 2, f_p)]
    if gated_:
        assert w_gate.shape == (nb, bi, f), (w_gate.shape, (nb, bi, f))
        in_specs.append(w_in_spec)
        args.append(pad_axis(pad_axis(w_gate, 1, bi_p), 2, f_p))
    in_specs.append(pl.BlockSpec((1, bf_, bo_p), lambda i, n, fi: (n, fi, 0)))
    args.append(pad_axis(pad_axis(w_down, 1, f_p), 2, bo_p))
    if quant:
        for sc in ([s_up, s_gate] if gated_ else [s_up]):
            in_specs.append(f_row_spec)
            args.append(row(sc, f, f_p))
        in_specs.append(o_row_spec)
        args.append(row(s_down, bo, bo_p))
    for b in (b_up, b_gate):
        if b is not None:
            assert b.shape == (nb * f,), (b.shape, nb, f)
            in_specs.append(f_row_spec)
            args.append(row(b, f, f_p))
    if b_down is not None:
        assert b_down.shape == (nb * bo,), (b_down.shape, nb, bo)
        in_specs.append(o_row_spec)
        args.append(row(b_down, bo, bo_p))

    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm_, bo_p), lambda i, n, fi: (i, n)),
        out_shape=jax.ShapeDtypeStruct((m_p, nb * bo_p), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bo_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    y = y.reshape(m_p, nb, bo_p)[:m, :, :bo]
    return y.reshape(*lead, nb * bo)
