"""Pallas TPU kernels for the paper-faithful *training* path (Fig 2).

Training computes ``y = x @ (M ∘ W)`` every step. Done naively this
materializes the masked weight ``M ∘ W`` in HBM each time (an extra
``d_in·d_out`` read+write). These kernels fuse the binary-mask multiply into
the matmul operand load, so the mask application is free VPU work between the
HBM→VMEM copy and the MXU:

* :func:`masked_matmul` — ``y = x @ (M∘W)`` (optionally with W transposed,
  which is exactly the input-gradient ``dx = g @ (M∘W)^T``).
* :func:`sddmm_masked` — ``dW = (x^T @ g) ∘ M`` — the weight gradient. The
  mask is applied in the epilogue (an SDDMM: output sampled by the mask),
  which keeps the optimizer's view of off-mask weights exactly zero.

Together with the custom_vjp in :mod:`repro.kernels.ops` these make the
faithful masked-dense mode train end-to-end without ever writing ``M∘W``
back to HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import ACTIVATIONS


def _choose_tile(dim: int, want: int) -> int:
    t = min(want, dim)
    if dim % t:
        t = next(s for s in range(t, 0, -1) if dim % s == 0)
    return t


def _mm_kernel(*refs, n_k: int, activation, out_dtype, has_bias: bool, transpose_rhs: bool):
    if has_bias:
        x_ref, w_ref, m_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, m_ref, o_ref, acc_ref = refs
        b_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wm = w_ref[...] * m_ref[...].astype(w_ref.dtype)  # fused mask multiply (VPU)
    if transpose_rhs:
        dn = (((1,), (1,)), ((), ()))  # contract x's K with w's *second* dim
    else:
        dn = (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], wm, dimension_numbers=dn, preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        acc = ACTIVATIONS[activation](acc)
        o_ref[...] = acc.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "transpose_rhs", "bm", "bn", "bk", "interpret", "out_dtype"),
)
def masked_matmul(
    x: jax.Array,
    w: jax.Array,
    mask: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    transpose_rhs: bool = False,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``y = x @ (mask ∘ w)`` (or ``x @ (mask ∘ w)^T`` with ``transpose_rhs``).

    ``x: (..., K)``; ``w/mask: (K, N)`` normally, ``(N, K)`` when transposed.
    """
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, kdim)
    if transpose_rhs:
        n, wk = w.shape
    else:
        wk, n = w.shape
    assert wk == kdim, (x.shape, w.shape, transpose_rhs)
    assert mask.shape == w.shape

    bm_, bn_, bk_ = _choose_tile(m, bm), _choose_tile(n, bn), _choose_tile(kdim, bk)
    n_k = kdim // bk_
    grid = (m // bm_, n // bn_, n_k)
    out_dtype = out_dtype or x.dtype
    has_bias = bias is not None

    kernel = functools.partial(
        _mm_kernel, n_k=n_k, activation=activation, out_dtype=out_dtype,
        has_bias=has_bias, transpose_rhs=transpose_rhs,
    )
    if transpose_rhs:
        w_spec = pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k))
    else:
        w_spec = pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j))
    in_specs = [pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)), w_spec, w_spec]
    args = [x2, w, mask]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)))
        args.append(bias.reshape(1, n))

    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    return y.reshape(*lead, n)


def _sddmm_kernel(x_ref, g_ref, m_ref, o_ref, acc_ref, *, n_m: int, out_dtype):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x tile (bt, bi), g tile (bt, bo): acc += x^T @ g
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == n_m - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] * m_ref[...].astype(jnp.float32)).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bi", "bo", "bt", "interpret", "out_dtype"))
def sddmm_masked(
    x: jax.Array,
    g: jax.Array,
    mask: jax.Array,
    *,
    bi: int = 128,
    bo: int = 128,
    bt: int = 512,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Weight gradient of the masked matmul: ``dW = (x^T @ g) ∘ mask``.

    ``x: (..., d_in)``, ``g: (..., d_out)`` (same leading dims) ->
    ``(d_in, d_out)``. The mask multiply in the epilogue means off-mask
    entries of ``dW`` are *exact* zeros — the masked-dense training invariant.
    """
    d_in, d_out = mask.shape
    m = 1
    for d in x.shape[:-1]:
        m *= d
    x2 = x.reshape(m, d_in)
    g2 = g.reshape(m, d_out)
    bi_, bo_, bt_ = _choose_tile(d_in, bi), _choose_tile(d_out, bo), _choose_tile(m, bt)
    n_m = m // bt_
    grid = (d_in // bi_, d_out // bo_, n_m)
    out_dtype = out_dtype or x.dtype

    return pl.pallas_call(
        functools.partial(_sddmm_kernel, n_m=n_m, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt_, bi_), lambda i, j, t: (t, i)),
            pl.BlockSpec((bt_, bo_), lambda i, j, t: (t, j)),
            pl.BlockSpec((bi_, bo_), lambda i, j, t: (i, j)),
        ],
        out_specs=pl.BlockSpec((bi_, bo_), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d_in, d_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((bi_, bo_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x2, g2, mask)
