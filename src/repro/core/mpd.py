"""MPDLinear — the paper's contribution as a composable JAX module.

Functional (pytree-params) layer with three modes:

* ``masked_dense`` — **paper-faithful** (Fig 2 / Algorithm 1): keep the full
  dense weight, multiply the binary mask into it on every forward pass.
  Gradients are masked automatically (``d/dW (M∘W) = M ∘ upstream``) and the
  optimizer additionally re-applies the mask after each update (Algorithm 1
  line 14, "binary masks are applied only on the updated weights"). Costs the
  *full* dense FLOPs — this is the §Perf baseline.

* ``packed`` — **beyond-paper optimized**: train directly in the folded
  parameterization (packed ``(nb, bi, bo)`` blocks + fixed permutations).
  The loss surface is identical (the masked-dense weight is a bijective
  re-indexing of the packed one; see tests/test_fold.py gradient-equivalence)
  but matmul FLOPs/bytes drop by the compression factor ``c = nb`` and the
  block axis becomes shardable (tensor-parallelism without all-reduce).

* ``dense`` — no compression (the paper's baseline networks).

The heavy math is delegated to :mod:`repro.kernels.ops`, which routes to the
Pallas kernels on TPU and to jnp references elsewhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import fold as fold_lib
from . import permute
from .mask import MaskSpec, mask_dense

Params = Dict[str, Any]

MODES = ("dense", "masked_dense", "packed")


@dataclasses.dataclass(frozen=True)
class MPDLinearSpec:
    """Static config of one (possibly compressed) linear layer."""

    d_in: int
    d_out: int
    mask: Optional[MaskSpec]  # None => plain dense layer
    mode: str = "packed"
    use_bias: bool = True
    # permutation fusion flags (set by the chain builder / fold pass):
    skip_in_perm: bool = False
    skip_out_perm: bool = False

    def __post_init__(self):
        assert self.mode in MODES, self.mode
        if self.mask is not None:
            assert self.mask.d_in == self.d_in and self.mask.d_out == self.d_out

    @property
    def compressed(self) -> bool:
        return self.mask is not None and self.mode != "dense"

    def param_count(self) -> int:
        n = self.d_in * self.d_out
        if self.compressed:
            n //= self.mask.nb
        return n + (self.d_out if self.use_bias else 0)


def _init_scale(d_in: int) -> float:
    return float(1.0 / np.sqrt(d_in))  # python float: weak-typed, no bf16 promotion


def init(key: jax.Array, spec: MPDLinearSpec, dtype=jnp.float32) -> Params:
    """Initialize parameters.

    Packed mode initializes blocks with the *same* per-element scale the
    masked-dense layer would see (fan-in of the dense layer), matching the
    paper's setup where masking happens after standard init.
    """
    scale = _init_scale(spec.d_in)
    p: Params = {}
    if spec.mask is None or spec.mode == "dense":
        p["w"] = jax.random.normal(key, (spec.d_in, spec.d_out), dtype) * scale
    elif spec.mode == "masked_dense":
        w = jax.random.normal(key, (spec.d_in, spec.d_out), dtype) * scale
        p["w"] = w * jnp.asarray(mask_dense(spec.mask, np.float32), dtype)
    else:  # packed
        m = spec.mask
        p["w"] = (
            jax.random.normal(key, (m.nb, m.block_in, m.block_out), dtype) * scale
        )
    if spec.use_bias:
        p["b"] = jnp.zeros((spec.d_out,), dtype)
    return p


def from_dense(spec: MPDLinearSpec, w_dense, b=None) -> Params:
    """Build params from an existing dense weight (compress-then-finetune or
    fold-for-inference flows)."""
    p: Params = {}
    if spec.mask is None or spec.mode == "dense":
        p["w"] = jnp.asarray(w_dense)
    elif spec.mode == "masked_dense":
        p["w"] = jnp.asarray(w_dense) * jnp.asarray(
            mask_dense(spec.mask, np.float32), jnp.asarray(w_dense).dtype
        )
    else:
        p["w"] = fold_lib.fold(spec.mask, w_dense)
    if spec.use_bias:
        p["b"] = jnp.zeros((spec.d_out,), jnp.asarray(w_dense).dtype) if b is None else jnp.asarray(b)
    return p


def to_packed(spec: MPDLinearSpec, params: Params) -> Params:
    """Fold a trained masked-dense layer into packed inference form (Eq. 2)."""
    assert spec.mode == "masked_dense" and spec.mask is not None
    out = {"w": fold_lib.fold(spec.mask, params["w"])}
    if spec.use_bias:
        out["b"] = params["b"]
    return out


def apply(spec: MPDLinearSpec, params: Params, x, *,
          activation: Optional[str] = None, extra_bias=None, precision=None):
    """Forward pass ``y = act(x @ W_eff + b)`` for any mode.

    ``x``: ``(..., d_in)`` -> ``(..., d_out)``. The bias and ``activation``
    (an entry of :data:`repro.kernels.ref.ACTIVATIONS`) are pushed *into*
    the kernel call as a fused epilogue on the compressed modes — one
    dispatch on the Pallas routes — instead of composing as separate XLA
    ops around it. ``extra_bias`` lets callers fold an additional additive
    term into the same epilogue (e.g. Mamba's ``dt_bias``); it combines
    with the layer's own bias when both exist. On the packed mode the
    epilogue takes the bias in packed order (it runs pre-unpack;
    elementwise activations commute with the output permutation), from
    :func:`packed_bias`: the ``bp`` leaf the serving engine makes beside
    ``b`` when it loads its weights, or ``b`` gathered on this call.
    """
    from repro.kernels import ops, ref  # late import: kernels optional at import time

    b = params["b"] if spec.use_bias else None
    if extra_bias is not None:
        b = extra_bias if b is None else b + extra_bias
    if spec.mask is None or spec.mode == "dense":
        y = jnp.dot(x, params["w"], precision=precision)
        if b is not None:
            y = y + b
        y = ref.ACTIVATIONS[activation](y)  # plain dense: XLA fuses this
    elif spec.mode == "masked_dense":
        mask = jnp.asarray(mask_dense(spec.mask, np.float32), params["w"].dtype)
        y = ops.masked_matmul(x, params["w"], mask, b, activation=activation,
                              precision=precision)
    else:  # packed
        m = spec.mask
        xp = fold_lib.pack_inputs(m, x, skip=spec.skip_in_perm)
        bp = packed_bias(spec, params, b)
        from repro.kernels.quant import is_quantized
        if is_quantized(params):
            # quantized deployment artifact (repro.core.export quantize
            # pass): int8 blocks + per-output-channel scales, already in
            # packed order — streamed by the int8 kernel, dequantized
            # in-register. Inference-only (no VJP).
            yp = ops.bdmm_quant(xp, params["w_q"], params["w_scale"], bp,
                                activation=activation, precision=precision)
        else:
            yp = ops.bdmm(xp, params["w"], bp, activation=activation,
                          precision=precision)
        y = fold_lib.unpack_outputs(m, yp, skip=spec.skip_out_perm)
    return y


def packed_bias(spec: MPDLinearSpec, params: Params, b):
    """``b``, a packed linear's bias in natural order, in the kernel's
    packed output order (``b[..., invert(out_perm)]``); ``None`` stays
    ``None``.

    Where ``b`` is the leaf's own ``b`` and the leaf also holds ``bp``, the
    same bias already in that order (:func:`repro.core.export.pack_biases`,
    which the serving engine runs when it loads its weights), ``bp`` is
    returned as it is. Any other bias is gathered on this call: training
    and checkpoint trees hold no ``bp``, and a sum with Mamba's
    ``extra_bias`` is not the leaf's own."""
    if b is None:
        return None
    if "bp" in params and b is params.get("b"):
        return params["bp"]
    return permute.apply(permute.invert(spec.mask.out_perm), b)


def reapply_mask(spec: MPDLinearSpec, params: Params) -> Params:
    """Algorithm 1 line 14 — re-zero off-mask weights after an optimizer step.

    A no-op for packed/dense modes (off-mask weights don't exist there).
    """
    if spec.mode != "masked_dense" or spec.mask is None:
        return params
    mask = jnp.asarray(mask_dense(spec.mask, np.float32), params["w"].dtype)
    out = dict(params)
    out["w"] = params["w"] * mask
    return out


def flops(spec: MPDLinearSpec, tokens: int) -> int:
    """Matmul FLOPs for ``tokens`` rows (2·d_in·d_out, ÷c when packed)."""
    f = 2 * tokens * spec.d_in * spec.d_out
    if spec.compressed and spec.mode == "packed":
        f //= spec.mask.nb
    return f
