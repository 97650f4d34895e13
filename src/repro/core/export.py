"""Whole-model fold/export pass: masked-dense training → packed deployment.

The paper's pipeline (Figs 2-3) is *train* with binary masks over dense
weights (Algorithm 1) and *serve* the folded block-diagonal form (Eq. 2).
:func:`fold_model` performs that conversion for an entire model in one
call:

1. build the packed twin of a ``masked_dense`` model (same config, same
   deterministic masks — only the parameterization changes),
2. fold every claimed linear's trained weight into packed blocks —
   asserting :func:`repro.core.fold.fold_residual` ≈ 0 first, so a
   checkpoint that was trained without the mask projection fails loudly
   instead of silently dropping weight mass,
3. optionally apply the paper's Fig-3 permutation-cancellation rewrite
   *post hoc* (:func:`apply_perm_fusion`): consecutive FFN projections get
   their boundary gathers merged via
   :func:`repro.core.fold.inter_layer_perm`, so the ``d_ff``-sized hidden
   activations flow in block order.  When the training run already used
   ``mpd_fuse`` (aligned masks), every merged gather is the identity and
   the FFN collapses onto the one-dispatch fused kernel
   (:func:`repro.kernels.ops.fused_ffn`); for independently-drawn masks
   the rewrite still replaces three inner gathers with at most two.

The rewrite is pure spec surgery: packed weights are always folded with the
*trained* masks; only the runtime permutations (and, when a rewritten gate
carries a bias, that bias vector) change. It is deterministic given the
config, so a reloaded checkpoint re-derives it (see
``repro.checkpoint.load_packed``).

4. optionally quantize the packed blocks (:func:`quantize_packed`):
   symmetric per-output-channel int8 (or int4-storage) with scales computed
   at fold time and round-trip error recorded — the paper's "pruning and
   quantization" combined pipeline, enabled by the block structure (dense,
   aligned blocks make per-``(block, channel)`` scales natural).

5. for serving, keep each packed bias in packed order too (:func:`pack_biases`):
   the serving engine does it when it loads its weights, so no program
   gathers a bias at run time; checkpoints keep ``b`` alone, in natural
   order.

Model structure is walked through :meth:`repro.models.Model._block_linears`
(late import — core stays importable without the model zoo).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from . import fold as fold_lib
from . import permute
from .mask import MaskSpec, mask_dense


class FoldResidualError(ValueError):
    """A claimed linear carries weight mass off-mask — the checkpoint was
    not trained with the masked-dense projection (Algorithm 1 line 14)."""


def _stacked_residual(mask_spec: MaskSpec, w: np.ndarray) -> float:
    """fold_residual over a weight stacked on arbitrary leading axes."""
    m = mask_dense(mask_spec, np.float32)
    w = np.asarray(w, np.float32)
    total = float(np.abs(w).sum()) + 1e-30
    return float(np.abs(w * (1.0 - m)).sum()) / total


def _fold_stacked(mask_spec: MaskSpec, w, check: bool, atol: float, path: str):
    """Fold a weight with any number of stacked leading axes (periods,
    experts, ...) into packed blocks."""
    if check:
        res = _stacked_residual(mask_spec, w)
        if res > atol:
            raise FoldResidualError(
                f"{path}: fold residual {res:.3e} > {atol:.1e} — off-mask "
                "weight mass present; was this trained in masked_dense mode "
                "with the mask projection enabled?")
    fn = lambda x: fold_lib.fold(mask_spec, x)
    for _ in range(np.ndim(w) - 2):
        fn = jax.vmap(fn)
    return fn(w)


def _get(node, path):
    for k in path:
        node = node[k]
    return node


def _set(node, path, value):
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value


def _copy_tree(tree):
    """Structural (container) copy; leaves shared."""
    if isinstance(tree, dict):
        return {k: _copy_tree(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_copy_tree(v) for v in tree]
    return tree


def fold_model(model, params, *, fuse: bool = False, check_residual: bool = True,
               atol: float = 1e-6,
               quantize: Optional[str] = None) -> Tuple[Any, Dict[str, Any]]:
    """Fold a trained ``masked_dense`` model into its packed inference twin.

    Returns ``(packed_model, packed_params)``. ``fuse=True`` additionally
    applies the Fig-3 permutation-cancellation rewrite
    (:func:`apply_perm_fusion`). ``check_residual`` asserts every folded
    weight carries zero off-mask mass (requires concrete — not traced —
    params). ``quantize`` (``"int8"``/``"int4"``) additionally runs
    :func:`quantize_packed` over the folded blocks — scales computed at
    fold time, round-trip error recorded on ``packed_model.quant_report``.
    """
    from repro.models import build

    cfg = model.cfg
    if cfg.mpd_mode != "masked_dense":
        raise ValueError(
            f"fold_model expects a masked_dense model, got mpd_mode="
            f"{cfg.mpd_mode!r} (packed models are already in inference form)")
    cfg_pk = dataclasses.replace(cfg, mpd_mode="packed")
    model_pk = build(cfg_pk)

    out = _copy_tree(params)
    n_folded = 0
    for bi_, (spec_md, spec_pk, pstack) in enumerate(
            zip(model.block_specs, model_pk.block_specs, out["blocks"])):
        for path, lin_pk in model_pk._block_linears(spec_pk):
            if lin_pk.spec.mode != "packed" or lin_pk.spec.mask is None:
                continue
            leaf = _get(pstack, path)
            tag = f"blocks[{bi_}]/" + "/".join(path)
            _set(pstack, path, dict(
                leaf, w=_fold_stacked(lin_pk.spec.mask, leaf["w"],
                                      check_residual, atol, tag)))
            n_folded += 1
        # MoE experts: one shared mask per layer, weights stacked
        # (periods, experts, d_in, d_out)
        ffn_pk = spec_pk["ffn"]
        if ffn_pk is not None and hasattr(ffn_pk, "router"):
            if ffn_pk.mode == "packed" and ffn_pk.mask_up is not None:
                for wk, msk in (("w_up", ffn_pk.mask_up),
                                ("w_gate", ffn_pk.mask_up),
                                ("w_down", ffn_pk.mask_down)):
                    if msk is None:
                        continue
                    tag = f"blocks[{bi_}]/ffn/{wk}"
                    pstack["ffn"][wk] = _fold_stacked(
                        msk, pstack["ffn"][wk], check_residual, atol, tag)
                    n_folded += 1
            shared = getattr(ffn_pk, "shared", None)
            if shared is not None:
                for wk in ("w_up", "w_gate", "w_down"):
                    lin = getattr(shared, wk, None)
                    if (lin is None or lin.spec.mode != "packed"
                            or lin.spec.mask is None):
                        continue
                    leaf = pstack["ffn"]["shared"][wk]
                    tag = f"blocks[{bi_}]/ffn/shared/{wk}"
                    pstack["ffn"]["shared"][wk] = dict(
                        leaf, w=_fold_stacked(lin.spec.mask, leaf["w"],
                                              check_residual, atol, tag))
                    n_folded += 1
    un = model_pk.unembed
    if un.spec.mode == "packed" and un.spec.mask is not None:
        out["unembed"] = dict(
            out["unembed"], w=_fold_stacked(un.spec.mask, out["unembed"]["w"],
                                            check_residual, atol, "unembed"))
        n_folded += 1
    if n_folded == 0:
        raise ValueError("fold_model: no compressed linears found "
                         f"(mpd_c={cfg.mpd_c}) — nothing to fold")
    if fuse:
        out = apply_perm_fusion(model_pk, out)
    if quantize is not None:
        from repro.kernels.quant import BITS
        out, report = quantize_packed(model_pk, out, bits=BITS[quantize])
        model_pk.quant_report = report
    return model_pk, out


# --------------------------------------------------------------------------
# post-fold quantization (the paper's "pruning and quantization together")
# --------------------------------------------------------------------------

def _iter_packed_leaves(model_pk, params):
    """Yield ``(parent, key, lin, tag)`` for every dict-leaf packed linear
    (mixer projections, FFN, MoE shared expert, unembed) so passes can
    rewrite ``parent[key]`` in place. MoE *routed* expert stacks are raw
    arrays (not ``{"w": ...}`` leaves) and stay fp — the routed matmul is
    gather-bound per token, not weight-stream-bound like decode."""
    for bi_, (spec, pstack) in enumerate(zip(model_pk.block_specs,
                                             params["blocks"])):
        for path, lin in model_pk._block_linears(spec):
            if lin.spec.mode != "packed" or lin.spec.mask is None:
                continue
            node = pstack
            for k in path[:-1]:
                node = node[k]
            yield node, path[-1], lin, f"blocks[{bi_}]/" + "/".join(path)
        ffn = spec["ffn"]
        shared = getattr(ffn, "shared", None) if ffn is not None else None
        if shared is not None:
            for wk in ("w_up", "w_gate", "w_down"):
                lin = getattr(shared, wk, None)
                if (lin is None or lin.spec.mode != "packed"
                        or lin.spec.mask is None):
                    continue
                yield (pstack["ffn"]["shared"], wk, lin,
                       f"blocks[{bi_}]/ffn/shared/{wk}")
    un = model_pk.unembed
    if un.spec.mode == "packed" and un.spec.mask is not None:
        yield params, "unembed", un, "unembed"


def quantize_packed(model_pk, params, *, bits: int = 8,
                    compute_report: bool = True):
    """Quantize every packed linear of a folded model to int-``bits``.

    Each ``{"w": (..., nb, bi, bo)}`` leaf becomes ``{"w_q": int8,
    "w_scale": (..., nb, bo)}`` (symmetric per-output-channel,
    :func:`repro.kernels.quant.quantize_blocks`); biases stay fp. Returns
    ``(params, report)`` — the report carries per-layer round-trip error
    (``compute_report`` requires concrete params; pass ``False`` under
    tracing, e.g. for ``jax.eval_shape`` restore templates).
    """
    from repro.kernels import quant as quant_lib

    out = _copy_tree(params)
    report: Optional[Dict[str, Any]] = (
        {"bits": bits, "layers": {}} if compute_report else None)
    n_q = 0
    for parent, key, lin, tag in _iter_packed_leaves(model_pk, out):
        leaf = parent[key]
        if "w" not in leaf:
            continue  # already quantized
        q, s = quant_lib.quantize_blocks(leaf["w"], bits=bits)
        new = {k: v for k, v in leaf.items() if k != "w"}
        new["w_q"], new["w_scale"] = q, s
        parent[key] = new
        n_q += 1
        if compute_report:
            report["layers"][tag] = quant_lib.quant_error(leaf["w"], q, s)
    if n_q == 0:
        raise ValueError("quantize_packed: no packed linears found "
                         "(is this a folded/packed model?)")
    if compute_report:
        rms = [l["rel_rms"] for l in report["layers"].values()]
        report["n_layers"] = n_q
        report["max_rel_rms"] = max(rms)
        report["mean_rel_rms"] = float(np.mean(rms))
    return out, report


def dequantize_packed(model_pk, params):
    """Inverse of :func:`quantize_packed` (up to rounding): every
    ``{"w_q", "w_scale"}`` leaf becomes an fp ``{"w"}`` leaf again, so the
    quantized artifact can run through the fp kernels — the reference point
    for drift/equivalence checks."""
    from repro.kernels import quant as quant_lib

    out = _copy_tree(params)
    for parent, key, _lin, _tag in _iter_packed_leaves(model_pk, out):
        leaf = parent[key]
        if "w_q" in leaf:
            new = {k: v for k, v in leaf.items()
                   if k not in ("w_q", "w_scale")}
            new["w"] = quant_lib.dequantize_blocks(leaf["w_q"],
                                                   leaf["w_scale"])
            parent[key] = new
    return out


def map_quantized_leaves(model_pk, params, fn):
    """Apply ``fn(w_q, lin) -> new_w_q`` to every quantized leaf (int4
    nibble pack/unpack for checkpoint storage rides through here)."""
    out = _copy_tree(params)
    for parent, key, lin, _tag in _iter_packed_leaves(model_pk, out):
        leaf = parent[key]
        if "w_q" in leaf:
            parent[key] = dict(leaf, w_q=fn(leaf["w_q"], lin))
    return out


def pack_biases(model_pk, params) -> Tuple[Dict[str, Any], int]:
    """Give every packed linear's bias a copy in the kernel's packed output
    order, for serving.

    Each leaf holding ``b`` comes back holding ``bp = b[..., invert(out_perm)]``
    beside it (stacked leading axes kept), made with the spec's current
    ``out_perm``, so leaves :func:`apply_perm_fusion` rewrote come out right.
    :func:`repro.core.mpd.packed_bias` then hands ``bp`` to the epilogue
    as it is, where a leaf with ``b`` alone is gathered on every call.
    ``b`` stays the source: ``bp`` is made from it anew on every call of
    the pass, so a tree whose ``b`` was edited serves the edit. All the
    gathers run as one jitted call; every other leaf of the result is the
    input's own array.

    Returns ``(params, n)``, ``n`` the number of per-layer biases the
    result holds in packed order (a stacked leaf counts once per layer).
    Training and checkpoints keep ``b`` alone, in natural order.
    """
    out = _copy_tree(params)
    todo, n = [], 0
    for parent, key, lin, _tag in _iter_packed_leaves(model_pk, out):
        if lin.spec.use_bias:
            todo.append((parent, key, permute.invert(lin.spec.mask.out_perm)))
            n += int(np.prod(np.shape(parent[key]["b"])[:-1]))
    if todo:
        gather = jax.jit(lambda bs: [permute.apply(inv, b) for b, (_, _, inv)
                                     in zip(bs, todo)])
        packed = gather([parent[key]["b"] for parent, key, _ in todo])
        for (parent, key, _), bp in zip(todo, packed):
            parent[key] = dict(parent[key], bp=bp)
    return out, n


def apply_perm_fusion(model_pk, params: Optional[Dict[str, Any]] = None):
    """Fig-3 permutation-cancellation rewrite, applied post hoc to a packed
    model (mutates ``model_pk.block_specs`` in place; returns ``params``).

    For every FFN whose up/down projections are packed with equal block
    count, the up (and gate) outputs are left in packed order and down's
    input gather becomes the single *merged* permutation
    ``inter_layer_perm(up, down)`` — identity (skipped entirely, enabling
    the one-dispatch fused kernel) when the masks were built aligned
    (``mpd_fuse`` training), a lone gather otherwise. Weights are
    untouched; a rewritten gate's bias vector (if any) is re-indexed into
    up-packed output order so the elementwise product stays aligned —
    that's the only params change, and it is skipped when ``params`` is
    ``None`` (checkpoint reload path, where the stored bias is already
    rewritten).
    """
    for bi_, spec in enumerate(model_pk.block_specs):
        ffn = spec["ffn"]
        if ffn is None or hasattr(ffn, "router") or ffn.w_up is None:
            continue
        up, gate, down = ffn.w_up, ffn.w_gate, ffn.w_down
        su, sd = up.spec, down.spec
        if not (su.mode == "packed" and sd.mode == "packed"
                and su.mask is not None and sd.mask is not None
                and su.mask.nb == sd.mask.nb):
            continue
        if su.skip_out_perm and sd.skip_in_perm:
            continue  # already fused at build time

        g = fold_lib.inter_layer_perm(su.mask, sd.mask)       # (d_ff,)
        new_down_mask = dataclasses.replace(sd.mask,
                                            in_perm=permute.invert(g))
        new_down = dataclasses.replace(
            down, spec=dataclasses.replace(
                sd, mask=new_down_mask,
                skip_in_perm=bool(permute.is_identity(g))))
        new_up = dataclasses.replace(
            up, spec=dataclasses.replace(su, skip_out_perm=True))
        new_gate = gate
        if gate is not None:
            sg = gate.spec
            # gate output must land in UP-packed order for the elementwise
            # product: merge unpack(gate) ∘ pack(up-order) into one gather
            r = permute.compose(permute.invert(su.mask.out_perm),
                                sg.mask.out_perm)
            new_gate_mask = dataclasses.replace(sg.mask, out_perm=r)
            new_gate = dataclasses.replace(
                gate, spec=dataclasses.replace(
                    sg, mask=new_gate_mask,
                    skip_out_perm=bool(permute.is_identity(r))))
            if sg.use_bias and params is not None:
                # stored gate bias must follow the rewritten output order
                b = _get(params["blocks"][bi_], ("ffn", "w_gate"))["b"]
                q = permute.invert(su.mask.out_perm)
                _set(params["blocks"][bi_], ("ffn", "w_gate"),
                     dict(_get(params["blocks"][bi_], ("ffn", "w_gate")),
                          b=permute.apply(q, b)))
        spec["ffn"] = dataclasses.replace(ffn, w_up=new_up, w_gate=new_gate,
                                          w_down=new_down)
    return params
