"""Packed-order biases made once for serving (``core.export.pack_biases``).

* the pass gives each packed linear's bias ``b`` a copy
  ``bp = b[..., invert(out_perm)]`` beside it, made from ``b`` anew on
  every call, and hands every other leaf through as the same array;
* :func:`repro.core.mpd.packed_bias` makes a leaf with ``bp`` and one with
  ``b`` alone give bit-identical outputs, on every packed route (float32,
  bfloat16, int8, the fused FFN) and with Mamba's ``extra_bias``.

The engine's side is in ``tests/test_serve_paged.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import common
from repro.core import mpd, permute
from repro.core.export import (_iter_packed_leaves, pack_biases,
                               quantize_packed)
from repro.core.mask import make_mask_spec
from repro.kernels import quant
from repro.models import ModelConfig, build
from test_serve_paged import _gathers


def _random_biases(params, seed=42):
    """Per-index random biases: a wrong permutation would pass with the
    zeros ``init`` gives."""
    key = jax.random.PRNGKey(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
        if getattr(path[-1], "key", None) == "b" else x, params)


def _model(arch, **over):
    m = build(common.get_config(arch, smoke=True, **over))
    return m, _random_biases(m.init(jax.random.PRNGKey(0)))


def _leaves_by_id(tree):
    return {id(x) for x in jax.tree.leaves(tree)}


# ------------------------------------------------------------------ the pass

def test_pack_biases_stores_each_bias_in_packed_order():
    m, raw = _model("granite-8b")
    packed, n = pack_biases(m, raw)
    cfg = m.cfg
    assert n == cfg.n_layers * 7          # q, k, v, o, up, gate, down
    seen = 0
    for (parent, key, lin, tag), (pparent, _, _, _) in zip(
            _iter_packed_leaves(m, raw), _iter_packed_leaves(m, packed)):
        leaf, pleaf = parent[key], pparent[key]
        if not lin.spec.use_bias:          # the unembedding
            assert pleaf["w"] is leaf["w"] and set(pleaf) == {"w"}, tag
            continue
        assert set(pleaf) == set(leaf) | {"bp"}, tag
        inv = permute.invert(lin.spec.mask.out_perm)
        b = np.asarray(leaf["b"])
        assert b.ndim == 2 and b.shape[0] == cfg.n_layers   # stacked
        np.testing.assert_array_equal(np.asarray(pleaf["bp"]), b[..., inv])
        assert pleaf["w"] is leaf["w"] and pleaf["b"] is leaf["b"], tag
        seen += 1
    assert seen == 7
    # every leaf but the biases is the input's own array: nothing
    # weight-sized is copied
    kept = _leaves_by_id(raw) & _leaves_by_id(packed)
    assert len(jax.tree.leaves(packed)) - len(kept) == seen


def _bp_leaves(tree):
    return {jax.tree_util.keystr(k): x for k, x in
            jax.tree_util.tree_leaves_with_path(tree) if "'bp'" in
            jax.tree_util.keystr(k)}


def test_pack_biases_is_idempotent_and_follows_b():
    """Run again, the pass gives the same tree; run on a tree whose ``b``
    was edited, it makes ``bp`` from the edit (``b`` is the source)."""
    m, raw = _model("granite-8b")
    once, n1 = pack_biases(m, raw)
    twice, n2 = pack_biases(m, once)
    assert n1 == n2 == m.cfg.n_layers * 7
    assert jax.tree.structure(once) == jax.tree.structure(twice)
    kept = _leaves_by_id(once) & _leaves_by_id(twice)
    assert len(jax.tree.leaves(twice)) - len(kept) == 7     # the bp leaves
    for k, bp in _bp_leaves(twice).items():
        np.testing.assert_array_equal(np.asarray(bp),
                                      np.asarray(_bp_leaves(once)[k]))
    edited = jax.tree_util.tree_map_with_path(
        lambda path, x: -x if getattr(path[-1], "key", None) == "b" else x,
        once)
    again, _ = pack_biases(m, edited)
    for k, bp in _bp_leaves(again).items():
        np.testing.assert_array_equal(np.asarray(bp),
                                      -np.asarray(_bp_leaves(once)[k]))


def test_pack_biases_converts_nothing_without_biases():
    m, raw = _model("olmo-1b")
    assert not m.cfg.use_bias
    packed, n = pack_biases(m, raw)
    assert n == 0
    assert jax.tree.structure(packed) == jax.tree.structure(raw)
    for a, b in zip(jax.tree.leaves(raw), jax.tree.leaves(packed)):
        assert a is b


# ------------------------------------------------------------- the helper

def _linear(dtype, quantized, use_bias):
    mask = make_mask_spec(48, 64, 4, seed=3)
    spec = mpd.MPDLinearSpec(48, 64, mask, use_bias=use_bias)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    p = mpd.init(k1, spec, dtype)
    if use_bias:
        p["b"] = jax.random.normal(k2, (64,), dtype)
    if quantized:
        q, s = quant.quantize_blocks(p.pop("w"))
        p.update(w_q=q, w_scale=s)
    return spec, p


@pytest.mark.parametrize("use_bias,extra", [
    (True, False), (True, True), (False, True)],
    ids=["bias", "bias+extra", "extra-only"])
@pytest.mark.parametrize("dtype,quantized", [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True)],
    ids=["float32", "bfloat16", "int8"])
def test_apply_is_bit_identical_with_a_packed_bias(dtype, quantized,
                                                   use_bias, extra):
    """``bp`` beside ``b`` changes no bit of ``mpd.apply``'s output: the
    same values reach the same epilogue. It takes the bias's gather out of
    the program; with ``extra_bias`` (Mamba's ``dt_bias``) the sum is
    gathered as before."""
    spec, p = _linear(dtype, quantized, use_bias)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 48), dtype)
    eb = (jax.random.normal(jax.random.PRNGKey(8), (64,), dtype)
          if extra else None)
    pk = dict(p)
    if use_bias:
        inv = permute.invert(spec.mask.out_perm)
        pk["bp"] = jnp.take(pk["b"], jnp.asarray(inv), axis=-1)
    apply = lambda q: mpd.apply(spec, q, x, activation="silu", extra_bias=eb)
    np.testing.assert_array_equal(np.asarray(jax.jit(apply)(pk)),
                                  np.asarray(jax.jit(apply)(p)))
    bias_gathers = lambda q: len(list(_gathers(
        jax.make_jaxpr(apply)(q).jaxpr, ndim=1)))
    assert bias_gathers(p) == 1
    assert bias_gathers(pk) == (0 if use_bias and not extra else 1)


@pytest.mark.parametrize("dtype,quantize", [
    ("float32", None), ("bfloat16", None), ("float32", "int8")],
    ids=["float32", "bfloat16", "int8"])
def test_model_logits_unchanged_by_the_pass(dtype, quantize):
    m, raw = _model("granite-8b", dtype=dtype)
    if quantize:
        raw, _ = quantize_packed(m, raw, compute_report=False)
    packed, _ = pack_biases(m, raw)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, m.cfg.vocab)
    logits = jax.jit(m.logits)
    np.testing.assert_array_equal(np.asarray(logits(packed, toks)),
                                  np.asarray(logits(raw, toks)))


@pytest.mark.parametrize("quantize", [None, "int8"], ids=["float32", "int8"])
def test_fused_ffn_after_perm_fusion_unchanged_by_the_pass(quantize):
    """The Fig-3 export (aligned masks, the gate bias re-indexed by
    ``apply_perm_fusion``) runs the one-dispatch fused FFN; the pass made
    with the rewritten ``out_perm`` gives the same logits bit for bit."""
    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab=128, mpd_c=4, mpd_mode="masked_dense",
                      use_bias=True, mpd_fuse=True)
    md = build(cfg)
    params = _random_biases(md.init(jax.random.PRNGKey(0)))
    m, raw = md.to_packed(params, fuse=True, quantize=quantize)
    assert m.block_specs[0]["ffn"].fused_packed()
    packed, n = pack_biases(m, raw)
    assert n == cfg.n_layers * 7
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    logits = jax.jit(m.logits)
    np.testing.assert_array_equal(np.asarray(logits(packed, toks)),
                                  np.asarray(logits(raw, toks)))
