"""Plain float32 reference forward of the benchmark's decoder models.

Straight ``jax.numpy`` at ``precision=HIGHEST``: no kernel route, no cache,
no batching, and nothing imported from the program under test. It reads the
benchmark's configuration file (``model`` block) and the weights that
``weights.py`` made, in the program's parameter layout.

MPDCompress (arXiv:1805.12085): every projection ``y = x @ W`` is stored as
packed diagonal blocks ``wp (nb, bi, bo)`` of ``W* = blockdiag(wp)``, with
``W = W*[p_in][:, p_out]`` for fixed random permutations. The reference
unfolds each packed weight into that dense ``W`` and multiplies with it. The
permutations are drawn here by the recipe the paper's masks follow
(``_mask``/``_plan``, one ``numpy`` generator per layer shape, seeded from the
configuration's ``mpd_seed``), so a program that packs with other
permutations disagrees with the reference.

Departures from the published models, as the configuration runs them and
as each ``bench/configs/*.json`` lists them: embeddings are scaled by
``sqrt(d_model)``, the unembedding is its own (packed) matrix, and norms use
``eps = 1e-5``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-5

# lower-precision weight formats a configuration's control can name
FORMATS = (None, "fp8", "int4")


# ---------------------------------------------------------------- MPD masks
def _mask(seed: int, d_in: int, d_out: int, nb: int, in_perm=None,
          out_perm=None):
    rng = np.random.default_rng(np.random.SeedSequence([seed, d_in, d_out,
                                                        nb]))
    if in_perm is None:
        in_perm = rng.permutation(d_in)
    if out_perm is None:
        out_perm = rng.permutation(d_out)
    return nb, np.asarray(in_perm, np.int32), np.asarray(out_perm, np.int32)


def _plan(m: Dict, d_in: int, d_out: int, salt: int):
    """Largest ``nb <= mpd_c`` dividing both sides with blocks of at least
    ``mpd_min_block``; None keeps the projection dense."""
    nb = int(m["mpd_c"])
    while nb > 1:
        if (d_in % nb == 0 and d_out % nb == 0
                and d_in // nb >= m["mpd_min_block"]
                and d_out // nb >= m["mpd_min_block"]):
            return _mask(int(m["mpd_seed"]) * 1_000_003 + salt, d_in, d_out,
                         nb)
        nb -= 1
    return None


def masks(m: Dict) -> Dict[str, Optional[tuple]]:
    """(nb, p_in, p_out) for each projection of the (single-kind) layer and
    the unembedding. With ``mpd_fuse`` (paper Fig. 3) q/k/v share q's input
    permutation, gate shares up's mask, and down's input permutation is
    up's output permutation."""
    d, f, v = m["d_model"], m["d_ff"], m["vocab"]
    hd = d // m["n_heads"]
    dq, dkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    a, b = 1, 100             # per-block salts of the attention and FFN
    out = {"wq": _plan(m, d, dq, a * 4 + 0), "wk": _plan(m, d, dkv, a * 4 + 1),
           "wv": _plan(m, d, dkv, a * 4 + 2), "wo": _plan(m, dq, d, a * 4 + 3),
           "w_up": _plan(m, d, f, b * 3 + 0),
           "w_gate": _plan(m, d, f, b * 3 + 1),
           "w_down": _plan(m, f, d, b * 3 + 2),
           "unembed": _plan(m, d, v, 0)}
    if m.get("mpd_fuse"):
        q = out["wq"]
        for name in ("wk", "wv"):
            k = out[name]
            if q is not None and k is not None and k[0] == q[0]:
                out[name] = (k[0], q[1], k[2])
        up, down = out["w_up"], out["w_down"]
        if up is not None and down is not None and up[0] == down[0]:
            out["w_gate"] = up
            out["w_down"] = (down[0], up[2], down[2])
    return out


# ---------------------------------------------------------- weight formats
def round_e4m3(x):
    """``x`` (within +-448) rounded to float8 e4m3: 3 mantissa bits,
    subnormal steps of 2**-9 below 2**-6. Done in arithmetic because XLA
    may drop a float32 -> float8 -> float32 round trip as excess precision
    (on the TPU it did, and the control read as the reference itself)."""
    _, e = jnp.frexp(jnp.maximum(jnp.abs(x), 2.0 ** -6))
    step = jnp.exp2((e - 4).astype(jnp.float32))
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0)


def _requantize(wp, fmt: Optional[str]):
    """``wp (..., nb, bi, bo)`` f32 through a lower-precision format with a
    scale per (block, output column)."""
    if fmt is None:
        return wp
    amax = jnp.max(jnp.abs(wp), axis=-2, keepdims=True)
    if fmt == "fp8":                  # e4m3, largest finite value 448
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return round_e4m3(wp / s) * s
    s = jnp.where(amax > 0, amax / 7.0, 1.0)      # int4: [-7, 7]
    return jnp.clip(jnp.round(wp / s), -7.0, 7.0) * s


def packed_f32(leaf) -> jax.Array:
    """The packed blocks of one linear as float32: int8 ``w_q * w_scale``
    exactly, or the stored floating weight widened."""
    if "w_q" in leaf:
        return leaf["w_q"].astype(jnp.float32) * leaf["w_scale"][..., None, :]
    return leaf["w"].astype(jnp.float32)


def unfold(wp, mask):
    """Dense ``W (d_in, d_out)`` from packed blocks: ``blockdiag(wp)`` with
    its rows gathered by ``p_in`` and its columns by ``p_out``."""
    if mask is None:
        return wp
    nb, p_in, p_out = mask
    _, bi, bo = wp.shape
    star = jnp.zeros((nb, bi, nb, bo), jnp.float32)
    star = star.at[jnp.arange(nb), :, jnp.arange(nb), :].set(wp)
    star = star.reshape(nb * bi, nb * bo)
    return star[jnp.asarray(p_in)][:, jnp.asarray(p_out)]


# ------------------------------------------------------------------ blocks
def _norm(kind: str, p, x):
    if kind == "none":                      # OLMo: LayerNorm, no affine
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + EPS)
    if kind == "rms":
        y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + EPS)
        return y * p["w"].astype(jnp.float32)
    if kind == "ln":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + EPS)
        return y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
    raise ValueError(kind)


def _rope(x, theta: float):
    """Rotary embedding on the two halves of each head: x (T, H, Dh)."""
    t, _, dh = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(t)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(x, leaf, mask, fmt):
    w = unfold(_requantize(packed_f32(leaf), fmt), mask)
    y = jnp.dot(x, w, precision=HI)
    if "b" in leaf:
        y = y + leaf["b"].astype(jnp.float32)
    return y


@functools.partial(jax.jit, static_argnames=("m_key", "fmt"))
def _layer(x, p, *, m_key, fmt):
    m, mk = _MODELS[m_key]
    t = x.shape[0]
    h_, kh = m["n_heads"], m["n_kv_heads"]
    dh = m["d_model"] // h_
    h = _norm(m["norm"], p.get("norm1", {}), x)
    mix = p["mixer"]
    q = _linear(h, mix["wq"], mk["wq"], fmt).reshape(t, h_, dh)
    k = _linear(h, mix["wk"], mk["wk"], fmt).reshape(t, kh, dh)
    v = _linear(h, mix["wv"], mk["wv"], fmt).reshape(t, kh, dh)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    g = h_ // kh
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(t, kh, g, dh), k,
                   precision=HI) / math.sqrt(dh)
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(t, h_ * dh)
    x = x + _linear(o, mix["wo"], mk["wo"], fmt)
    h2 = _norm(m["norm"], p.get("norm2", {}), x)
    ffn = p["ffn"]
    up = _linear(h2, ffn["w_up"], mk["w_up"], fmt)
    if m["ffn_kind"] == "swiglu":
        up = jax.nn.silu(_linear(h2, ffn["w_gate"], mk["w_gate"], fmt)) * up
    else:
        up = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[m["ffn_kind"]](up)
    return x + _linear(up, ffn["w_down"], mk["w_down"], fmt)


@functools.partial(jax.jit, static_argnames=("m_key", "fmt"))
def _head(x, final_norm, unembed, *, m_key, fmt):
    m, mk = _MODELS[m_key]
    x = _norm(m["norm"], final_norm, x)
    return _linear(x, unembed, mk["unembed"], fmt)


# jit static arguments must hash: the model dict and its masks are
# registered once under a key (the configuration name)
_MODELS: Dict[str, tuple] = {}


def register(name: str, model: Dict) -> str:
    if name not in _MODELS:
        _MODELS[name] = (dict(model), masks(model))
    return name


def forward(m_key: str, params, tokens, fmt: Optional[str] = None):
    """Logits (T, vocab) in float32 for one sequence ``tokens (T,)``, one
    layer at a time. ``fmt`` puts every packed weight through a lower
    precision first (the control)."""
    m, _ = _MODELS[m_key]
    assert fmt in FORMATS, fmt
    table = params["embed"]["table"]
    x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    x = x * math.sqrt(m["d_model"])
    blocks = params["blocks"][0]
    for i in range(m["n_layers"]):
        p = jax.tree.map(lambda a, i=i: a[i], blocks)
        x = _layer(x, p, m_key=m_key, fmt=fmt)
    return _head(x, params.get("final_norm", {}), params["unembed"],
                 m_key=m_key, fmt=fmt)
