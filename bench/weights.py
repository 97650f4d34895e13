"""Weights for a benchmark run, made by the benchmark from ``--seed``.

The program under test supplies only the *layout* of its parameter tree
(``jax.eval_shape`` of its own init, and of its int8 quantize pass where the
configuration serves int8). Every value is drawn here, on the device, in one
jitted call and in the type it is served in, so the plain reference
(``reference.py``) reads weights that the program did not make.

Rules, by leaf:

* ``embed/table`` (vocab, d): normal x 0.02, the model dtype;
* a packed linear ``w`` (..., nb, bi, bo): normal / sqrt(nb*bi), the dense
  layer's fan-in, in the model dtype;
* an int8 packed linear ``{w_q, w_scale}``: the same normal draw in float32,
  quantized symmetrically per (block, output column): ``scale = amax/127``,
  ``w_q = round(w/scale)``. The served weights are then exactly
  ``w_q * scale``, which is what the reference multiplies with;
* a norm weight ``w``: 1 + 0.1 x normal (float32), so the scale is exercised;
* a bias ``b``: 0.02 x normal.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QMAX = 127


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed the key and
    the rest are folded in, so seeds past 2**32 stay distinct."""
    s = int(seed) & ((1 << 64) - 1)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def _path_names(path):
    return [getattr(p, "key", getattr(p, "idx", None)) for p in path]


def _leaf_value(key, names, sds):
    last = names[-1]
    parent = names[-2] if len(names) > 1 else None
    shape, dtype = sds.shape, sds.dtype
    if last == "table":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
    if last == "w" and len(shape) >= 3 and parent not in ("norm1", "norm2",
                                                          "final_norm"):
        fan_in = shape[-3] * shape[-2]
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype)
    if last == "w":                                   # a norm scale
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if last == "b":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    raise ValueError(f"no rule for parameter {'/'.join(map(str, names))} "
                     f"{shape} {dtype}")


def quantize_columns(w):
    """Symmetric int8 per (block, output column) of ``w (..., nb, bi, bo)``:
    returns ``(q int8, scale f32 (..., nb, bo))``."""
    amax = jnp.max(jnp.abs(w), axis=-2)
    scale = jnp.where(amax > 0, amax / QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale[..., None, :]), -QMAX, QMAX)
    return q.astype(jnp.int8), scale


def make_params(layout, seed: int):
    """Fill the parameter ``layout`` (a tree of ShapeDtypeStructs) from
    ``seed`` in one jitted call; returns device arrays in the same tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)
    entries = [(_path_names(p), s) for p, s in flat]

    def build(key):
        leaves, scales = [], {}
        for i, (names, sds) in enumerate(entries):
            k = jax.random.fold_in(key, i)
            if names[-1] == "w_q":
                fan_in = sds.shape[-3] * sds.shape[-2]
                w = jax.random.normal(k, sds.shape, jnp.float32) / math.sqrt(
                    fan_in)
                q, scales[tuple(names[:-1])] = quantize_columns(w)
                leaves.append(q)
            elif names[-1] == "w_scale":
                leaves.append(None)           # its w_q sibling supplies it
            else:
                leaves.append(_leaf_value(k, names, sds))
        return [scales[tuple(n[:-1])] if n[-1] == "w_scale" else v
                for (n, _), v in zip(entries, leaves)]

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
