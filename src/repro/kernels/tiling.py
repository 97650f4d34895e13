"""Grid-tiling policy shared by the Pallas kernels.

A TPU block must respect the vreg layout: its last dim is a multiple of
:data:`LANE` (128) and its second-to-last a multiple of the dtype's sublane
count (8 rows for 4-byte, 16 for 2-byte, 32 for 1-byte types), unless the
block spans that whole array dim. The kernels index packed operands as flat
2-D ``(rows, nb * width)`` arrays, so a block never spans the packed dim
and every tile here is aligned.

Awkward dims are never met by shrinking the tile below alignment: the
operand is *padded* to the next tile multiple (zero rows/cols are exact —
they contribute nothing to a matmul and are sliced off the output).
"""

from __future__ import annotations

import numpy as np

LANE = 128


def sublane(dtype) -> int:
    """Row multiple of a ``dtype`` block's second-to-last dim: 8 rows of
    4-byte values fill a vreg, 2-byte types pack 16, 1-byte types 32."""
    return {4: 8, 2: 16, 1: 32}[min(np.dtype(dtype).itemsize, 4)]


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pad_axis(a, axis: int, to: int):
    """Zero-pad one axis of ``a`` up to length ``to`` (no-op when equal)."""
    if a.shape[axis] == to:
        return a
    import jax.numpy as jnp
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, to - a.shape[axis])
    return jnp.pad(a, pad)


def pick_tile(dim: int, want: int, align: int):
    """Choose an ``align``-multiple tile for ``dim`` aiming at ``want``.

    Returns ``(tile, padded_dim)`` with ``padded_dim % tile == 0``. A dim
    that fits in ``want`` is one tile (padded up to ``align``). Otherwise an
    exact divisor of the aligned dim in ``[want/2, want]`` wins; failing
    that, the fewest tiles of at most ``want`` are balanced and the dim is
    padded to their sum, which bounds the padding below one ``align`` per
    tile.
    """
    want = max(want // align * align, align)
    dim_a = round_up(dim, align)
    if dim_a <= want:
        return dim_a, dim_a
    for t in range(want, want // 2 - 1, -align):
        if dim_a % t == 0:
            return t, dim_a
    n = -(-dim // want)
    t = round_up(-(-dim // n), align)
    return t, n * t
