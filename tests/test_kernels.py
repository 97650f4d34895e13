"""Per-kernel allclose tests: Pallas (interpret mode) vs pure-jnp oracle,
sweeping shapes and dtypes, plus custom-VJP correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bdmm as bdmm_kernel
from repro.kernels import masked_matmul as mm_kernel
from repro.kernels import ops, ref


def _relerr(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


BDMM_SHAPES = [
    # (m, nb, bi, bo) — aligned, unaligned, tall, wide, tiny
    (128, 4, 128, 128),
    (64, 8, 96, 80),
    (17, 3, 33, 65),
    (256, 2, 512, 64),
    (8, 16, 8, 8),
    (1, 4, 256, 256),  # decode-like single row
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", BDMM_SHAPES)
def test_bdmm_vs_oracle(shape, dtype):
    m, nb, bi, bo = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 3)
    x = jax.random.normal(k1, (m, nb * bi), dtype)
    w = jax.random.normal(k2, (nb, bi, bo), dtype)
    b = jax.random.normal(k3, (nb * bo,), dtype)
    y = bdmm_kernel.bdmm(x, w, b, activation="relu", interpret=True)
    yr = ref.bdmm_ref(
        x.astype(jnp.float32), w.astype(jnp.float32), b.astype(jnp.float32),
        activation="relu",
    )
    assert y.shape == yr.shape
    assert _relerr(y, yr) < _tol(dtype)


def test_bdmm_leading_dims():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 24))
    y = bdmm_kernel.bdmm(x, w, interpret=True)
    assert y.shape == (2, 3, 4, 96)
    assert _relerr(y, ref.bdmm_ref(x, w)) < 2e-5


MM_SHAPES = [(64, 128, 128), (96, 160, 224), (17, 48, 96), (256, 512, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", MM_SHAPES)
def test_masked_matmul_vs_oracle(shape, dtype):
    m, di, do = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 3)
    x = jax.random.normal(k1, (m, di), dtype)
    w = jax.random.normal(k2, (di, do), dtype)
    mask = (jax.random.uniform(k3, (di, do)) < 0.125).astype(jnp.float32)
    y = mm_kernel.masked_matmul(x, w, mask, interpret=True)
    yr = ref.masked_matmul_ref(x.astype(jnp.float32), w.astype(jnp.float32), mask)
    assert _relerr(y, yr) < _tol(dtype)


@pytest.mark.parametrize("shape", MM_SHAPES[:2])
def test_masked_matmul_transpose_rhs(shape):
    m, di, do = shape
    g = jax.random.normal(jax.random.PRNGKey(0), (m, do))
    w = jax.random.normal(jax.random.PRNGKey(1), (di, do))
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (di, do)) < 0.25).astype(jnp.float32)
    dx = mm_kernel.masked_matmul(g, w, mask, transpose_rhs=True, interpret=True)
    dxr = g @ (w * mask).T
    assert _relerr(dx, dxr) < 2e-5


@pytest.mark.parametrize("shape", MM_SHAPES[:3])
def test_sddmm_masked(shape):
    m, di, do = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (m, di))
    g = jax.random.normal(jax.random.PRNGKey(1), (m, do))
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (di, do)) < 0.1).astype(jnp.float32)
    dw = mm_kernel.sddmm_masked(x, g, mask, interpret=True)
    dwr = ref.matmul_masked_grad_ref(x, g, mask)
    assert _relerr(dw, dwr) < 2e-5
    # the SDDMM invariant: output support == mask support, exactly
    assert np.all(np.asarray(dw) * (1 - np.asarray(mask)) == 0)


class TestTilePadding:
    """Awkward (prime/odd) dims must pad to the next tile multiple instead
    of silently degrading the tile search to size 1."""

    def test_pick_tile_pads_prime_dim(self):
        from repro.kernels.tiling import pick_tile
        tile, padded = pick_tile(131, 128, 8)
        assert tile % 8 == 0 and padded % tile == 0 and padded >= 131
        assert (tile, padded) == (72, 144)    # 2 balanced tiles, not 128+128

    def test_pick_tile_exact_divisor_kept(self):
        from repro.kernels.tiling import pick_tile
        assert pick_tile(192, 128, 8) == (96, 192)  # aligned divisor wins
        assert pick_tile(128, 128, 8) == (128, 128)
        assert pick_tile(6400, 512, 128) == (256, 6400)

    @pytest.mark.parametrize("dim,want,dtype,rows,tile", [
        # OLMo-1B packed unembed: bo = 50304/8 = 6288 lanes
        (6288, 128, jnp.bfloat16, False, 128),
        (6288, 512, jnp.bfloat16, False, 256),
        # dtype sublane floors on the row axis (decode m = a few slots)
        (3, 128, jnp.float32, True, 8),
        (3, 128, jnp.bfloat16, True, 16),
        (3, 128, jnp.int8, True, 32),
        (40, 128, jnp.int8, True, 64),
    ])
    def test_tile_alignment(self, dim, want, dtype, rows, tile):
        """Lane tiles are 128-multiples and row tiles dtype-sublane
        multiples (8/16/32 rows for 4/2/1-byte types): a tile the TPU
        lowering would refuse is never produced; the dim pads instead."""
        from repro.kernels.tiling import LANE, pick_tile, sublane
        align = sublane(dtype) if rows else LANE
        t, padded = pick_tile(dim, want, align)
        assert (t, t % align, padded % t) == (tile, 0, 0)
        assert dim <= padded < dim + (padded // t) * align

    def test_bdmm_prime_m_matches_oracle(self):
        # m=131 used to degrade to a 131-step tile-1 grid
        x = jax.random.normal(jax.random.PRNGKey(0), (131, 2 * 13))
        w = jax.random.normal(jax.random.PRNGKey(1), (2, 13, 29))
        y = bdmm_kernel.bdmm(x, w, interpret=True, small_m=False)
        assert _relerr(y, ref.bdmm_ref(x, w)) < 2e-5

    def test_fused_ffn_prime_dims_match_oracle(self):
        from repro.kernels import fused_ffn as ffn_kernel
        m, nb, bi, f, bo = 37, 3, 16, 46, 16
        k = jax.random.split(jax.random.PRNGKey(2), 5)
        x = jax.random.normal(k[0], (m, nb * bi))
        wu = jax.random.normal(k[1], (nb, bi, f))
        wg = jax.random.normal(k[2], (nb, bi, f))
        wd = jax.random.normal(k[3], (nb, f, bo))
        bu = jax.random.normal(k[4], (nb * f,))
        y = ffn_kernel.fused_ffn(x, wu, wd, wg, b_up=bu, interpret=True)
        yr = ref.fused_ffn_ref(x, wu, wd, wg, b_up=bu)
        assert _relerr(y, yr) < 2e-5


class TestCustomVJP:
    """ops.* wrappers must differentiate identically to the jnp reference."""

    def test_bdmm_grads(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (32, 4 * 24))
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 24, 16))

        def f_ops(x, w):
            return jnp.sum(ops.bdmm(x, w, activation="gelu") ** 2)

        def f_ref(x, w):
            return jnp.sum(ref.bdmm_ref(x, w, activation="gelu") ** 2)

        gx1, gw1 = jax.grad(f_ops, (0, 1))(x, w)
        gx2, gw2 = jax.grad(f_ref, (0, 1))(x, w)
        assert _relerr(gx1, gx2) < 1e-5
        assert _relerr(gw1, gw2) < 1e-5

    def test_masked_matmul_grads(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (32, 48))
        w = jax.random.normal(jax.random.PRNGKey(1), (48, 40))
        mask = (jax.random.uniform(jax.random.PRNGKey(2), (48, 40)) < 0.25).astype(jnp.float32)

        def f_ops(x, w):
            return jnp.sum(ops.masked_matmul(x, w, mask) ** 2)

        def f_ref(x, w):
            return jnp.sum(ref.masked_matmul_ref(x, w, mask) ** 2)

        gx1, gw1 = jax.grad(f_ops, (0, 1))(x, w)
        gx2, gw2 = jax.grad(f_ref, (0, 1))(x, w)
        assert _relerr(gx1, gx2) < 1e-5
        assert _relerr(gw1, gw2) < 1e-5
        assert np.all(np.asarray(gw1) * (1 - np.asarray(mask)) == 0)

    def test_interpret_backend_end_to_end(self):
        """Run the differentiable wrappers through the Pallas interpret path."""
        old = ops.get_backend()
        ops.set_backend("interpret")
        try:
            x = jax.random.normal(jax.random.PRNGKey(0), (16, 2 * 16))
            w = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
            g1 = jax.grad(lambda w: jnp.sum(ops.bdmm(x, w) ** 2))(w)
        finally:
            ops.set_backend(old)
        g2 = jax.grad(lambda w: jnp.sum(ref.bdmm_ref(x, w) ** 2))(w)
        assert _relerr(g1, g2) < 1e-5
