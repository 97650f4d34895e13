"""Ahead-of-time compiles of the serve-path Pallas kernels for a TPU v5e.

No chip is needed: the TPU compiler that ships with jax compiles against a
*described* ``v5e:2x2`` topology and refuses what the chip would refuse
(block shapes off the (sublane, 128-lane) tiling, VMEM overuse), which
interpret mode cannot show. Shapes are OLMo-1B packed at ``mpd_c=8``:
``nb=8`` diagonal blocks of 256x256 (attention), 256x1024 / 1024x256 (FFN),
256x6288 (unembed, ``50304/8`` lanes — not a 128 multiple), 16 heads of
``Dh=128``, KV pages of 16 tokens.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bdmm as bdmm_kernel
from repro.kernels import fused_ffn as ffn_kernel
from repro.kernels import paged_attention as pa_kernel
from repro.kernels import paged_prefill as pp_kernel

NB = 8
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [8, 64], ids=["decode", "general"])
@pytest.mark.parametrize("wdtype", [BF16, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize("bi,bo", [(256, 256), (1024, 256), (256, 6288)],
                         ids=["attn", "ffn_down", "unembed"])
def test_bdmm_compiles_for_v5e(one_chip, m, wdtype, bi, bo):
    """m=8 takes the weight-stationary decode grid, m=64 (one prefill
    chunk) the general K-streaming grid."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x, w, b = s((m, NB * bi), BF16), s((NB, bi, bo), wdtype), s((NB * bo,), F32)
    if wdtype == I8:
        _compile(lambda x, w, b, sc: bdmm_kernel.bdmm(x, w, b, sc),
                 x, w, b, s((NB, bo), F32))
    else:
        _compile(lambda x, w, b: bdmm_kernel.bdmm(x, w, b), x, w, b)


@pytest.mark.parametrize("m", [8, 64], ids=["decode", "prefill"])
def test_fused_ffn_int8_compiles_for_v5e(one_chip, m):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bi, f, bo = 256, 1024, 256
    _compile(
        lambda x, u, g, d, su, sg, sd: ffn_kernel.fused_ffn(
            x, u, d, w_gate=g, s_up=su, s_gate=sg, s_down=sd),
        s((m, NB * bi), BF16), s((NB, bi, f), I8), s((NB, bi, f), I8),
        s((NB, f, bo), I8), s((NB, f), F32), s((NB, f), F32),
        s((NB, bo), F32))


def test_paged_attention_compiles_for_v5e(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    slots, heads, dh, ps, width, pages = 8, 16, 128, 16, 32, 257
    pool = s((pages, ps, heads, dh), BF16)
    _compile(pa_kernel.paged_attention, s((slots, heads, dh), BF16), pool,
             pool, s((slots, width), I32), s((slots,), I32))


def test_paged_prefill_attention_compiles_for_v5e(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    chunk, heads, dh, ps, width, pages = 64, 16, 128, 16, 32, 257
    pool = s((pages, ps, heads, dh), BF16)
    _compile(pp_kernel.paged_prefill_attention, s((chunk, heads, dh), BF16),
             pool, pool, s((width,), I32), s((), I32), s((), I32))
