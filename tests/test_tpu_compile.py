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
import re

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


# --------------------------------------------------- the layer scan's pools
#
# The paged decode and chunk programs carry each attention block's stacked
# page pool through the layer scan whole and update it in place. In the
# compiled program that leaves one copy of each pool at entry (the input
# pool is not donated) and none per layer: no slice of one layer's pool out
# of the stack, no write of it back, and inside the loop no pool-sized
# result but the in-place scatter and views of the pool. The pool is larger
# than the chip's VMEM (3 x 768 pages of 64 KiB), as a served one is, so
# the compiler keeps it in HBM as it would in service.

N_LAYERS, N_PAGES = 3, 768
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(.*?)\b([a-z][a-z0-9-]*)\(")
CALLED = re.compile(r"\b(?:calls|to_apply|body|condition)=%([\w.-]+)")
# ops that may give a pool-sized result inside the loop: views of the carried
# pool, the scatter that updates it in place, and the fusion around that
# scatter (whose own ops are checked as part of the loop)
IN_PLACE = {"parameter", "get-tuple-element", "tuple", "bitcast", "scatter",
            "fusion"}


def _computations(text):
    """``{computation: [(opcode, result shape, line)]}`` of compiled HLO
    text, and the computations each one calls."""
    comps, calls, name = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.-]+)\s.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name], calls[name] = [], set()
            continue
        m = INSTR.match(line)
        if name is None or m is None:
            continue
        comps[name].append((m.group(3), m.group(2), line))
        calls[name] |= set(CALLED.findall(line))
    return comps, calls


def _loop_computations(comps, calls):
    """Every computation a ``while`` body runs, fusions included."""
    todo = [re.search(r"\bbody=%([\w.-]+)", line).group(1)
            for ins in comps.values() for op, _, line in ins if op == "while"]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += calls.get(c, ())
    return seen


# Granite-8B-Code's cell: bf16 weights unfused, a bias on every projection,
# 32 query heads over 8 KV heads, and the cell's pool of 16 slots x 64
# pages plus the null page. Its pages are half OLMo's (8 KV heads), so it
# takes four layers for the pool to outgrow VMEM (4 x 1025 pages of 32
# KiB); at three the compiler stages one pool through VMEM, which a served
# 36-layer pool never is.
GRANITE_LAYERS, GRANITE_PAGES = 4, 1025


def _paged_program(program, one_chip, arch="olmo-1b"):
    """The compiled paged decode step or final prefill chunk on the Pallas
    route, bf16 K/V: a 3-layer OLMo-1B packed c=8 with the Fig-3 fusion
    and int8 weights, or a 4-layer Granite-8B-Code packed c=8 in bf16 with
    its biases, also in packed order as the serving engine holds them.
    Returns the HLO text, the config, the pool's page count and the
    compiled program's memory analysis."""
    from repro.configs.common import get_config
    from repro.core.export import pack_biases, quantize_packed
    from repro.kernels import ops
    from repro.models import build

    if arch == "olmo-1b":
        cfg = get_config(arch, n_layers=N_LAYERS, dtype="bfloat16",
                         mpd_c=8, mpd_fuse=True)
        model = build(cfg)
        params = jax.eval_shape(
            lambda: quantize_packed(model, model.init(jax.random.PRNGKey(0)),
                                    bits=8, compute_report=False)[0])
        slots, pages, width = 8, N_PAGES, 32
    else:
        cfg = get_config(arch, n_layers=GRANITE_LAYERS)
        assert cfg.use_bias and cfg.n_heads == 4 * cfg.n_kv_heads
        model = build(cfg)
        params = jax.eval_shape(
            lambda: pack_biases(model, model.init(jax.random.PRNGKey(0)))[0])
        slots, pages, width = 16, GRANITE_PAGES, 64
    ps, chunk = 16, 256
    caches = jax.eval_shape(
        lambda: model.init_paged_caches(slots, pages, ps, BF16))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    s = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    saved = ops._BACKEND
    ops.set_backend("pallas")
    try:
        if program == "decode":
            low = jax.jit(lambda p, c, t, bt, live: model.decode_step(
                p, t, c, block_tables=bt, live=live)).lower(
                place(params), place(caches), s((slots,)),
                s((slots, width)), s((slots,), jnp.bool_))
        else:
            low = jax.jit(lambda p, c, t, bt, st, cl: model.prefill_chunk(
                p, t, c, bt, 0, st, cl, final=True)).lower(
                place(params), place(caches), s((1, chunk)), s((width,)),
                s(()), s(()))
        compiled = low.compile()
        return compiled.as_text(), cfg, pages, compiled.memory_analysis()
    finally:
        ops._BACKEND = saved


@pytest.mark.parametrize("arch,program", [
    pytest.param("olmo-1b", "decode", id="decode"),
    pytest.param("olmo-1b", "chunk", id="chunk"),
    pytest.param("granite-8b", "decode", id="granite-decode"),
    pytest.param("granite-8b", "chunk", id="granite-chunk")])
def test_layer_scan_updates_pool_in_place_for_v5e(one_chip, arch, program):
    """Granite's programs also take every projection's bias through the
    bdmm epilogue (the decode grid at 16 rows, the general grid at a
    256-token chunk) and the GQA kernels at 4 query heads per KV head.
    Their temporaries stay under one layer's pool: nothing pool-sized is
    made beside the carried pools."""
    text, cfg, pages, mem = _paged_program(program, one_chip, arch)
    tail = f"16,{cfg.n_kv_heads},{cfg.hd}]"
    layer_pool = re.compile(rf"\[(?:1,)?{pages},{tail}")
    n_l = cfg.n_layers
    whole_pool = re.compile(rf"\[(?:{n_l},{pages}|{n_l * pages}),{tail}")
    comps, calls = _computations(text)
    assert "tpu_custom_call" in text
    ins = [(c, op, shape, line) for c, lines in comps.items()
           for op, shape, line in lines]
    sliced = [line for _, op, _, line in ins
              if op in ("dynamic-slice", "dynamic-update-slice")
              and layer_pool.search(line)]
    assert not sliced, sliced[:2]
    loop = _loop_computations(comps, calls)
    assert loop
    pool_sized = lambda shape: layer_pool.search(shape) or \
        whole_pool.search(shape)
    moved = [line for c, op, shape, line in ins
             if c in loop and op not in IN_PLACE and pool_sized(shape)]
    assert not moved, moved[:2]
    copies = [line for _, op, shape, line in ins
              if op in ("copy", "copy-start") and pool_sized(shape)]
    assert len(copies) <= 2, copies       # at most one per pool (K and V)
    layer_pool_bytes = pages * 16 * cfg.n_kv_heads * cfg.hd * 2
    print(f"{arch} {program}: temporaries {mem.temp_size_in_bytes} B, "
          f"one layer's pool {layer_pool_bytes} B")
    assert mem.temp_size_in_bytes < layer_pool_bytes
