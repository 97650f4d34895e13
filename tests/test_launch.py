"""Launch-layer integration: cell construction + AOT compile + roofline
extraction on a small forced-device mesh (subprocess; the main process keeps
one device)."""

from conftest import run_forced_device_subprocess as _run


def test_mesh_shapes():
    _run("""
import jax
from repro.dist.mesh import make_production_mesh, data_axes
# NB: on 8 forced devices we can't build the real 256/512-chip meshes, but
# the factory's shape logic is what we assert here.
try:
    make_production_mesh()
except ValueError as e:
    assert "requires" in str(e) or "devices" in str(e)
from repro.dist.mesh import make_mesh
m = make_mesh((2, 4), ("data", "model"))
assert data_axes(m) == ("data",)
m2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
assert data_axes(m2) == ("pod", "data")
print("OK")
""")


def test_tiny_cell_compiles_with_roofline_terms():
    _run("""
import dataclasses, jax, json
import repro.configs.common as cc
from repro.configs.common import SHAPES
from repro.launch import specs as specs_lib
from repro.launch import hlo as hlo_lib

# shrink a shape + arch so the cell compiles on 8 host devices
from repro.dist.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cc.SHAPES = dict(cc.SHAPES)
cc.SHAPES["train_4k"] = dataclasses.replace(SHAPES["train_4k"],
                                            seq_len=64, global_batch=8)
specs_lib.SHAPES = cc.SHAPES

import repro.configs.olmo_1b as mod
full = mod.full
def small(mpd_c=4, mpd_mode="packed"):
    return dataclasses.replace(full(mpd_c=mpd_c, mpd_mode=mpd_mode),
                               n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=4, d_ff=128, vocab=256)
mod.full = small

cell = specs_lib.make_cell("olmo-1b", "train_4k", mesh, mpd_c=4, grad_accum=2)
c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings).lower(*cell.args_sds).compile()
ma = c.memory_analysis()
assert ma.temp_size_in_bytes > 0
ca = c.cost_analysis()
assert ca.get("flops", 0) > 0
coll = hlo_lib.collective_summary(c.as_text())
assert coll.get("total", 0) > 0  # DP grad sync must appear
print("OK", ca.get("flops"), coll.get("total"))
""")


def test_fused_cell_reduces_collectives():
    """Iteration-5 regression: permutation fusion must cut collective bytes."""
    _run("""
import dataclasses, jax
import repro.configs.common as cc
from repro.configs.common import SHAPES
from repro.launch import specs as specs_lib
from repro.launch import hlo as hlo_lib

from repro.dist.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cc.SHAPES = dict(cc.SHAPES)
cc.SHAPES["train_4k"] = dataclasses.replace(SHAPES["train_4k"],
                                            seq_len=128, global_batch=8)
specs_lib.SHAPES = cc.SHAPES
import repro.configs.olmo_1b as mod
full = mod.full
def small(mpd_c=4, mpd_mode="packed", mpd_fuse=False):
    return dataclasses.replace(full(mpd_c=mpd_c, mpd_mode=mpd_mode),
                               n_layers=2, d_model=128, n_heads=4,
                               n_kv_heads=4, d_ff=256, vocab=256,
                               mpd_fuse=mpd_fuse)
mod.full = small

def coll(fuse):
    cell = specs_lib.make_cell("olmo-1b", "train_4k", mesh, mpd_c=4,
                               grad_accum=2, mpd_fuse=fuse)
    c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings).lower(*cell.args_sds).compile()
    return hlo_lib.collective_summary(c.as_text()).get("total", 0)

base, fused = coll(False), coll(True)
assert fused < base, (base, fused)
print("OK", base, "->", fused)
""")


def test_launcher_builds_paged_engine():
    """The launcher's default flags serve from the paged KV cache; the
    removed ``--static`` and ``--paged`` flags no longer parse, and an
    embed-frontend arch stops with the engine's own reason."""
    import pytest

    from repro.launch import serve
    from repro.serve import PagedCache

    args = serve.build_parser().parse_args(["--arch", "olmo-1b", "--smoke"])
    _, model, params = serve._load_model(args)
    engine, mode = serve._build_engine(args, model, params, chaos=False)
    assert isinstance(engine.cache, PagedCache) and mode == "paged"
    assert engine.cache.page_size == args.page_size
    for gone in ("--static", "--paged", "--batch=4"):
        with pytest.raises(SystemExit):
            serve.build_parser().parse_args(["--arch", "olmo-1b", gone])
    args = serve.build_parser().parse_args(["--arch", "qwen2-vl-72b",
                                            "--smoke"])
    _, model, params = serve._load_model(args)
    with pytest.raises(SystemExit, match="token frontends only"):
        serve._build_engine(args, model, params, chaos=False)
