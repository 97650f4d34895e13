"""HLO collective-parser validation: loop-scaled collective bytes from a
scanned program must match the unrolled program's direct count."""

from conftest import run_forced_device_subprocess as _run


def test_loop_scaling_matches_unrolled():
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo as hlo_lib

from repro.dist.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
L, D = 6, 64
w_sh = NamedSharding(mesh, P(None, None, "model"))
x_sh = NamedSharding(mesh, P("data", None))

def layer(x, w):
    # row-parallel matmul => one all-reduce of the (B, D) output per layer
    h = jnp.einsum("bd,df->bf", x, w)
    return jnp.tanh(jax.lax.with_sharding_constraint(
        h, NamedSharding(mesh, P("data", None))))

def scanned(x, ws):
    return jax.lax.scan(lambda x, w: (layer(x, w), None), x, ws)[0].sum()

def unrolled(x, ws):
    for i in range(L):
        x = layer(x, ws[i])
    return x.sum()

x = jax.ShapeDtypeStruct((8, D), jnp.float32)
ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
outs = NamedSharding(mesh, P())
b_scan = hlo_lib.collective_summary(
    jax.jit(scanned, in_shardings=(x_sh, w_sh), out_shardings=outs)
    .lower(x, ws).compile().as_text()).get("total", 0)
b_unroll = hlo_lib.collective_summary(
    jax.jit(unrolled, in_shardings=(x_sh, w_sh), out_shardings=outs)
    .lower(x, ws).compile().as_text()).get("total", 0)
assert b_scan > 0 and b_unroll > 0, (b_scan, b_unroll)
ratio = b_scan / b_unroll
assert 0.8 < ratio < 1.3, f"loop scaling off: scan={b_scan} unroll={b_unroll}"
print("OK", b_scan, b_unroll)
""")
    assert "OK" in out


def test_shape_bytes():
    from repro.launch.hlo import shape_bytes
    assert shape_bytes("bf16[4,8]") == 64
    assert shape_bytes("f32[10]") == 40
    assert shape_bytes("(bf16[2,2], f32[3])") == 8 + 12
    assert shape_bytes("pred[16]") == 16
    assert shape_bytes("token[]") == 0  # non-numeric types ignored


def test_trip_parse():
    from repro.launch import hlo as hlo_lib
    comps = {"cond": ["%c = s32[] constant(17)",
                      "ROOT %cmp = pred[] compare(%p, %c), direction=LT"]}
    assert hlo_lib._parse_trip(comps["cond"]) == 17
