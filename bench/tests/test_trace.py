"""The trace reduction on a small recorded trace, against numbers worked
out by hand."""

import json

import pytest

import trace_reduce as tr
from conftest import BENCH


@pytest.fixture
def red():
    with open(BENCH / "tests" / "data" / "trace_small.json") as f:
        return tr.reduce(json.load(f), 0, 3000)


def test_busy_union_and_gaps(red):
    dev = red["devices"][0]
    # [100, 1100) + [1500, 1700) + [2900, 3000): the while op covers its body
    assert dev["busy_ns"] == 1000 + 200 + 100
    assert dev["gaps"] == [(0, 100), (1100, 400), (1700, 1200)]
    assert tr.busy_s(red) == pytest.approx(1300e-9)
    assert tr.window_s(red) == pytest.approx(3000e-9)


def test_kernel_time_by_name(red):
    dev = red["devices"][0]
    assert "while.3" not in dev["op_ns"]              # a container, not an op
    assert dev["op_ns"]["bdmm.1"] == 200
    assert dev["op_ns"]["copy.1"] == 100              # clipped at the slice end
    assert tr.kernel_ns(red, "bdmm") == 300
    assert tr.kernel_ns(red, "paged_attn|paged_attention") == 300


def test_programs_and_their_ops(red):
    dec = tr.executions(red, "decode_paged_impl")
    assert [(e["start"], e["dur"]) for e in dec] == [(100, 1000)]
    assert [n for n, _ in dec[0]["ops"]] == [
        "bdmm.1", "paged_attention.6", "bdmm.1", "fused_ffn.2",
        "dynamic-slice_bitcast_fusion.36"]
    chunk = tr.executions(red, "prefill_chunk_impl")
    assert chunk[0]["ops"] == [("bdmm.4", 100),
                               ("paged_prefill_attention.6", 100)]


def test_breakdown(red):
    b = tr.breakdown(red, top=3)
    assert b["device_ops"][0] in (["bdmm", 300e-9],
                                  ["paged_attention", 300e-9])
    assert {n for n, _ in b["device_ops"][:2]} == {"bdmm", "paged_attention"}
    assert b["idle_gaps"] == [["none", 1200e-9], ["bench.idle", 400e-9],
                              ["bench.step", 100e-9]]


def test_op_names():
    assert tr.op_name("%paged_attention.6 = bf16[32,16,128]{2,1,0} "
                      "custom-call(s32[32,128] %copy-done)") == \
        "paged_attention.6"
    assert tr.op_name("jit__decode_paged_impl(1)") == \
        "jit__decode_paged_impl(1)"
