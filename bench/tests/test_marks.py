"""The readers of the program's own marks (engine spans, layer-scan scopes,
compile times) on a small recorded trace and a made-up window, against
numbers worked out by hand; on a trace without the marks, as an older
program gives, they return None."""

import json
from types import SimpleNamespace

import pytest

import harness
import trace_marks
import trace_reduce as tr
from conftest import BENCH


def _spans():
    with open(BENCH / "tests" / "data" / "trace_spans.json") as f:
        trace = json.load(f)
    # the two readings of one trace: trace_reduce's plain form, and the
    # engine's spans with the op scopes
    plain = {"devices": trace["devices"],
             "host": [h[:3] for h in trace["host"]]}
    red = tr.reduce(plain, 0, 10000)
    engine = [h for h in trace["host"] if h[0] in trace_marks.engine_spans()
              and h[1] + h[2] <= red["hi"]]
    ctx = SimpleNamespace(red=red, engine_spans=engine,
                          op_scopes=trace["scopes"])
    return plain, ctx


def test_host_self_ms_by_hand():
    _, ctx = _spans()
    # steps wholly in the slice, less their sync children: 3700 - 600 -
    # 1700, 1900 - 1000, 900 (admission only); two of them decoded
    got = harness.metric_reader("host_self_ms")(ctx)
    assert got == pytest.approx((1400 + 900 + 900) / 2 / 1e6)


def test_layer_scan_slicing_by_hand():
    _, ctx = _spans()
    # busy: [600, 1200) + [1850, 3550) + [4650, 5550) = 3200 ns; ops scoped
    # layer_scan and not layer (the while ops are containers): 100 + 100 in
    # the chunk, 400 + 600 and 200 + 300 in the two decodes
    got = harness.metric_reader("busy_share.layer_scan_slicing")(ctx)
    assert got == pytest.approx(100.0 * 1700 / 3200)
    # the same ops read by their fusion names, without the copy
    by_name = tr.kernel_ns(ctx.red, r"^(dynamic-slice_bitcast_fusion"
                                r"|bitcast_dynamic-update-slice_fusion)")
    assert 100.0 * by_name / 3200 == pytest.approx(50.0)


def test_breakdown_names_the_innermost_engine_span():
    plain, _ = _spans()
    b = tr.breakdown(tr.reduce(plain, 0, 10000), top=3)
    # the gap [3550, 4650) falls in engine.admit, inside engine.step, inside
    # bench.step; the others in engine.step between its children
    assert b["idle_gaps"] == [["engine.step", 4450e-9],
                              ["engine.admit", 1100e-9],
                              ["engine.step", 650e-9]]


def test_marks_absent_read_none():
    with open(BENCH / "tests" / "data" / "trace_small.json") as f:
        red = tr.reduce(json.load(f), 0, 3000)
    older = SimpleNamespace(red=red, engine_spans=[], op_scopes={})
    for name in ("host_self_ms", "busy_share.layer_scan_slicing"):
        read = harness.metric_reader(name)
        assert read(older) is None
        assert read(SimpleNamespace(red=None)) is None


def test_window_compiles_counts_the_window_only(monkeypatch):
    import repro.serve.metrics as serve_metrics

    t0 = 100.0
    monkeypatch.setattr(serve_metrics, "COMPILE_TIMES",
                        [t0 + 0.5, t0 + 1.0, t0 + 2.9, t0 + 3.0])
    run = SimpleNamespace(t0=t0, t_open=1.0, t_close=3.0)
    got = harness.metric_reader("window_compiles")(SimpleNamespace(run=run))
    assert got == 2.0


def test_program_scopes_of_the_cell(cpu_cell):
    """The scopes read from the cell's compiled programs, at smoke size on
    the CPU: the scan's own slicing is scoped layer_scan alone, the body's
    matmuls layer too."""
    config, mix = cpu_cell["chat"]
    scopes = trace_marks.program_scopes(config, mix)
    scan_only = {n for n, p in scopes.items() if trace_marks.scan_only(p)}
    assert any("dynamic_slice" in scopes[n] for n in scan_only)
    assert any("dynamic_update_slice" in scopes[n] for n in scan_only)
    assert any("/layer/" in p and "dot_general" in p
               for p in scopes.values())
    assert not any("dot_general" in scopes[n] for n in scan_only)
