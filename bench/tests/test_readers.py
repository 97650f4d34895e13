"""Per-layer readers on a small recorded trace and a made-up window, against
numbers worked out by hand; with nothing to read they return None."""

import json
from types import SimpleNamespace

import pytest

import flops
import harness
import peaks
import trace_reduce as tr
from conftest import BENCH

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "d_ff": 128, "vocab": 96, "mpd_c": 4, "ffn_kind": "swiglu"}


def _ctx(red):
    # one request with 10 prompt tokens; its tokens 1 and 2 fall in the
    # window [1, 3) s; three engine steps of which two decoded
    rec = SimpleNamespace(id=0, n_prompt=10, times=[0.5, 1.5, 2.5])
    run = SimpleNamespace(recs=[rec], t_open=1.0, t_close=3.0,
                          occupancy=[0.5, 0.0, 0.25])
    return SimpleNamespace(run=run, model=MODEL, red=red,
                           peaks=peaks.peaks("tpu", "TPU v5 lite"))


def test_step_mfu_by_hand():
    with open(BENCH / "tests" / "data" / "trace_small.json") as f:
        red = tr.reduce(json.load(f), 0, 3000)
    work = flops.token_flops(MODEL, 11) + flops.token_flops(MODEL, 12)
    # one decode execution wholly inside the slice, 1000 ns
    want = 100.0 * (work / 2) / (1000e-9 * 197e12)
    got = harness.metric_reader("step_mfu")(_ctx(red))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", ["step_mfu", "decode_step_ms",
                                  "idle_share", "bdmm_roofline",
                                  "busy_share.paged_attention"])
def test_untraced_readers_return_none(name):
    assert harness.metric_reader(name)(_ctx(None)) is None
