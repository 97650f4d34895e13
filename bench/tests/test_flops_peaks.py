"""The FLOP and byte functions, the peak table and the refusal of any
device that is not a known TPU."""

import json
import os
import subprocess
import sys

import pytest

import flops
import harness
import peaks
from conftest import BENCH, ROOT


def _model(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    return cfg["model"], cfg["quantize"]


def test_bdmm_counts_by_hand():
    # 32 rows through 8 blocks of 256x256 int8 weights with f32 scales
    f, b = flops.bdmm(32, 8, 256, 256, 1)
    assert f == 2 * 32 * 8 * 256 * 256 == 33_554_432
    assert b == 8 * 256 * 256 + 32 * 8 * (256 + 256) * 2 + 8 * 256 * 4
    f, b = flops.bdmm(1, 8, 512, 512, 2)
    assert (f, b) == (2 * 8 * 512 * 512, 8 * 512 * 512 * 2 + 8 * 1024 * 2)


def test_fused_ffn_counts_by_hand():
    f, b = flops.fused_ffn(32, 8, 2048, 8192, True, 1)
    assert f == 2 * 32 * 3 * 2048 * 8192 / 8
    assert b == 3 * 2048 * 8192 / 8 + 2 * 32 * 2048 * 2 + (2 * 8192 + 2048) * 4


@pytest.mark.parametrize("name,decode,chunk,ffn", [
    ("olmo-1b.c8.int8-fused", 16 * 4 + 1, 16 * 4, 16),
    ("granite-8b.c8.bf16", 36 * 7 + 1, 36 * 7, 0)])
def test_program_calls(name, decode, chunk, ffn):
    model, q = _model(name)
    calls = flops.program_calls(model, q, "decode", 32)
    assert len(calls["bdmm"]) == decode and len(calls["fused_ffn"]) == ffn
    assert len(flops.program_calls(model, q, "chunk", 256)["bdmm"]) == chunk
    assert len(flops.program_calls(model, q, "chunk_final",
                                   256)["bdmm"]) == chunk + 1


def test_token_flops_olmo():
    model, _ = _model("olmo-1b.c8.int8-fused")
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    params = (16 * per_layer + 2048 * 50304) / 8
    assert flops.matmul_params(model) == params
    assert flops.token_flops(model, 100) == 2 * params + 4 * 16 * 100 * 2048


def test_peak_table():
    p = peaks.peaks("tpu", "TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    with pytest.raises(ValueError, match="device kind"):
        peaks.peaks("tpu", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="platform"):
        peaks.peaks("cpu", "cpu")


def test_require_chip_refuses_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.require_chip(1)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, env=env,
                          timeout=300)


def test_run_off_tpu_prints_nothing():
    r = _run(ROOT, "--workload", "olmo1b-c8i8.chat", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_run_without_the_program_prints_nothing(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--workload", "olmo1b-c8i8.chat", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and r.stdout == ""
