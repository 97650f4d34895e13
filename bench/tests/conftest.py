"""CPU tests of the benchmark harness at smoke sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import copy
import json
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the smoke widths of each benchmark configuration: same arch, norm,
# fusion and quantization, widths a CPU test can hold, and float32 (the CPU
# backend has no bf16 x bf16 -> f32 dot)
SMOKE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
         "vocab": 96, "mpd_c": 4, "dtype": "float32"}


def smoke_config(name: str, **model) -> dict:
    with open(BENCH / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    kv = cfg["model"]["n_kv_heads"] * SMOKE["n_heads"] // cfg["model"]["n_heads"]
    cfg["model"].update(SMOKE, n_kv_heads=kv, **model)
    cfg["name"] = f"{name}.smoke"
    # smoke readings (CPU, float32 program against the float32 reference):
    # sound runs 0.0 on every seed; the control 0.026 at the least (fp8,
    # seeds 1-3; int4 0.199)
    cfg["check"]["limits"] = {"max_logit_gap": 0.005}
    cfg["check"]["sample_requests"] = 8
    return cfg


def smoke_mix(name: str, **arrival) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    mix = copy.deepcopy(mix)
    mix["engine"] = {"slots": 4, "max_len": 64, "page_size": 8,
                     "prefill_chunk": 16}
    mix["prompt"].update(median=12, min=4, max=24)
    mix["output"].update(min=4, max=24)
    if mix["output"]["dist"] == "lognormal":
        mix["output"]["median"] = 8
    if mix.get("shared_prefix"):
        mix["shared_prefix"].update(n_prompts=2, tokens=16)
    if mix["arrival"]["kind"] == "poisson":
        mix["arrival"].update(rate=20.0, ramp_s=0.5, tail_s=0.5)
    else:
        mix["arrival"].update(requests=24)
    mix["arrival"].update(arrival)
    return mix


@pytest.fixture
def cpu_cell():
    """A cell at smoke size: (config, mix) for each benchmark config."""
    return {"chat": (smoke_config("olmo-1b.c8.int8-fused"), smoke_mix("chat")),
            "decode": (smoke_config("granite-8b.c8.bf16"),
                       smoke_mix("backlog-short"))}
