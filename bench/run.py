#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run that also records a profiler trace of a slice
of the window. Both compare what the window served with the plain float32
reference and print each compared number beside its limit, last on
standard error and last in the result line. Off a TPU, with fewer chips
than the cell asks for, or off the Pallas kernel route, the run exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
# the TPU runtime logs under /tmp/tpu_logs unless told otherwise; a run
# writes only inside its checkout
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(HERE.parent / ".bench_out" / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import harness
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    try:
        cell = harness.load_cell(args.workload)
        harness.enable_cache()
        devices = harness.require_chip(cell.chips)
        out = harness.cell_run(cell, args.seed, args.seconds,
                               bool(args.trace), T_START, devices)
    except (harness.BenchError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
