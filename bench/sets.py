#!/usr/bin/env python3
"""Runs of one cell, one process each, and the spread a bound is set from.
Not part of a benchmark run.

    python bench/sets.py run --workload <cell> --seeds 1,2,3 --seconds 51 \\
        --trace 0 --tag set1 --out runs.jsonl
    python bench/sets.py spread runs.jsonl

``run`` starts ``bench/run.py`` once per seed, one after another (each
process holds the chip alone), and appends one JSON line per run to
``--out``: the tag, the seed, the exit code, the wall time and the result
line. ``spread`` reads those lines and prints, for each metric of each tag,
the median and the spread: the distance between the first and third
quartiles (``statistics.quantiles(values, n=4)``) over the median.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from collections import defaultdict
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def run(args) -> int:
    for seed in args.seeds.split(","):
        t = time.perf_counter()
        p = subprocess.run([sys.executable, str(RUN), "--workload",
                            args.workload, "--seed", seed, "--seconds",
                            str(args.seconds), "--trace", str(args.trace)],
                           capture_output=True, text=True)
        wall = time.perf_counter() - t
        last = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            line = json.loads(last)
        except json.JSONDecodeError:
            line = None
        with open(args.out, "a") as f:
            f.write(json.dumps({"tag": args.tag, "workload": args.workload,
                                "seed": int(seed), "rc": p.returncode,
                                "wall_s": wall, "line": line}) + "\n")
        print(f"{args.tag} seed {seed}: rc {p.returncode}, {wall:.1f} s",
              file=sys.stderr)
        print(p.stderr[-1500:], file=sys.stderr, flush=True)
        if p.returncode != 0:
            return p.returncode
    return 0


def summary(path) -> int:
    by_tag = defaultdict(list)
    with open(path) as f:
        for raw in f:
            r = json.loads(raw)
            if r["line"] is not None:
                by_tag[(r["workload"], r["tag"])].append(r["line"])
    for (cell, tag), lines in sorted(by_tag.items()):
        print(f"{cell} {tag}: {len(lines)} runs, correct "
              f"{sum(ln['correct'] for ln in lines)}")
        for name in lines[0]["metrics"]:
            v = [ln["metrics"][name]["value"] for ln in lines
                 if name in ln["metrics"]]
            s = spread(v) if len(v) > 1 else float("nan")
            print(f"  {name:28s} median {statistics.median(v):12.4f} "
                  f"spread {s:.4f}  {v}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--tag", required=True)
    r.add_argument("--out", required=True)
    s = sub.add_parser("spread")
    s.add_argument("path")
    args = ap.parse_args(argv)
    return run(args) if args.mode == "run" else summary(args.path)


if __name__ == "__main__":
    sys.exit(main())
