#!/usr/bin/env python3
"""Readings a cell's bounds and limits are set from, many seeds or rates in
one process (set-up once). Not part of a benchmark run.

    python bench/calibrate.py --workload <cell> knee --rates 3,4,5,6 --seconds 20
    python bench/calibrate.py --workload <cell> limits --seeds 1,2,3 --seconds 40

``knee``: the cell's traffic at each offered rate (Poisson mixes), one
window each: completed tok/s, TTFT p95, and the queue left at the close.
The knee is the highest rate the engine sustains.

``limits``: for each seed, new weights and traffic, one window at the
cell's load, then the widest logit gap of the served tokens against the
float32 reference (the lower reading) and the same for the control, the
reference itself computed with the configuration's ``check.control``
weight format in the program's place (the upper reading). One JSON line
per rate or seed on stdout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def clear(engine, run) -> None:
    """Cancel what the window left queued or running (a backlog would take
    minutes to drain)."""
    for r in run.recs:
        if r.submit is not None:
            engine.cancel(run.objs[r.id])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("mode", choices=("knee", "limits"))
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import harness
    import weights
    from repro.serve import ServeMetrics

    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.require_chip(cell.chips)
    config, mix = cell.config, cell.mix
    seeds = [int(s) for s in args.seeds.split(",")]
    model, layout = harness.build_model(config)
    params = weights.make_params(layout, seeds[0])
    engine = harness.build_engine(model, params, mix)
    harness.warm(engine, mix)
    print(f"set up in {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    vocab, max_len = config["model"]["vocab"], mix["engine"]["max_len"]

    if args.mode == "knee":
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            m = copy.deepcopy(mix)
            m["arrival"]["rate"] = rate
            engine.metrics = ServeMetrics()
            reqs = harness.gen.generate(m, seeds[0] + i, args.seconds, vocab)
            run = harness.drive(engine, reqs, m, args.seconds)
            e2e = harness.end_to_end(run, 0.0)
            waiting = len(engine.scheduler.waiting)
            print(json.dumps({"rate": rate, "tok_s": e2e["tok_s"],
                              "ttft_p95_ms": e2e["ttft_p95_ms"],
                              "itl_p95_ms": e2e["itl_p95_ms"],
                              "waiting_at_close": waiting}), flush=True)
            clear(engine, run)
        return 0

    fmt = config["check"]["control"]
    for seed in seeds:
        engine.params = params = weights.make_params(layout, seed)
        engine.metrics = ServeMetrics()
        engine.cache.flush_trie()
        reqs = harness.gen.generate(mix, seed, args.seconds, vocab)
        run = harness.drive(engine, reqs, mix, args.seconds)
        e2e = harness.end_to_end(run, 0.0)
        sound = harness.compare(config, params, run, max_len, seed)
        ctl = harness.compare(config, params, run, max_len, seed, fmt=fmt)
        print(json.dumps({"seed": seed, "sound": sound, f"control_{fmt}": ctl,
                          "tok_s": e2e["tok_s"]}), flush=True)
        clear(engine, run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
