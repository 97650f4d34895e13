"""Benchmark harness — one section per paper table/figure.

Prints ``name,value,derived`` CSV rows. Sections:

  * table1_*   — accuracy + FC-param compression (paper Table 1)
  * fig4*_*    — mask-robustness, mask-sum uniformity, permutation ablation
  * fig5_*     — sparsity sweep (4x / 8x / 16x)
  * speedup_*  — dense vs masked vs packed wall-clock (paper §3.3)
  * bdmm_* / masked_matmul_* — kernel-path microbenches

``--fast`` trims step counts for CI-style runs; the full run reproduces the
numbers quoted in EXPERIMENTS.md.

Serving speed is measured on the chip by ``bench/`` (see ``PERF.md``).
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer train steps / masks (smoke-level)")
    ap.add_argument("--sections", default="",
                    help="comma list: table1,fig4,fig5,speedup,kernels")
    args = ap.parse_args()
    want = set(args.sections.split(",")) if args.sections else None

    def on(name):
        return want is None or name in want

    steps = 150 if args.fast else 400
    n_masks = 4 if args.fast else 8

    from benchmarks import paper_repro, speedup

    rows = []
    if on("table1"):
        rows += paper_repro.table1(steps=steps)
    if on("fig4"):
        rows += paper_repro.fig4_masks(n_masks=n_masks, steps=max(steps // 2, 100))
        rows += paper_repro.fig4_permutation_ablation(steps=steps)
    if on("fig5"):
        rows += paper_repro.fig5_sparsity(steps=max(steps // 2, 100))
    if on("speedup"):
        rows += speedup.layer_speedup()
    if on("kernels"):
        rows += speedup.kernel_bench()
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
