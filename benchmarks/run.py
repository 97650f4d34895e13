"""Benchmark harness — one section per paper table/figure.

Prints ``name,value,derived`` CSV rows. Sections:

  * table1_*   — accuracy + FC-param compression (paper Table 1)
  * fig4*_*    — mask-robustness, mask-sum uniformity, permutation ablation
  * fig5_*     — sparsity sweep (4x / 8x / 16x)
  * speedup_*  — dense vs masked vs packed wall-clock (paper §3.3)
  * bdmm_* / masked_matmul_* — kernel-path microbenches
  * serve,*    — static vs continuous-batching throughput (BENCH_serve.json)
  * fused,*    — fused vs unfused packed FFN + folded vs masked_dense
                 serving (BENCH_fused.json)
  * quant,*    — int8 packed decode vs fp + decode-path grid + logit
                 drift (BENCH_quant.json)
  * paged,*    — paged vs slot-dense serving: KV bytes allocated vs dense
                 reservation, decode tok/s, prefix-reuse savings
                 (BENCH_paged.json)
  * spec,*     — speculative decoding with the MPD-folded int8 draft:
                 decode tok/s vs the non-spec paged baseline, draft
                 acceptance, tokens/step, shared-trie prefix reuse
                 (BENCH_spec.json)

``--fast`` trims step counts for CI-style runs; the full run reproduces the
numbers quoted in EXPERIMENTS.md.

``--check`` runs the regression gate instead of printing rows: each
engine-level section (serve/fused/quant/paged/paged_prefill/spec/
serve_degraded/serve_dist) re-runs fresh at smoke scale and its headline
ratio is compared against the committed ``BENCH_*.json``; a drop of more
than ``--check-threshold`` (default 25%) exits non-zero. See
``benchmarks/check.py``.
"""

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer train steps / masks (smoke-level)")
    ap.add_argument("--sections", default="",
                    help="comma list: table1,fig4,fig5,speedup,kernels,"
                         "serve,fused,quant,paged,spec")
    ap.add_argument("--check", action="store_true",
                    help="regression gate: re-run sections fresh and fail "
                         "if a headline drops >threshold vs the committed "
                         "BENCH_*.json")
    ap.add_argument("--check-threshold", type=float, default=0.25,
                    help="--check failure threshold (fraction below the "
                         "committed headline)")
    args = ap.parse_args()
    want = set(args.sections.split(",")) if args.sections else None

    if args.check:
        from benchmarks import check
        sys.exit(check.run_check(
            sections=sorted(want) if want else None,
            threshold=args.check_threshold))

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
    if on("serve"):
        from benchmarks import serve_bench
        rows += serve_bench.rows(smoke=args.fast)
    if on("fused"):
        from benchmarks import fused_bench
        rows += fused_bench.rows(smoke=args.fast)
    if on("quant"):
        from benchmarks import quant_bench
        rows += quant_bench.rows(smoke=args.fast)
    if on("paged"):
        from benchmarks import paged_bench
        rows += paged_bench.rows(smoke=args.fast)
    if on("spec"):
        from benchmarks import spec_bench
        rows += spec_bench.rows(smoke=args.fast)
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
