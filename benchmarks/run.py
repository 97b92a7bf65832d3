"""Benchmark driver — one module per paper table/figure family.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV rows. Wall-times are CPU XLA
timings (ratios meaningful, absolutes are not TPU numbers); `derived`
carries the paper-figure quantity (speedup / op fraction / traffic ratio).
TPU roofline numbers live in the dry-run path (repro.launch.dryrun) and
EXPERIMENTS.md.
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sweeps (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: prune,kernels,fft_opt,"
                         "fusion,e2e,serve,train,tuned")
    ap.add_argument("--autotune", action="store_true",
                    help="regenerate the tuned block-plan cache "
                         "(scripts/autotune.py) before benchmarking, so "
                         "the tuned rows measure fresh winners")
    ap.add_argument("--ranks", default="1,2,3",
                    help="spatial ranks for the train rank sweep "
                         "(e.g. --ranks 3 tracks only the 3D path)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        ranks = tuple(int(r) for r in args.ranks.split(","))
    except ValueError:
        ranks = ()
    if not ranks or any(r not in (1, 2, 3) for r in ranks):
        ap.error(f"--ranks must be a comma-separated subset of 1,2,3 "
                 f"(got {args.ranks!r})")

    if args.autotune:
        from repro.tuning import autotune
        autotune.tune(measure="auto" if not args.quick else "none")
        print()

    from benchmarks import (bench_e2e, bench_fft_opt, bench_fusion,
                            bench_kernels, bench_prune, bench_train)
    table = {
        "prune": lambda: bench_prune.run(),
        "kernels": lambda: bench_kernels.run(args.quick),
        "fft_opt": lambda: bench_fft_opt.run(args.quick),
        "fusion": lambda: bench_fusion.run(args.quick),
        "e2e": lambda: bench_e2e.run(args.quick),
        "serve": lambda: bench_e2e.run_serve(args.quick),
        "train": lambda: bench_train.run(args.quick, ranks=ranks),
        "tuned": lambda: bench_e2e.run_tuned(args.quick),
    }
    # "e2e" already includes the serving AND tuned rows; don't run them
    # twice on a full sweep.
    only = args.only.split(",") if args.only else \
        [k for k in table if k not in ("serve", "tuned")]
    for name in only:
        table[name]()
        print()


if __name__ == "__main__":
    main()
