"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  fig1  -- PCA bottleneck split (paper Fig. 1)
  fig6  -- execution time across datasets (paper Fig. 6)
  fig7  -- energy model (paper Fig. 7)
  fig8  -- Frobenius-norm convergence study (paper Fig. 8 / Sec. VII-D)
  dse   -- T/S design-space exploration (paper Figs. 9-11)
  table3-- resource/config comparison (paper Tables I-III)
  serve -- batched multi-tenant serving throughput (repro.serving)
  autotune -- tuned-vs-default serving-plan gain (serving.autotune)
  cold_start -- fresh-replica TTFR: cold JIT vs warm disk cache vs warmup
  goodput -- open-loop goodput-under-SLO vs offered load (serving.frontend)
  controller -- controller regret vs oracle on a regime shift, plus the
                bandit's measured-eval pruning (serving.controller)
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--full", action="store_true",
                    help="larger sweeps (slow on CPU)")
    args = ap.parse_args()

    from . import (autotune_gain, cold_start, controller_regret, dse,
                   fig1_bottlenecks, fig6_exec_time, fig7_energy,
                   fig8_frobenius, goodput, perf_variants,
                   serve_throughput, table3_configs)
    suite = {
        "table3": table3_configs,
        "fig8": fig8_frobenius,
        "fig7": fig7_energy,
        "fig6": fig6_exec_time,
        "fig1": fig1_bottlenecks,
        "dse": dse,
        "perf": perf_variants,
        "serve": serve_throughput,
        "autotune": autotune_gain,
        "cold_start": cold_start,
        "goodput": goodput,
        "controller": controller_regret,
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for name, mod in suite.items():
        if only and name not in only:
            continue
        try:
            mod.run(fast=not args.full)
        except Exception:  # keep the harness running, report at the end
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED,{','.join(failed)},", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
