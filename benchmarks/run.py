"""Benchmark harness entrypoint — one benchmark per paper table/figure
(deliverable d) plus kernel microbench, planner/serving hot paths and the
roofline table.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig3_payload roofline
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: fast serving
                                                     # subset, refreshes
                                                     # BENCH_serving.json
"""
from __future__ import annotations

import argparse
import csv
import functools
import io
import sys
import time

BENCHES = {}


def _register():
    from benchmarks import (calibration_bench, cost_fidelity_bench,
                            decode_bench, fleet_bench, fleet_scale_bench,
                            kernel_bench, paper_tables, planner_bench,
                            roofline_report)
    BENCHES.update({
        "fig3_payload": paper_tables.payload,
        "fig5_layerwise": paper_tables.layerwise_cost,
        "fig6_size_vs_acc": paper_tables.size_vs_accuracy,
        "fig7_10_baselines": paper_tables.baselines,
        "table4_multimodel": paper_tables.multimodel,
        "kernels": kernel_bench.kernels,
        "planner": planner_bench.planner,
        "serving": calibration_bench.serving,
        "fleet": fleet_bench.fleet,
        "fleet_chaos": fleet_bench.fleet_chaos,
        "fleet_scale": fleet_scale_bench.fleet_scale,
        "decode": decode_bench.decode,
        "cost_fidelity": cost_fidelity_bench.cost_fidelity,
        "roofline": roofline_report.roofline,
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--csv", default=None, help="also write rows to a file")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: reduced-depth serving bench + "
                         "full-size fleet bench")
    ap.add_argument("--profile", action="store_true",
                    help="run the selected benchmarks under cProfile and "
                         "print the top-20 cumulative-time hot spots")
    args = ap.parse_args(argv)
    if args.smoke and args.only:
        ap.error("--smoke selects its own benchmark set; drop --only")
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    _register()
    if args.smoke:
        from benchmarks import calibration_bench
        BENCHES["serving"] = functools.partial(calibration_bench.serving,
                                               smoke=True)
        from benchmarks import cost_fidelity_bench, decode_bench
        BENCHES["cost_fidelity"] = functools.partial(
            cost_fidelity_bench.cost_fidelity, smoke=True)
        BENCHES["decode"] = functools.partial(decode_bench.decode,
                                              smoke=True)
        # kernel microbench smoke point: the decode-attention scan-vs-
        # kernel rows (interpret-lane correctness off TPU) ride CI
        from benchmarks import kernel_bench
        BENCHES["kernels"] = functools.partial(kernel_bench.kernels,
                                               smoke=True)
        # the fleet benches are pricing-only and already CI-fast: --smoke
        # runs them at FULL size (>=1k requests, >=3 servers) so the
        # BENCH_serving.json fleet + fleet_chaos (MMPP arrivals, seeded
        # churn, retry/dead-letter accounting, journal-replay check)
        # trajectories are always fresh; the cost-fidelity bench
        # refreshes the predicted-vs-measured trajectory (its MNIST
        # setup is shared/cached)
        from benchmarks import fleet_scale_bench
        BENCHES["fleet_scale"] = functools.partial(
            fleet_scale_bench.fleet_scale, smoke=True)
        # fleet_scale --smoke: one 50k x 16-server point through the
        # engine's scale configuration with an asserted wall budget —
        # the §12 hot-path latency contract runs on every CI build
        names = ["serving", "fleet", "fleet_chaos", "fleet_scale",
                 "decode", "kernels", "cost_fidelity"]
    else:
        names = args.only or list(BENCHES)
    all_rows = []
    profiler = None
    if args.profile:
        import cProfile
        profiler = cProfile.Profile()
    for name in names:
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        if profiler is not None:
            profiler.enable()
        rows = BENCHES[name]()
        if profiler is not None:
            profiler.disable()
        all_rows += rows
        keys = list(rows[0].keys()) if rows else []
        out = io.StringIO()
        w = csv.DictWriter(out, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)
        print(out.getvalue().rstrip())
        print(f"--- {name}: {len(rows)} rows in {time.time() - t0:.1f}s\n",
              flush=True)
    if args.csv:
        keys = sorted({k for r in all_rows for k in r})
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            w.writerows(all_rows)
    if profiler is not None:
        import pstats
        print("=== profile: top 20 by cumulative time ===")
        pstats.Stats(profiler).sort_stats("cumulative").print_stats(20)
    print(f"TOTAL {len(all_rows)} rows from {len(names)} benchmarks")
    return 0


if __name__ == "__main__":
    sys.exit(main())
