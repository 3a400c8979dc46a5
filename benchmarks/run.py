"""Benchmark harness: one module per paper table/figure + system benches.

Prints ``name,value`` CSV rows (value is us_per_call for kernel benches and
a derived metric otherwise).  ``--quick`` trims iteration counts.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args(argv)
    from repro import use_compile_cache
    use_compile_cache()

    from benchmarks import (async_throughput, fault_sweep, fig2_convergence,
                            kernel_bench, noise_sweep, population_scale,
                            privacy_epsilon, roofline_report,
                            telemetry_overhead)
    benches = {
        "fig2_convergence": fig2_convergence.run,     # paper Fig. 2
        "noise_sweep": noise_sweep.run,               # Fig. 2 right, extended
        "privacy_epsilon": privacy_epsilon.run,       # Theorem 2
        "fault_sweep": fault_sweep.run,               # resilience runtime
        "population_scale": population_scale.run,     # virtual-K engine
        "async_throughput": async_throughput.run,     # event-driven engine
        "kernel_bench": kernel_bench.run,             # Pallas kernels
        "kernel_round": kernel_bench.run_round,       # fused round pipeline
                                                      # (writes BENCH_kernels)
        "roofline_report": roofline_report.run,       # deliverable (g)
        "telemetry_overhead": telemetry_overhead.run,  # docs/observability.md
                                                       # (writes BENCH_telemetry)
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    print("name,value,seconds")
    failures = 0
    for name, fn in benches.items():
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
            dt = time.time() - t0
            for metric, val in rows:
                print(f"{metric},{val:.6g},{dt:.1f}")
        except Exception:
            failures += 1
            print(f"{name},FAILED,{time.time()-t0:.1f}", file=sys.stderr)
            traceback.print_exc()
    # refresh the BENCH_index.json catalog over whatever landed on disk
    from benchmarks.meta import write_index
    write_index()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
