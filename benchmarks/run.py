"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV to stdout and writes detailed
CSVs to experiments/bench/. Run: PYTHONPATH=src python -m benchmarks.run
"""

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names to run")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: run a small fast subset at --quick "
                         "sizes so the perf scripts cannot silently rot")
    ap.add_argument("--report-dir", default=None, metavar="DIR",
                    help="directory for the machine-readable "
                         "BENCH_<name>.json reports (default "
                         "experiments/bench)")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
        if args.only is None:
            args.only = ("overlap,overlap_trace,sched,admission,openloop,"
                         "tenants,decode_microbench,chunk_kv")

    from benchmarks import (bench_breakdown, bench_budget, bench_chunk_kv,
                            bench_decode_microbench,
                            bench_hitrate, bench_kernels, bench_latency,
                            bench_nprobe, bench_openloop, bench_overlap,
                            bench_overlap_trace, bench_sched, bench_scaling,
                            bench_tenants, bench_throughput)
    from benchmarks.common import set_report_dir

    if args.report_dir:
        set_report_dir(args.report_dir)

    benches = {
        "overlap": lambda: bench_overlap.run(64 if args.quick else 256),
        "overlap_trace": lambda: bench_overlap_trace.run(
            n_requests=12 if args.quick else 24),
        "hitrate": lambda: bench_hitrate.run(8 if args.quick else 32),
        "latency": lambda: bench_latency.run(4 if args.quick else 16),
        "throughput": lambda: bench_throughput.run(
            (1, 4) if args.quick else (1, 2, 4, 8)),
        "scaling": lambda: bench_scaling.run(
            (1, 2) if args.quick else (1, 2, 4, 8),
            global_batch=8 if args.quick else 32),
        "sched": lambda: bench_sched.run(
            global_batch=8 if args.quick else 32),
        "nprobe": lambda: bench_nprobe.run(
            (16, 64) if args.quick else (16, 32, 64, 128)),
        "breakdown": lambda: bench_breakdown.run(4 if args.quick else 8),
        "budget": lambda: bench_budget.run(
            n_queries=4 if args.quick else 16),
        "admission": lambda: bench_budget.run_admission(
            n_queries=4 if args.quick else 8),
        "kernels": lambda: bench_kernels.run(
            P=512 if args.quick else 2048),
        "decode_microbench": lambda: (
            bench_decode_microbench.run_smoke() if args.quick
            else bench_decode_microbench.run()),
        "chunk_kv": lambda: (
            bench_chunk_kv.run_smoke() if args.quick
            else bench_chunk_kv.run()),
        "openloop": lambda: bench_openloop.run(
            n_requests=16 if args.quick else 48),
        "tenants": lambda: bench_tenants.run(
            n_latency=4 if args.quick else 8,
            n_batch=10 if args.quick else 24),
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},0,FAILED:{type(e).__name__}:{e}", file=sys.stderr)
            raise
    print(f"# total wall {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
