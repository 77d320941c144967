#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell, in one process.

  python benchmarks/chip/run.py --workload granite-20b-stage.hyde \\
      --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
and a traffic file; ``harness.run_cell`` builds the served path with
them, warms it up, drives whole drains of the closed loop for
``--seconds``, reads the metrics and compares what was served with the
plain references.  ``--trace 1`` records a profiler trace of the window
and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result, one JSON object; the
numbers compared and their limits are the last lines of standard error.
Exits 2, printing no result, where JAX finds no TPU, fewer chips than
the cell asks for, kernels that do not resolve to compiled Pallas, or no
program beside the benchmark.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def refuse(msg: str) -> int:
    print(f"benchmarks/chip/run.py: {msg}; nothing was run", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return refuse(f"no program under {ROOT}/src")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from benchmarks.chip import cell as cell_mod
    c = cell_mod.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return refuse(f"no TPU (JAX found {devices[0].platform})")
    if len(devices) < c.chips:
        return refuse(f"the cell needs {c.chips} chips, JAX found "
                      f"{len(devices)}")
    from repro.kernels import ops
    mode = ops.resolve_mode("auto")
    if mode != "kernel":
        return refuse(f"kernels resolve to {mode!r}, not 'kernel'")
    # the persistent compile cache lives in the checkout at a fixed path
    # unless JAX_COMPILATION_CACHE_DIR gives one
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    d = devices[0]
    print(f"# device: platform {d.platform}, kind {d.device_kind!r}, count "
          f"{len(devices)}; cell {c.name} on {c.chips} chip(s); compile "
          f"cache {os.environ.get('JAX_COMPILATION_CACHE_DIR') or CACHE_DIR}",
          file=sys.stderr, flush=True)

    from benchmarks.chip.harness import run_cell
    result = run_cell(c, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), device=d, t_start=T_START,
                      trace_dir=TRACE_DIR)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
