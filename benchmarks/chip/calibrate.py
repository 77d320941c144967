#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers and
its controls' (``check.py``), over many seeds in one process, each seed a
short run of the cell at its own size.

  python benchmarks/chip/calibrate.py --workload granite-20b-stage.hyde \\
      --seeds 11 12 13 --seconds 10

Prints one JSON line per seed on standard output.  Needs the chip, as
``run.py`` does; the benchmark's own runs never run this.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from benchmarks.chip import cell as cell_mod
    from benchmarks.chip.harness import run_cell
    from benchmarks.chip.run import CACHE_DIR, TRACE_DIR

    d = jax.devices()[0]
    if d.platform != "tpu":
        print("calibrate.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    c = cell_mod.load_cell(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        res = run_cell(c, seed=seed, seconds=args.seconds, trace=False,
                       device=d, t_start=t, trace_dir=TRACE_DIR,
                       control=True)
        print(json.dumps({"workload": c.name, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "numbers": res["numbers"],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
