"""Recommended process environment for serving/benchmark launches.

TPU-pod JAX launchers converge on the same handful of process-level
knobs (see SNIPPETS.md — HomebrewNLP / olmax run.sh): tcmalloc
preloaded for allocation-heavy host paths, its large-alloc warning
threshold raised past slab sizes, TF logging silenced, and XLA flags
pinning the step-marker to the outer while loop (so profiles bucket by
decode step) plus an explicit host-platform device count for CPU
experiments with sharded meshes.

This module is the ONE place those knobs live: ``recommended_env()``
computes them for this machine, ``render_shell()`` prints an
eval-able/source-able shell snippet, and ``validate()`` reports which
recommendations the current process is missing.  ``launch/serve.py
--print-env`` (and ``python -m repro.launch.env``) print the snippet so
interactive runs and CI wrappers cannot drift apart.

None of this is required for correctness — it is the perf hygiene
layer; everything degrades gracefully when (e.g.) tcmalloc is absent.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)

# raised past any slab/datastore allocation so tcmalloc never warns
# mid-benchmark (the warning itself perturbs timing)
LARGE_ALLOC_THRESHOLD = 60_000_000_000


def _find_tcmalloc() -> Optional[str]:
    for p in TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def xla_flags(host_device_count: Optional[int] = None) -> str:
    """XLA flag string: step-marker at the outer while loop (decode-step
    profile buckets) and, when given, a forced host-platform device
    count (CPU stand-in for multi-chip meshes)."""
    flags = ["--xla_step_marker_location=1"]
    if host_device_count is not None and host_device_count > 1:
        flags.append(
            f"--xla_force_host_platform_device_count={host_device_count}")
    existing = os.environ.get("XLA_FLAGS", "").strip()
    if existing:
        # keep the operator's flags; ours only fill gaps
        ours = [f for f in flags if f.split("=")[0] not in existing]
        return (existing + " " + " ".join(ours)).strip()
    return " ".join(flags)


def recommended_env(host_device_count: Optional[int] = None,
                    ) -> Dict[str, str]:
    """The recommended launch environment for THIS machine (keys absent
    when unavailable — e.g. no tcmalloc installed)."""
    env: Dict[str, str] = {}
    tc = _find_tcmalloc()
    if tc is not None:
        env["LD_PRELOAD"] = tc
    env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = str(LARGE_ALLOC_THRESHOLD)
    env["TF_CPP_MIN_LOG_LEVEL"] = "4"
    env["XLA_FLAGS"] = xla_flags(host_device_count)
    return env


def render_shell(host_device_count: Optional[int] = None) -> str:
    """Source-able shell snippet of ``recommended_env`` (export lines)."""
    lines = ["# repro launch env (generated by repro.launch.env)"]
    for key, val in recommended_env(host_device_count).items():
        lines.append(f'export {key}="{val}"')
    if "LD_PRELOAD" not in recommended_env(host_device_count):
        lines.append("# (tcmalloc not found — LD_PRELOAD skipped)")
    return "\n".join(lines)


def validate(host_device_count: Optional[int] = None,
             ) -> List[Tuple[str, str, str]]:
    """Compare the live process env against the recommendation; returns
    (key, want, have) for every divergence ("" = unset)."""
    diffs: List[Tuple[str, str, str]] = []
    for key, want in recommended_env(host_device_count).items():
        have = os.environ.get(key, "")
        if key == "XLA_FLAGS":
            # flag-wise containment, not string equality — operators add
            # their own flags and order is irrelevant
            missing = [f for f in want.split() if f not in have.split()]
            if missing:
                diffs.append((key, want, have))
        elif have != want:
            diffs.append((key, want, have))
    return diffs


# one fixed path inside the checkout (never a temp name, pid or time):
# a cache that moves between runs is never hit
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and no
    other path is set here), else ``COMPILE_CACHE_DIR``.  Call before the
    first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        import jax
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def print_env(host_device_count: Optional[int] = None) -> None:
    """Print the snippet plus a divergence report for the live process."""
    print(render_shell(host_device_count))
    diffs = validate(host_device_count)
    if not diffs:
        print("# current process: all recommendations active")
    else:
        for key, want, have in diffs:
            print(f"# current process: {key} = {have!r} (want {want!r})")


if __name__ == "__main__":
    print_env()
