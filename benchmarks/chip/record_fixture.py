#!/usr/bin/env python3
"""Records the small profiler trace the harness tests reduce
(``fixtures/decode_steps.xplane.pb``): three granite-moe-3b decode
steps through ``serve_step_paged`` and ``flash_decode_paged`` on a short
KV slab, inside a ``bench.drain`` span, each step in a
``bench.decode_wave`` span, with a host pause in a ``bench.retrieve``
span between the second and the third.  Needs the chip.

  python benchmarks/chip/record_fixture.py <output.xplane.pb>
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import cell, xplane
    from repro.models import transformer as tf

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_fixture.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    cfg = cell.load_json(os.path.join(ROOT, "benchmarks", "chip", "configs",
                                      "granite-moe-3b.json"))
    layout = cell.load_layout(cfg)
    arch = layout.arch_config(cfg)
    params = layout.program_params(cfg, 1, dev)
    B, ps, blocks = 16, 16, 4
    slab = (arch.num_layers, B * blocks + 1, ps, arch.num_kv_heads,
            arch.resolved_head_dim)
    k = jnp.zeros(slab, jnp.bfloat16, device=dev)
    v = jnp.zeros(slab, jnp.bfloat16, device=dev)
    table = jnp.arange(B * blocks, dtype=jnp.int32).reshape(B, blocks)
    step = jax.jit(lambda p, k, v, lens, tok: tf.serve_step_paged(
        p, k, v, table, lens, {"token": tok, "live_rows": jnp.int32(B)},
        arch, kernel_mode="kernel"), donate_argnums=(1, 2))
    tok = jnp.zeros((B,), jnp.int32)
    lens = jnp.zeros((B,), jnp.int32)
    logits, k, v = step(params, k, v, lens, tok)       # compile
    jax.block_until_ready(logits)
    tmp = tempfile.mkdtemp()
    xplane.start(tmp)
    with jax.profiler.TraceAnnotation("bench.drain"):
        for t in range(1, 4):
            with jax.profiler.TraceAnnotation("bench.decode_wave"):
                logits, k, v = step(params, k, v, lens + t,
                                    jnp.argmax(logits, -1).astype(jnp.int32))
                jax.block_until_ready(logits)
            if t == 2:
                with jax.profiler.TraceAnnotation("bench.retrieve"):
                    time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
