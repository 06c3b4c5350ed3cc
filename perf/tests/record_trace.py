"""Records the small ``.xplane.pb`` that ``test_trace_reduce.py`` reduces.
Run once on the chip (``chiprun -- python perf/tests/record_trace.py``); it
writes ``chiprun_out/small_trace.xplane.pb`` and prints what the trace holds.
Three steps of a tiny program that calls both Pallas kernel families and an
XLA matmul, with a 30 ms host sleep between steps (a known idle gap), under
the options the harness traces with."""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

from saturn_tpu.ops.ce import fused_linear_cross_entropy
from saturn_tpu.ops.flash import flash_attention


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 256, 64), jnp.bfloat16)
    x = jax.random.normal(key, (256, 128), jnp.bfloat16)
    w = jax.random.normal(key, (512, 128), jnp.float32)
    labels = jnp.arange(256, dtype=jnp.int32) % 512

    def loss(q, x, w):
        a = flash_attention(q, q, q).astype(jnp.float32).sum()
        return a * 1e-6 + fused_linear_cross_entropy(x, w, labels) + (x @ x.T).sum() * 1e-9

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(step(q, x, w))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perf.window"):
        for _ in range(3):
            jax.block_until_ready(step(q, x, w))
            time.sleep(0.03)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join("chiprun_out", "small_trace.xplane.pb"))
    print("size", os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:12]:
                print("     ", ev.name[:80], ev.start_ns, ev.duration_ns,
                      [(k, str(v)[:60]) for k, v in list(ev.stats)[:8]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
