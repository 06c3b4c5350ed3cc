"""Records the small four-plane ``.xplane.pb`` that ``test_collectives.py``
reduces. Run once on four chips (``chiprun --chips 4 -- python
perf/tests/record_trace4.py``); it writes
``chiprun_out/small_trace4.xplane.pb`` and prints what the trace holds. Three
steps of a tiny program with the collectives a ZeRO-3 step has (an
all-gather of a sharded weight, a gradient summed over the chips and kept
sharded, a ``ppermute`` ring step as the overlapped twin makes them), with a
30 ms host sleep between steps, under the options the harness traces with."""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main() -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != 4:
        raise SystemExit("needs four TPU chips")
    out = os.path.join("chiprun_out", "trace_probe4")
    shutil.rmtree(out, ignore_errors=True)
    mesh = Mesh(np.asarray(devices), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    key = jax.random.PRNGKey(0)
    w = jax.device_put(jax.random.normal(key, (2048, 1024), jnp.float32), rows)
    x = jax.device_put(jax.random.normal(key, (512, 2048), jnp.bfloat16), rows)

    def loss(w, x):
        h = x @ w.astype(jnp.bfloat16)          # the weight gathered, the batch sharded
        return jnp.mean(jnp.square(h.astype(jnp.float32)))

    def ring(x):
        return jax.lax.ppermute(x, "data", [(i, (i + 1) % 4) for i in range(4)])

    @jax.jit
    def step(w, x):
        g = jax.lax.with_sharding_constraint(jax.grad(loss)(w, x), rows)
        moved = jax.shard_map(ring, mesh=mesh, in_specs=P("data", None),
                              out_specs=P("data", None))(x)
        return w - 1e-3 * g, moved

    jax.block_until_ready(step(w, x))
    text = step.lower(w, x).compile().as_text()
    for word in ("all-gather", "reduce-scatter", "all-reduce", "collective-permute"):
        print(word, text.count(word))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perf.window"):
        for _ in range(3):
            w, x = step(w, x)
            jax.block_until_ready((w, x))
            time.sleep(0.03)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join("chiprun_out", "small_trace4.xplane.pb"))
    print("size", os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            if plane.name.startswith("/device:TPU:0") or line.name == "XLA Ops":
                for ev in events[:40]:
                    print("     ", ev.name[:160], ev.start_ns, ev.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main())
