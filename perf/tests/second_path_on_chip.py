"""On the chip, not part of the benchmark's runs: the routed layer's exact
second path (``ops/moe.py::_masked_experts``) at the Laguna cell's shapes.

A router that sends every token's eight choices to held experts fills 131072
pairs where the cell's row buffer holds 36864: the step overflows and takes
the second path. The same step through a worst-case buffer (which no step can
overflow) is the yardstick: outputs and all five gradients are compared, the
counters are checked (``second_path`` 1 against 0, every pair held in both),
and both are timed. Usage:

    chiprun -- python3 perf/tests/second_path_on_chip.py [seed]

Prints one JSON line; exits 1 where the two paths differ by more than bf16's
rounding of different summation orders allows (2 %).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from saturn_tpu.ops import moe

T, D, E, HELD, K, F = 16384, 2048, 256, 32, 8, 512


def main(seed: int) -> int:
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    y = jax.random.normal(keys[0], (T, D), jnp.float32).at[:, 0].set(4.0)
    # lane 0 is 4 in every row and only the first eight experts read it
    router = (0.02 * jax.random.normal(keys[1], (D, E), jnp.float32)
              ).at[0].set(jnp.where(jnp.arange(E) < K, 2.0, 0.0))
    w_gate, w_up = (0.02 * jax.random.normal(k, (HELD, D, F), jnp.float32)
                    for k in keys[2:4])
    w_down = 0.02 * jax.random.normal(keys[4], (HELD, F, D), jnp.float32)
    probe = jax.random.normal(keys[5], (T, D), jnp.float32)
    plans = {"buffer": moe.routed_plan(T, E, HELD, K, impl="kernel"),
             "worst": moe.routed_plan(T, E, HELD, K, buffer=1e9, impl="kernel")}
    assert plans["buffer"].second_path and not plans["worst"].second_path

    def run(plan):
        def loss(*operands):
            out, stats = moe.routed_experts(*operands, plan=plan, scale=2.5)
            return jnp.sum(out.astype(jnp.float32) * probe), (out, stats)

        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        operands = (y.astype(jnp.bfloat16), router, w_gate, w_up, w_down)
        result = jax.block_until_ready(fn(*operands))       # compiles
        t0 = time.perf_counter()
        for _ in range(3):
            result = jax.block_until_ready(fn(*operands))
        return result, (time.perf_counter() - t0) / 3

    said, results = {"device": jax.devices()[0].device_kind, "seed": seed}, {}
    for name, plan in plans.items():
        ((_, (out, stats)), grads), seconds = run(plan)
        results[name] = [np.asarray(out, np.float32)] + [np.asarray(g, np.float32) for g in grads]
        said[name] = {"rows": plan.rows, "ms": 1e3 * seconds,
                      "second_path": int(stats["second_path"]),
                      "pairs_held": int(stats["pairs_held"])}
    names = ("out", "dy", "drouter", "dw_gate", "dw_up", "dw_down")
    said["rel_rms"] = {
        n: float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        for n, a, b in zip(names, results["buffer"], results["worst"])}
    ok = (said["buffer"]["second_path"] == 1 and said["worst"]["second_path"] == 0
          and said["buffer"]["pairs_held"] == said["worst"]["pairs_held"] == T * K
          and all(v < 0.02 for n, v in said["rel_rms"].items() if n != "drouter"))
    said["ok"] = ok
    print(json.dumps(said), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 0))
