"""Diagnostic (PR 24): do two multi-chip programs on disjoint blocks run side
by side from two threads of one process? Run each variant in its own process:

    chiprun --chips 4 -- sh -c 'for v in exec put compile "serial dp fsdp" \
        "real dp fsdp" "real fsdp fsdp"; do
        python tools/chip_diag_concurrent.py $v; echo "== $v rc=$?"; done'

``exec``/``put``/``compile`` are synthetic programs; ``real A B`` runs the
trials of technique A on chips [0:2] and of B on chips [2:4] from two threads
(what the trial runner does at size 2); ``serial A B`` runs the same trials
one after the other.
"""
import os
import sys
import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

variant = sys.argv[1]
devs = jax.devices()
assert devs[0].platform == "tpu" and len(devs) == 4, devs
blocks = [devs[0:2], devs[2:4]]
errors = []


def make(block, d=1024, k=8):
    mesh = Mesh(np.array(block), ("data",))
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(None, "data"))

    def step(w, x):  # x: (B, d) sharded over data; grad-like all-reduce
        y = jnp.tanh(x @ w)
        g = x.T @ y / x.shape[0]  # contraction over the sharded batch dim
        return w - 1e-3 * g, jnp.mean(y)

    def window(w, xs):
        return jax.lax.scan(step, w, xs)

    f = jax.jit(window, in_shardings=(rep, row), out_shardings=(rep, rep),
                donate_argnums=(0,))
    w = jax.device_put(jnp.eye(d, dtype=jnp.float32), rep)
    xs = np.random.default_rng(0).standard_normal((k, 64, d)).astype(np.float32)
    return f, w, xs, row


def worker(i, iters, put_each, compile_each):
    try:
        f, w, xs, row = make(blocks[i])
        staged = jax.device_put(xs, row)
        for it in range(iters):
            if compile_each and it % 10 == 0:
                f, _, _, _ = make(blocks[i], d=1024 + 128 * (it // 10 + 1))
                f2, w2, xs2, row2 = make(blocks[i], d=512 + 128 * (it // 10))
                w2, _ = f2(w2, jax.device_put(xs2, row2))
                f, w, xs, row = make(blocks[i])
                staged = jax.device_put(xs, row)
            if put_each:
                staged = jax.device_put(xs, row)
            w, loss = f(w, staged)
            if it % 10 == 9:
                float(jax.device_get(loss)[-1])
        print(f"worker {i}: {iters} windows ok", flush=True)
    except Exception:
        errors.append(traceback.format_exc())


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def real(i):
    import chip_smoke
    from saturn_tpu import library

    try:
        tech = library.retrieve(sys.argv[2 + i])()
        task = chip_smoke.make_task(f"diag-{i}", "gpt2-small", 512, 6, 16,
                                    "chip_smoke_out/diag")
        for rep in range(1):
            for config in tech.candidate_configs(task, 2):
                t0 = time.time()
                t = tech._try_config(task, blocks[i], config)
                print(f"{variant} {i} {tech.name} rep {rep} {config}: {t} "
                      f"({time.time() - t0:.1f}s)", flush=True)
                tech._bundles.clear()
    except Exception:
        errors.append(traceback.format_exc())


def one():
    """``one TECH PRESET SEQ BATCH ID [ID ...]``: every grid point of TECH on
    the block of the named device ids, alone in the process."""
    import chip_smoke
    from saturn_tpu import library

    library.register_default_library()
    tech = library.retrieve(sys.argv[2])()
    preset, seq, batch = sys.argv[3], int(sys.argv[4]), int(sys.argv[5])
    block = [devs[int(i)] for i in sys.argv[6:]]
    task = chip_smoke.make_task("diag-one", preset, seq, batch, 16,
                                "chip_smoke_out/diag")
    for config in tech.candidate_configs(task, len(block)):
        t0 = time.time()
        try:
            t = tech._try_config(task, block, config)
            print(f"one {sys.argv[2:]} {config}: {t} ({time.time() - t0:.1f}s)",
                  flush=True)
        except Exception as e:
            errors.append(traceback.format_exc())
            print(f"one {sys.argv[2:]} {config}: FAILED {e!r}"[:300], flush=True)
            break


t0 = time.time()
if variant == "one":
    one()
    threads = []
elif variant in ("real", "serial"):
    from saturn_tpu import library

    library.register_default_library()
    threads = [threading.Thread(target=real, args=(i,)) for i in range(2)]
    if variant == "serial":
        for t in threads:
            t.start()
            t.join()
        threads = []
else:
    args = {"exec": (300, False, False), "put": (300, True, False),
            "compile": (60, True, True)}[variant]
    threads = [threading.Thread(target=worker, args=(i, *args)) for i in range(2)]
for t in threads:
    t.start()
for t in threads:
    t.join()
print(f"variant {variant}: {len(errors)} error(s) in {time.time() - t0:.1f}s", flush=True)
for e in errors:
    print(e[-1500:], flush=True)
sys.exit(1 if errors else 0)
