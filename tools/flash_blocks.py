"""Chip readings behind ``ops/flash.py::flash_plan`` (PR 41): each of the
three causal kernels alone, at each pair of blocks, on one chip.

    chiprun -- python tools/flash_blocks.py [--check] [--tree DIR]
        [--plan-only] [--chunk-elements N] [shape ...]

For each shape the cells run (batch, q heads, k/v heads, T, head dim) the
forward runs once for the residuals; then ``saturn_flash_fwd`` / ``_dq`` /
``_dkv`` are each timed alone at every (block_q, block_k) of the candidates
(host clock around ``block_until_ready``, median of 3 sets of 8 calls), and
the three under ``flash_plan``'s own blocks beside them. A candidate the
compiler refuses is a row with ``error``. ``roofline`` is the least time the
kernel's call could take (``perf/lib/flops.py::flash_call`` and
``roofline_share`` with ``perf/lib/peaks.json``: half the products at the
MXU's peak, or every operand across HBM once) over the time read.
``--plan-only`` times each kernel at the plan's own blocks and no other
(and the whole call only under a window, which has no rows of its own).
With ``--check`` the plan's output and gradients are compared with dense
float32 attention first. One JSON line a row on stdout and in
``chiprun_out/flash_blocks.jsonl``. ``--chunk-elements N`` reads the rows
under another budget for a chunk (``ops/flash.py::_CHUNK_ELEMENTS``).
``--tree DIR`` imports the package from
``DIR`` (a ``git archive`` of another commit); of a tree without
``flash_plan`` only the rows every tree has are read: ``flash_attention``
forward, and forward + backward, as the model calls it. No CPU branch:
without a TPU it exits 1.
"""
import functools
import itertools
import json
import math
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = (sys.argv[sys.argv.index("--tree") + 1] if "--tree" in sys.argv
        else REPO)
sys.path[:0] = [TREE, REPO]
from saturn_tpu.ops import flash  # noqa: E402
from perf.lib import bench, flops  # noqa: E402

#: name -> (batch, q heads, k/v heads, T, head dim, window), as the cells run
#: them (gpt2-medium at 4x its batch: a call there has to outlast the 0.14 ms
#: between two dispatches of this host)
SHAPES = {
    "gpt2-medium": (16, 16, 16, 1024, 64, None),
    "gptj": (4, 16, 16, 2048, 256, None),
    "ouro": (2, 16, 16, 4096, 128, None),
    "hybrid": (1, 15, 15, 8192, 128, None),
    "laguna": (1, 48, 8, 8192, 128, None),
    "laguna-window": (1, 64, 8, 8192, 128, 512),
}
CANDIDATES = (128, 256, 512, 1024)
MOST_SCORES = 512 * 1024           # a float32 score block of 2 MiB


def timed_ms(fn, *args, sets=3, calls=8):
    jax.block_until_ready(fn(*args))          # compile + warm
    out = []
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(calls):
            r = fn(*args)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(out)


def least_ms(kernel, b, h, t, d, peaks):
    """The least a kernel's call could take, as the benchmark's roofline
    reader counts it."""
    call = flops.flash_call(f"saturn_flash_{kernel}", b, h, t, d)
    return 1e3 * flops.roofline_share(
        call["flops"], call["bytes"], 1.0, peaks)["least_s"]


def dense(q, k, v):
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def check(name, h, kv, t, d, emit):
    """The plan's kernels on the chip against dense float32 attention, at
    two heads a k/v head of the shape (the reference holds (T, T) scores)."""
    h, kv = 2 * (h // kv), 2
    ks = jax.random.split(jax.random.PRNGKey(t + d), 4)
    q = jax.random.normal(ks[0], (1, h, t, d), jnp.float32)
    k, v = (jax.random.normal(x, (1, kv, t, d), jnp.float32) for x in ks[1:3])
    w = jax.random.normal(ks[3], (1, h, t, d), jnp.float32)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    got = jax.jit(jax.value_and_grad(
        functools.partial(loss, flash.flash_attention), argnums=(0, 1, 2)))(*low)
    ref = jax.jit(jax.value_and_grad(
        functools.partial(loss, dense), argnums=(0, 1, 2)))(
            *(x.astype(jnp.float32) for x in low))
    row = {"shape": name, "check": True,
           "loss_rel": float(abs(got[0] - ref[0]) / abs(ref[0]))}
    for n, g, r in zip(("dq", "dk", "dv"), got[1], ref[1]):
        g = g.astype(jnp.float32)
        row[n + "_rel"] = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    emit(row)


def whole(name, b, h, kv, t, d, window, emit):
    """``flash_attention`` as the model calls it: forward, and forward +
    backward (the three kernels and XLA's delta), under the tree's own plan."""
    ks = jax.random.split(jax.random.PRNGKey(t + d), 3)
    q = jax.random.normal(ks[0], (b, h, t, d)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(x, (b, kv, t, d)).astype(jnp.bfloat16)
            for x in ks[1:])

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    attend = functools.partial(flash.flash_attention, window=window)
    emit({"shape": name, "tree": TREE, "kernel": "whole",
          "fwd_ms": round(timed_ms(jax.jit(attend), q, k, v), 4),
          "grad_ms": round(timed_ms(
              jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, k, v), 4)})


def main(argv):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing measured", file=sys.stderr)
        return 1
    peaks = bench.load_peaks(dev.device_kind)
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/flash_blocks.jsonl", "a")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    if "--chunk-elements" in argv:   # another VMEM budget for a chunk
        flash._CHUNK_ELEMENTS = int(argv[argv.index("--chunk-elements") + 1])
    do_check = "--check" in argv
    names = [a for a in argv if a in SHAPES] or list(SHAPES)
    for name in names:
        b, h, kv, t, d, window = SHAPES[name]
        if do_check and window is None:
            check(name, h, kv, t, d, emit)
        if window is not None or "--plan-only" not in argv:
            whole(name, b, h, kv, t, d, window, emit)
        if window is not None or not hasattr(flash, "flash_plan"):
            continue
        ks = jax.random.split(jax.random.PRNGKey(t + d), 4)
        q, do = (jax.random.normal(x, (b * h, t, d)).astype(jnp.bfloat16)
                 for x in (ks[0], ks[3]))
        k, v = (jax.random.normal(x, (b * kv, t, d)).astype(jnp.bfloat16)
                for x in ks[1:3])
        scale = 1.0 / math.sqrt(d)
        plan = flash.flash_plan(t, d)
        kw = dict(scale=scale, causal=True, h=h, kv=kv,
                  interpret=flash._use_interpret())
        o, lse = jax.jit(functools.partial(
            flash._fwd, block_q=plan["fwd"]["block_q"],
            block_k=plan["fwd"]["block_k"], chunk=plan["fwd"]["chunk"],
            **kw))(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)[:, None, :]
        calls = {
            "fwd": (flash._fwd, (q, k, v)),
            "dq": (flash._dq, (q, k, v, do, lse, delta)),
            "dkv": (flash._dkv, (q, k, v, do, lse, delta)),
        }
        blocks = [c for c in CANDIDATES if t % c == 0]
        for kernel, (fn, args) in calls.items():
            mine = (plan[kernel]["block_q"], plan[kernel]["block_k"])
            least = least_ms(kernel, b, h, t, d, peaks)
            for bq, bk in itertools.product(blocks, blocks):
                if bq * bk > MOST_SCORES or (
                        "--plan-only" in argv and (bq, bk) != mine):
                    continue
                chunk = flash._chunk(t, d, bq if kernel == "dkv" else bk)
                row = {"shape": name, "tree": TREE, "kernel": kernel,
                       "block_q": bq, "block_k": bk, "plan": (bq, bk) == mine,
                       **flash._walk(t, bq, bk, chunk, kernel != "dkv")}
                try:
                    ms = timed_ms(jax.jit(functools.partial(
                        fn, block_q=bq, block_k=bk, chunk=chunk, **kw)), *args)
                    row.update(ms=round(ms, 4),
                               roofline=round(100 * least / ms, 2))
                except Exception as e:  # the compiler's refusal is the reading
                    row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
