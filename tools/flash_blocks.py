"""Chip readings behind ``ops/flash.py::flash_plan`` (PR 41) and
``window_plan`` (PR 50): each of the three kernels alone, at each pair of
blocks, on one chip.

    chiprun -- python tools/flash_blocks.py [--check] [--tree DIR]
        [--plan-only] [--chunk-elements N] [--windows W,W,...] [shape ...]

For each shape the cells run (batch, q heads, k/v heads, T, head dim) the
forward runs once for the residuals; then ``saturn_flash_fwd`` / ``_dq`` /
``_dkv`` are each timed alone at every (block_q, block_k) of the candidates
(host clock around ``block_until_ready``, median of 3 sets of 8 calls), and
the three under ``flash_plan``'s own blocks beside them. A candidate the
compiler refuses is a row with ``error``. ``roofline`` is the least time the
kernel's call could take (``perf/lib/flops.py::flash_call`` and
``roofline_share`` with ``perf/lib/peaks.json``: half the products at the
MXU's peak, or every operand across HBM once) over the time read.
``--plan-only`` times each kernel at the plan's own blocks and no other.
A shape with a window times ``saturn_swa_fwd`` / ``_dq`` / ``_dkv`` alone at
each of ``WINDOW_CANDIDATES`` (the side that stays, the walked side, and
whether the walk is the grid's, a chunk of one block, or the loop's inside a
chunk of ``_chunk``'s size) against ``perf/lib/flops_laguna.py::attn_call``'s
least time: the readings ``window_plan``'s threshold stands on.
``--windows 512,1024,..`` reads a window shape at each of those windows in
place of its own (where between two cells' windows the threshold lies).
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
import inspect
import itertools
import json
import math
import os
import statistics
import sys
import time
import types

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = (sys.argv[sys.argv.index("--tree") + 1] if "--tree" in sys.argv
        else REPO)
sys.path[:0] = [TREE, REPO]
from saturn_tpu.ops import flash  # noqa: E402
from perf.lib import bench, flops, flops_laguna  # noqa: E402

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
    "smallthinker-window": (4, 28, 4, 8192, 128, 4096),
}
CANDIDATES = (128, 256, 512, 1024)
#: under a window: (block of the side that stays, walked block, the walk).
#: "grid": a chunk of one block, the reached blocks a grid axis; "loop": the
#: chunk ``_chunk`` gives, walked inside the kernel
WINDOW_CANDIDATES = ((256, 256, "grid"), (512, 512, "grid"),
                     (256, 256, "loop"), (512, 256, "loop"),
                     (512, 512, "loop"), (1024, 512, "loop"))
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


def dense(q, k, v, window=None):
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    keep = jnp.tril(jnp.ones((t, t), bool))
    if window is not None:
        keep = keep & ~jnp.tril(jnp.ones((t, t), bool), -window)
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def check(name, h, kv, t, d, window, emit):
    """The plan's kernels on the chip against dense float32 attention (under
    the window's mask), at two k/v heads of the shape's group (one under a
    window, whose groups are the larger: the reference holds (T, T) scores)."""
    h, kv = (h // kv, 1) if window else (2 * (h // kv), 2)
    ks = jax.random.split(jax.random.PRNGKey(t + d), 4)
    q = jax.random.normal(ks[0], (1, h, t, d), jnp.float32)
    k, v = (jax.random.normal(x, (1, kv, t, d), jnp.float32) for x in ks[1:3])
    w = jax.random.normal(ks[3], (1, h, t, d), jnp.float32)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]

    def loss(fn, q, k, v):
        out = fn(q, k, v).astype(jnp.float32)
        return jnp.sum(out * w), out

    got = jax.jit(jax.value_and_grad(
        functools.partial(loss, functools.partial(
            flash.flash_attention, window=window)), argnums=(0, 1, 2),
        has_aux=True))(*low)
    ref = jax.jit(jax.value_and_grad(
        functools.partial(loss, functools.partial(dense, window=window)),
        argnums=(0, 1, 2), has_aux=True))(*(x.astype(jnp.float32) for x in low))
    # the loss is a sum of mean zero: at a short window its relative distance
    # says little (0.12 at Laguna's, PR 50); the output's own says it
    (got_loss, got_out), (ref_loss, ref_out) = got[0], ref[0]
    row = {"shape": name, "check": True, "window": window,
           "loss_rel": float(abs(got_loss - ref_loss) / abs(ref_loss)),
           "out_rel": float(jnp.linalg.norm(got_out - ref_out)
                            / jnp.linalg.norm(ref_out))}
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
    emit({"shape": name, "tree": TREE, "kernel": "whole", "window": window,
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
    windows = ([int(w) for w in argv[argv.index("--windows") + 1].split(",")]
               if "--windows" in argv else None)
    names = [a for a in argv if a in SHAPES] or list(SHAPES)
    shapes = [(name, *SHAPES[name][:5], w) for name in names
              for w in ([SHAPES[name][5]] if SHAPES[name][5] is None
                        or windows is None else windows)]
    # a tree from before PR 50 has no plan of a window's three kernels
    windowed = hasattr(flash, "_walk") and "window" in inspect.signature(
        flash._walk).parameters
    for name, b, h, kv, t, d, window in shapes:
        if do_check and (window is None or windowed):
            check(name, h, kv, t, d, window, emit)
        if window is not None or "--plan-only" not in argv:
            whole(name, b, h, kv, t, d, window, emit)
        if not hasattr(flash, "flash_plan") or (window is not None
                                                and not windowed):
            continue
        ks = jax.random.split(jax.random.PRNGKey(t + d), 4)
        q, do = (jax.random.normal(x, (b * h, t, d)).astype(jnp.bfloat16)
                 for x in (ks[0], ks[3]))
        k, v = (jax.random.normal(x, (b * kv, t, d)).astype(jnp.bfloat16)
                for x in ks[1:3])
        scale = 1.0 / math.sqrt(d)
        kw = dict(scale=scale, causal=True, h=h, kv=kv,
                  interpret=flash._use_interpret())
        kernels = ("fwd", "dq", "dkv")
        if window is None:
            plan = flash.flash_plan(t, d)
            blocks = [c for c in CANDIDATES if t % c == 0]
            candidates = {
                kernel: [(bq, bk, flash._chunk(t, d, bq if kernel == "dkv" else bk))
                         for bq, bk in itertools.product(blocks, blocks)
                         if bq * bk <= MOST_SCORES]
                for kernel in kernels}
            least = {kernel: least_ms(kernel, b, h, t, d, peaks)
                     for kernel in kernels}
        else:
            plan = flash.window_plan(t, d, window)
            kw["window"] = window
            sliding = types.SimpleNamespace(
                kinds=(flops_laguna.SLIDING,), heads=(h,), head_dim=d,
                n_kv_heads=kv, window=window)
            candidates = {
                kernel: [(*((walked, stays) if kernel == "dkv" else (stays, walked)),
                          flash._chunk(t, d, walked) if walk == "loop" else walked)
                         for stays, walked, walk in WINDOW_CANDIDATES]
                for kernel in kernels}
            least = {}
            for kernel in kernels:
                call = flops_laguna.attn_call(f"saturn_swa_{kernel}", sliding, b, t)
                least[kernel] = 1e3 * flops.roofline_share(
                    call["flops"], call["bytes"], 1.0, peaks)["least_s"]
        mine = {kernel: tuple(plan[kernel][x] for x in ("block_q", "block_k", "chunk"))
                for kernel in kernels}
        o, lse = jax.jit(functools.partial(
            flash._fwd, **dict(zip(("block_q", "block_k", "chunk"), mine["fwd"])),
            **kw))(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)[:, None, :]
        calls = {
            "fwd": (flash._fwd, (q, k, v)),
            "dq": (flash._dq, (q, k, v, do, lse, delta)),
            "dkv": (flash._dkv, (q, k, v, do, lse, delta)),
        }
        under = () if window is None else (window,)
        for kernel, (fn, args) in calls.items():
            # the plan's own first, then every candidate that is not it
            for bq, bk, chunk in [mine[kernel]] + [
                    c for c in candidates[kernel] if c != mine[kernel]]:
                if "--plan-only" in argv and (bq, bk, chunk) != mine[kernel]:
                    continue
                row = {"shape": name, "tree": TREE, "kernel": kernel,
                       "window": window, "block_q": bq, "block_k": bk,
                       "plan": (bq, bk, chunk) == mine[kernel],
                       **flash._walk(t, bq, bk, chunk, kernel != "dkv", *under)}
                try:
                    ms = timed_ms(jax.jit(functools.partial(
                        fn, block_q=bq, block_k=bk, chunk=chunk, **kw)), *args)
                    row.update(ms=round(ms, 4),
                               roofline=round(100 * least[kernel] / ms, 2))
                except Exception as e:  # the compiler's refusal is the reading
                    row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
