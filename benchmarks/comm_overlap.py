"""Comm/compute overlap benchmark: serial vs overlapped, same shape.

Times the three overlapped lowerings this repo carries against their serial
twins on identical shapes, and proves the swap is free: each pair runs a
short SGD trajectory and the per-step losses must agree **bitwise** (the
overlap knobs reorder communication, never arithmetic):

  zero3          ``ops/collective_matmul.zero3_loss_and_grads`` with
                 ``prefetch`` off (layer k's gather on the critical path,
                 the GSPMD-like serial lowering) vs on (layer k+1's hops
                 ride under layer k's compute).
  pipeline_1f1b  ``ops/pipeline.staged_pipeline_loss_and_grads`` with
                 ``overlap`` off vs on (next tick's stage hop launched
                 before this tick's compute).
  ring           ``ops/ring.ring_attention`` with ``overlap`` off vs on
                 (kv block s+1's ppermute issued before folding block s).

Per pair the row reports min-of-reps step time, achieved FLOP/s and MFU
against a nominal peak (``SATURN_TPU_BENCH_PEAK_FLOPS``, default 1e12 —
the *ratio* is the signal; on CPU the absolute MFU is nominal-relative).
The headline is the pair with the best overlapped/serial speedup.

Overlap is a *scheduling* win: it needs hardware that can run a DMA and
compute concurrently. On a single-core CI host XLA executes every thunk
serially, so the measured overlapped time is bounded below by serial and
the double-buffer's extra copies show up as a small tax — the row records
``host_cores`` so readers (and the guard) can tell a serialized host from
a real regression. The ``priced`` section is the deterministic witness:
it traces the fsdp overlap grid point through shardflow and prices the
ledger serial vs overlapped with the active per-op-class factors — the
same repricing admission and the solver apply — which is strictly below
serial on every host. ``bench_guard.validate_overlap_row`` gates on all
of it: trajectories bitwise equal, measured overlapped time within noise
tolerance of serial (and strictly faster where the host can overlap),
MFU non-decreasing within the same tolerance, priced speedup > 1.

Run: ``python benchmarks/comm_overlap.py [--json] [--reps 10]``
"""

from __future__ import annotations

import argparse
import json
import os
import timeit


def _envf(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _time_min(fn, args, reps: int, warmup: int = 2) -> float:
    """Min-of-reps seconds for ``fn(*args)`` whose first output is a scalar
    loss (host-read to sync the device queue, as utils/timing does)."""
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.device_get(out[0])
    best = float("inf")
    for _ in range(reps):
        t0 = timeit.default_timer()
        out = fn(*args)
        jax.device_get(out[0])
        best = min(best, timeit.default_timer() - t0)
    return best


def _trajectory(fn, params, tokens, steps: int, lr: float = 0.1):
    """Per-step losses of a short SGD loop — the bit-identity witness."""
    import jax

    losses = []
    for _ in range(steps):
        loss, grads = fn(params, tokens)
        losses.append(float(jax.device_get(loss)))
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return losses


def _toy(L, DM, V, B, T, seed=0):
    import jax
    import jax.numpy as jnp

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = {
        "emb": jax.random.normal(k1, (V, DM)) * 0.02,
        "blocks": {
            "w": jax.random.normal(k2, (L, DM, DM)) * 0.1,
            "b": jnp.zeros((L, DM)),
        },
        "head": jax.random.normal(k3, (DM, V)) * 0.02,
    }
    tokens = jax.random.randint(k4, (B, T), 0, V)
    fns = dict(
        embed_fn=lambda other, tok: other["emb"][tok],
        block_fn=lambda lp, h: jnp.tanh(h @ lp["w"] + lp["b"]),
        head_fn=lambda other, h: h @ other["head"],
        loss_fn=lambda logits, tok: -jnp.mean(
            jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), tok[..., None], axis=-1
            )
        ),
    )
    # fwd+bwd dense-matmul flops: 3x the forward 2mnk per block matmul
    # plus the head projection (embedding lookup is a gather, not counted).
    flops = 6.0 * B * T * DM * DM * L + 6.0 * B * T * DM * V
    return params, tokens, fns, flops


def bench_zero3(reps, steps):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from saturn_tpu.ops.collective_matmul import zero3_loss_and_grads

    L, DM, V, B, T = 8, 256, 512, 32, 64
    params, tokens, fns, flops = _toy(L, DM, V, B, T)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    def make(prefetch):
        return jax.jit(lambda p, t: zero3_loss_and_grads(
            p, t, mesh=mesh, block_key="blocks", shard_axis="data",
            prefetch=prefetch, min_size=1, **fns))

    return _run_pair(make(False), make(True), params, tokens,
                     reps, steps, flops)


def bench_pipeline(reps, steps):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from saturn_tpu.ops.pipeline import staged_pipeline_loss_and_grads

    L, DM, V, B, T = 8, 256, 512, 32, 64
    params, tokens, fns, flops = _toy(L, DM, V, B, T)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("data", "stage"))

    def make(overlap):
        return jax.jit(lambda p, t: staged_pipeline_loss_and_grads(
            p, t, mesh=mesh, block_key="blocks", n_microbatches=8,
            schedule="1f1b", overlap=overlap, **fns))

    return _run_pair(make(False), make(True), params, tokens,
                     reps, steps, flops)


def bench_ring(reps, steps):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from saturn_tpu.ops.ring import ring_attention
    from jax import shard_map

    B, H, T, D, S = 4, 8, 1024, 64, 8
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(1, S), ("data", "seq"))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (B, H, T, D))
    k = jax.random.normal(kk, (B, H, T, D))
    v = jax.random.normal(kv, (B, H, T, D))

    def make(overlap):
        def attn(qq, kk_, vv):
            return ring_attention(
                qq, kk_, vv, axis_name="seq", axis_size=S, overlap=overlap
            )

        sm = shard_map(
            attn, mesh=mesh,
            in_specs=(P(None, None, "seq", None),) * 3,
            out_specs=P(None, None, "seq", None),
        )

        def loss_and_grads(qq, rest):
            kk_, vv = rest

            def L(x):
                return jnp.mean(sm(x, kk_, vv) ** 2)

            return jax.value_and_grad(L)(qq)

        return jax.jit(loss_and_grads)

    # causal attention fwd+bwd: ~3x fwd; fwd = 2 matmuls of 2*B*H*T^2*D / 2
    flops = 3.0 * 2.0 * 2.0 * B * H * T * T * D / 2.0
    return _run_pair(make(False), make(True), q, (k, v), reps, steps, flops)


def _run_pair(serial_fn, overlap_fn, params, tokens, reps, steps, flops):
    serial_tr = _trajectory(serial_fn, params, tokens, steps)
    overlap_tr = _trajectory(overlap_fn, params, tokens, steps)
    bit_identical = serial_tr == overlap_tr
    t_serial = _time_min(serial_fn, (params, tokens), reps)
    t_overlap = _time_min(overlap_fn, (params, tokens), reps)
    peak = _envf("SATURN_TPU_BENCH_PEAK_FLOPS", 1e12)
    return {
        "serial_ms": round(t_serial * 1e3, 3),
        "overlapped_ms": round(t_overlap * 1e3, 3),
        "speedup": round(t_serial / t_overlap, 4),
        "tflops_serial": round(flops / t_serial / 1e12, 4),
        "tflops_overlapped": round(flops / t_overlap / 1e12, 4),
        "mfu_serial": round(flops / t_serial / peak, 4),
        "mfu_overlapped": round(flops / t_overlap / peak, 4),
        "bit_identical": bit_identical,
        "loss_trajectory": [round(x, 8) for x in serial_tr],
    }


def priced_pair() -> dict:
    """Serial vs overlapped **static pricing** of one real executor program.

    Traces the fsdp overlap grid point through shardflow (the same
    ``trace_step`` -> ``interpret`` -> ``estimate_step_seconds`` path
    admission and the solver run) and prices the ledger both ways. Unlike
    the measured pairs this delta is deterministic everywhere: the
    per-op-class overlap factors discount the gather wire time, so the
    overlapped estimate is strictly below serial whenever the program
    communicates at all — the repricing the calibrated factors feed.
    """
    import jax

    from saturn_tpu import HParams, Task
    from saturn_tpu.analysis.shardflow.interp import interpret
    from saturn_tpu.analysis.shardflow.prior import estimate_step_seconds
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss
    from saturn_tpu.parallel.fsdp import FSDP

    seq, batch = 64, 8
    task = Task(
        get_model=lambda **kw: build_gpt2("test-tiny", seq_len=seq, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=seq, batch_size=batch, n_tokens=seq * batch * 2,
        ),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=2),
        save_dir="/tmp/comm_overlap_bench",
    )
    devices = jax.devices()[:8]
    traced = FSDP().trace_step(
        task, devices, {"remat": False, "offload": False, "overlap": True}
    )
    ledger = interpret(traced)
    serial_s = estimate_step_seconds(ledger, len(devices), overlap=False)
    over_s = estimate_step_seconds(ledger, len(devices), overlap=True)
    return {
        "serial_ms": round(serial_s * 1e3, 6),
        "overlapped_ms": round(over_s * 1e3, 6),
        "speedup": round(serial_s / over_s, 4),
    }


def run(reps: int = 10, steps: int = 3) -> dict:
    import jax

    pairs = {
        "zero3": bench_zero3(reps, steps),
        "pipeline_1f1b": bench_pipeline(reps, steps),
        "ring": bench_ring(reps, steps),
    }
    headline = max(pairs, key=lambda n: pairs[n]["speedup"])
    hp = pairs[headline]
    return {
        "metric": "comm_overlap",
        "platform": jax.devices()[0].platform,
        "devices": len(jax.devices()),
        "host_cores": os.cpu_count() or 1,
        "pairs": pairs,
        "headline": headline,
        "serial_ms": hp["serial_ms"],
        "overlapped_ms": hp["overlapped_ms"],
        "speedup": hp["speedup"],
        "mfu_serial": hp["mfu_serial"],
        "mfu_overlapped": hp["mfu_overlapped"],
        "bit_identical": all(p["bit_identical"] for p in pairs.values()),
        "priced": priced_pair(),
    }


def main():
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--steps", type=int, default=3,
                    help="SGD steps in the bit-identity trajectory")
    ap.add_argument("--json", action="store_true",
                    help="print only the JSON row")
    args = ap.parse_args()

    row = run(reps=args.reps, steps=args.steps)
    if not args.json:
        for name, p in row["pairs"].items():
            print(f"{name:14s} serial {p['serial_ms']:9.2f} ms  "
                  f"overlapped {p['overlapped_ms']:9.2f} ms  "
                  f"speedup {p['speedup']:.3f}x  "
                  f"bit_identical={p['bit_identical']}")
        print(f"headline: {row['headline']} {row['speedup']:.3f}x")
    print(json.dumps(row))
    return 0 if row["bit_identical"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
