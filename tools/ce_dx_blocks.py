"""Chip reading behind ``ops/ce.py``'s dx token block (PR 31): the fused head's
dx kernel alone, at each token block, on one chip.

    chiprun -- python tools/ce_dx_blocks.py

For each shape (tokens, d_model, vocab, mode) the forward runs once for the
residuals; then ``saturn_ce_dx`` alone is timed at every block of the row
(host clock around ``block_until_ready``, median of 5 sets of 10 calls),
asking the compiler for VMEM exactly as ``fused_linear_cross_entropy`` would
(``_dx_vmem_limit``). ``dx`` at every block is compared bit for bit with the
first block's. ``saturn_ce_dw`` and ``saturn_ce_fwd`` at the plan's own blocks
are timed beside it, and the whole call (forward and both backward kernels in
one program, as a step runs them: ``grad_ms``). One JSON line a row on stdout
and in ``chiprun_out/ce_dx_blocks.jsonl``. No CPU branch: without a TPU it
exits 1.
"""
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from saturn_tpu.ops import ce  # noqa: E402

#: (tokens, d_model, vocab, stash) -> dx token blocks to time
ROWS = [
    ((8192, 4096, 50400, False), (64, 128, 256, 512)),    # gptj-6b-1chip.steady
    ((8192, 2048, 49152, False), (256, 512)),             # ouro-2.6b-1chip
    ((2048, 4096, 50400, True), (128, 256, 512)),         # d 4096 under the stash threshold
    ((8192, 1024, 50257, False), (256, 512, 1024)),
    # gptj-6b-1chip.steady again, in the mode the trial runner asks the
    # compile about since PR 51 (a 0.77 GiB stash)
    ((8192, 4096, 50400, True), (128, 256, 512)),
]
PEAK_FLOPS, HBM_BYTES_PER_S = 197e12, 819e9


def timed_ms(fn, *args, sets=5, calls=10):
    jax.block_until_ready(fn(*args))          # compile + warm
    out = []
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(calls):
            r = fn(*args)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(out)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing measured", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/ce_dx_blocks.jsonl", "a")
    for (n, d, v, stash), dx_blocks in ROWS:
        plan = ce.ce_plan(n, d, v, stash=stash)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n + d), 3)
        x = (jax.random.normal(k1, (n, d)) * 0.5).astype(jnp.bfloat16)
        w = jax.random.normal(k2, (v, d), jnp.float32) * 0.02
        lab = jax.random.randint(k3, (n, 1), 0, v).astype(jnp.int32)
        g = jnp.full((n, 1), 1.0 / n, jnp.float32)
        vp = ce._padded_vocab(v, plan.blocks)
        passes = 1 if stash else 2
        matmul_ms = passes * 2 * n * vp * d / PEAK_FLOPS * 1e3

        fwd = jax.jit(lambda x_, w_: ce._fused_ce_fwd(
            x_, w_, lab, plan.blocks, v, False, stash, None))
        _, res = fwd(x, w)
        fwd_ms = timed_ms(lambda: fwd(x, w)[0])

        def bwd(blocks, limit, pick):
            return jax.jit(lambda res_, g_: ce._fused_ce_bwd(
                blocks, v, False, stash, limit, res_, g_)[pick])

        dw_ms = timed_ms(bwd(plan.blocks, plan.dx_vmem_limit, 1), res, g)
        # the whole call as a step runs it: forward and both backward kernels
        # in one program, the gradients of the mean loss
        whole = jax.jit(jax.grad(lambda x_, w_: jnp.sum(ce._fused_ce(
            x_, w_, lab, plan.blocks, v, False, stash, plan.dx_vmem_limit) * g),
            argnums=(0, 1)))
        grad_ms = timed_ms(whole, x, w)
        first = None
        for bn_dx in dx_blocks:
            blocks = plan.blocks[:4] + (bn_dx,)
            limit = ce._dx_vmem_limit(bn_dx, plan.bv, d, stash)
            fn = bwd(blocks, limit, 0)
            try:
                ms = timed_ms(fn, res, g)
            except Exception as e:  # the compiler's refusal is a reading too
                row = {"error": repr(e)[:300]}
            else:
                dx = np.asarray(fn(res, g).astype(jnp.float32))
                if first is None:
                    first = dx
                stream_ms = (n // bn_dx) * vp * d * 2 / HBM_BYTES_PER_S * 1e3
                row = {"dx_ms": round(ms, 3),
                       "bitwise_equal_to_first": bool((dx == first).all()),
                       "w_stream_ms_at_819GBps": round(stream_ms, 2),
                       "matmul_ms_at_peak": round(matmul_ms, 2),
                       "mxu_share_pct": round(100 * matmul_ms / ms, 1)}
            row = {"shape": [n, d, v], "mode": plan.mode, "bn_dx": bn_dx,
                   "plan_bn_dx": plan.bn_dx, "vmem_limit": limit,
                   "dx_vmem": ce._dx_vmem(bn_dx, plan.bv, d, stash), **row,
                   "dw_ms": round(dw_ms, 3), "fwd_ms": round(fwd_ms, 3),
                   "grad_ms": round(grad_ms, 3),
                   "device": dev.device_kind}
            line = json.dumps(row)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
