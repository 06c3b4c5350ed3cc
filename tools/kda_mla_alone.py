"""Chip readings of the Ling cell's two new mechanisms alone (PR 45): the
delta rule with a decay a channel (``ops/kda.py``: the chunked scan, forward
and forward + backward) and latent attention through the flash kernels at
192 score lanes over 128 value lanes (``ops/flash.py``: forward, forward +
backward, and the same with v padded to 192 lanes), at the cell's shapes.

    chiprun -- python tools/kda_mla_alone.py

Host clock around ``block_until_ready``, median of 5 sets of 5 calls. A
latent-attention row says the least time ``perf/lib/flops_ling.py`` gives
the call and the share of it: the count behind ``mla_flash_roofline``
checked against a call timed alone (no share may read over 100 %). One JSON
line a row on stdout and in ``chiprun_out/kda_mla_alone.jsonl``. No CPU
branch: without a TPU it exits 1.
"""
import json
import os
import statistics
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perf.lib import flops_ling  # noqa: E402
from saturn_tpu.ops import flash, kda  # noqa: E402

PEAK_FLOPS, HBM_BYTES_PER_S = 197e12, 819e9
ARCH = SimpleNamespace(n_heads=32, head_dim=128, qk_nope=128, qk_rope=64, v_head=128)
SEQ = 8192


def timed_ms(fn, *args, sets=5, calls=5):
    jax.block_until_ready(fn(*args))          # compile + warm
    out = []
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(calls):
            r = fn(*args)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(out)


def least_ms(need):
    return 1e3 * max(need["flops"] / PEAK_FLOPS, need["bytes"] / HBM_BYTES_PER_S)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing measured", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/kda_mla_alone.jsonl", "a")

    def say(**row):
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    H, d = ARCH.n_heads, ARCH.head_dim
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(ks[0], (1, H, SEQ, d))) / d ** 0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (1, H, SEQ, d))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, H, SEQ, d)).astype(jnp.bfloat16)
    g = -5.0 * jax.nn.sigmoid(1.5 * jax.random.normal(ks[3], (1, H, SEQ, d)) - 3.9)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, H, SEQ)))
    operands = (q, k, v, g, beta)
    say(row="kda fwd", ms=timed_ms(jax.jit(kda.kda), *operands))
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda(*a)), argnums=(0, 1, 2, 3, 4)))
    say(row="kda fwd+bwd", ms=timed_ms(grad, *operands))

    qk = ARCH.qk_nope + ARCH.qk_rope
    mq, mk = (jax.random.normal(ks[i], (1, H, SEQ, qk)).astype(jnp.bfloat16) for i in (5, 6))
    mv = jax.random.normal(ks[7], (1, H, SEQ, ARCH.v_head)).astype(jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(flash.flash_attention(q, k, v).astype(jnp.float32))
    calls = {n: flops_ling.mla_flash_call(n, ARCH, 1, SEQ)
             for n in ("saturn_mla_fwd", "saturn_mla_dq", "saturn_mla_dkv")}
    fwd_ms = timed_ms(jax.jit(flash.flash_attention), mq, mk, mv)
    both_ms = timed_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), mq, mk, mv)
    say(row="saturn_mla_fwd", ms=fwd_ms, least_ms=least_ms(calls["saturn_mla_fwd"]),
        roofline_pct=100 * least_ms(calls["saturn_mla_fwd"]) / fwd_ms,
        plan=flash.flash_plan(SEQ, qk, d_v=ARCH.v_head))
    all_least = sum(map(least_ms, calls.values()))
    say(row="saturn_mla_fwd + _dq + _dkv", ms=both_ms, least_ms=all_least,
        roofline_pct=100 * all_least / both_ms)
    padded = jnp.pad(mv, ((0, 0),) * 3 + ((0, qk - ARCH.v_head),))
    say(row="the same with v padded to 192 lanes (saturn_flash_*): fwd",
        ms=timed_ms(jax.jit(flash.flash_attention), mq, mk, padded))
    say(row="the same with v padded to 192 lanes: fwd+bwd",
        ms=timed_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), mq, mk, padded))
    return 0


if __name__ == "__main__":
    sys.exit(main())
