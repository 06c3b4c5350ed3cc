"""Chip readings of the LFM2 cell's convolution mixer alone (PR 52): one
``Block._short_conv_mixer`` with its output projection at the cell's shape
(seq 8192 x batch 4, d 2048, 3 taps, bf16 compute over float32 parameters),
forward and forward + backward; and its elementwise middle alone (the two
gates and the taps: ``(C * conv3(B * u))`` from B, C, u already projected),
which is the part ISSUE 52 priced at its least traffic: 4 streams of 32768 x
2048 bf16 forward and 7 backward, 1.8 ms a layer and step at 819 GB/s.

    chiprun -- python tools/conv_mixer_alone.py

Host clock around ``block_until_ready``, median of 5 sets of 5 calls. The
whole mixer's least time is its four 2048 x 2048 products' (``4 d^2`` a token,
forward; three times that forward + backward) at the chip's bf16 peak. One JSON
line a row on stdout and in ``chiprun_out/conv_mixer_alone.jsonl``. No CPU
branch: without a TPU it exits 1.
"""
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from saturn_tpu.models import gpt2  # noqa: E402

PEAK_FLOPS, HBM_BYTES_PER_S = 197e12, 819e9
BATCH, SEQ, D, TAPS = 4, 8192, 2048, 3


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


class _Mixer(gpt2.Block):
    """The mixer and its ``W_out`` with nothing around them."""

    @gpt2.nn.compact
    def __call__(self, h):
        def dense(features, name):
            return gpt2.nn.Dense(features, dtype=self.cfg.dtype, use_bias=False,
                                 param_dtype=self.cfg.param_dtype, name=name)
        return dense(D, "attn_out")(self._short_conv_mixer(h, dense))


def middle(gate_in, gate_out, u, w):
    """The gates and the taps as the mixer computes them, float32 inside."""
    f32 = jnp.float32
    s = gate_in.astype(f32) * u.astype(f32)
    padded = jnp.pad(s, ((0, 0), (TAPS - 1, 0), (0, 0)))
    conv = sum(w[j] * padded[:, j:j + SEQ] for j in range(TAPS))
    return (gate_out.astype(f32) * conv).astype(jnp.bfloat16)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing measured", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/conv_mixer_alone.jsonl", "a")

    def say(**row):
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    key = jax.random.PRNGKey(0)
    cfg = gpt2.config_for("lfm2-8b-a1b", seq_len=SEQ)
    mixer = _Mixer(cfg, kind="conv")
    h = jax.random.normal(key, (BATCH, SEQ, D), jnp.bfloat16)
    params = jax.jit(mixer.init)(key, h)
    tokens = BATCH * SEQ
    products = 2.0 * tokens * 4 * D * D            # W_b, W_c, W_x, W_out
    fwd = jax.jit(mixer.apply)
    both = jax.jit(jax.grad(lambda p, x: jnp.sum(mixer.apply(p, x).astype(jnp.float32)),
                            argnums=(0, 1)))
    for name, fn, least in (("mixer_fwd", fwd, products / PEAK_FLOPS),
                            ("mixer_fwd_bwd", both, 3 * products / PEAK_FLOPS)):
        ms = timed_ms(fn, params, h)
        say(row=name, ms=ms, least_ms=1e3 * least, share_pct=100 * 1e3 * least / ms)
    b, c, u = (jax.random.normal(jax.random.fold_in(key, i), (BATCH, SEQ, D), jnp.bfloat16)
               for i in range(3))
    w = jax.random.normal(jax.random.fold_in(key, 9), (TAPS, D), jnp.float32)
    stream = tokens * D * 2                        # one bf16 stream of the rows
    mid = jax.jit(middle)
    mid_both = jax.jit(jax.grad(lambda *a: jnp.sum(middle(*a).astype(jnp.float32)),
                                argnums=(0, 1, 2, 3)))
    for name, fn, streams in (("middle_fwd", mid, 4), ("middle_fwd_bwd", mid_both, 7)):
        ms = timed_ms(fn, b, c, u, w)
        least = streams * stream / HBM_BYTES_PER_S
        say(row=name, ms=ms, least_ms=1e3 * least, times_least=ms / (1e3 * least))
    return 0


if __name__ == "__main__":
    sys.exit(main())
