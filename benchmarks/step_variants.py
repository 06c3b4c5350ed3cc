"""A/B matrix for the config-#1 train step: attention x loss x scan-unroll.

One run produces every pending chip measurement for the MFU work
(VERDICT r2 item 1c): flash vs dense attention, fused vs logits
cross-entropy, and the layer-scan unroll factor (the round-3 trace showed
the scan's activation-stash dynamic-update-slices dragging MLP matmul
fusions to ~0.4-0.5 efficiency — unrolling lets XLA address the stash
statically at the cost of compile time).

Timing protocol matches bench.py: donated state, compile+warmup excluded,
queued steps with ONE host sync at the end of the timed region.

Run: ``python benchmarks/step_variants.py [--attentions flash dense]
[--losses fused logits] [--unrolls 1 4 12]``
Prints a markdown table for BASELINE.md; flags the fastest variant.
"""

from __future__ import annotations

import argparse
import itertools
import timeit


def time_variant(preset, batch, seq, attention, loss, unroll, n_timed=20):
    import jax
    import jax.numpy as jnp
    import optax

    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    spec = build_gpt2(
        preset, seq_len=seq, attention=attention, scan_unroll=unroll
    )
    ds = make_lm_dataset(
        context_length=seq, batch_size=batch,
        vocab_size=spec.config.vocab_size, n_tokens=seq * batch * 8,
    )
    tx = optax.adamw(3e-4)

    if loss == "fused":
        if spec.fused_loss_fn is None:
            # don't silently time the logits path under a 'fused' label
            raise ValueError(f"{preset} has no fused loss (moe/non-causal)")
        loss_of = spec.fused_loss_fn
    else:
        loss_of = lambda p, b: pretraining_loss(spec.apply_fn(p, b), b)

    def init_state():
        p = spec.init_fn(jax.random.PRNGKey(0))
        return {"params": p, "opt": tx.init(p)}

    def step(state, batch):
        l, g = jax.value_and_grad(loss_of)(state["params"], batch)
        up, opt = tx.update(g, state["opt"], state["params"])
        return {"params": optax.apply_updates(state["params"], up),
                "opt": opt}, l

    jstep = jax.jit(step, donate_argnums=(0,))
    state = jax.jit(init_state)()
    batches = [jnp.asarray(ds.batch(i)) for i in range(4)]
    t0 = timeit.default_timer()
    for _ in range(3):
        state, l = jstep(state, batches[0])
    float(jax.device_get(l))          # sync: see utils/timing.py
    compile_s = timeit.default_timer() - t0

    t0 = timeit.default_timer()
    for i in range(n_timed):
        state, l = jstep(state, batches[i % len(batches)])
    float(jax.device_get(l))
    dt = (timeit.default_timer() - t0) / n_timed
    del state
    return dt, compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-small")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--attentions", nargs="+", default=["flash", "dense"])
    ap.add_argument("--losses", nargs="+", default=["fused", "logits"])
    ap.add_argument("--unrolls", type=int, nargs="+", default=[1, 4, 12])
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("variant timing is only meaningful on the TPU")

    print(f"preset={args.preset} b{args.batch}x{args.seq} "
          f"({jax.devices()[0].device_kind})\n")
    print("| attention | loss | unroll | ms/step | tokens/s | compile s |")
    print("|---|---|---|---|---|---|", flush=True)
    best = None
    for attn, loss, unroll in itertools.product(
        args.attentions, args.losses, args.unrolls
    ):
        try:
            dt, compile_s = time_variant(
                args.preset, args.batch, args.seq, attn, loss, unroll
            )
            tps = args.batch * args.seq / dt
            row = (attn, loss, unroll, dt)
            if best is None or dt < best[3]:
                best = row
            print(f"| {attn} | {loss} | {unroll} | {dt*1e3:.1f} "
                  f"| {tps:,.0f} | {compile_s:.0f} |", flush=True)
        except Exception as e:
            print(f"| {attn} | {loss} | {unroll} | FAIL "
                  f"({type(e).__name__}: {str(e)[:60]}) | | |", flush=True)
    if best:
        print(f"\nfastest: attention={best[0]} loss={best[1]} "
              f"unroll={best[2]} at {best[3]*1e3:.1f} ms/step")


if __name__ == "__main__":
    main()
