"""Headline benchmark: flagship training throughput on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Metric: GPT-2-small causal-LM training throughput (tokens/sec) at batch 8 x
seq 512 — driver config #1 ("GPT-2-small on WikiText-103, single job, 1
device", BASELINE.md). The reference publishes no in-tree numbers
(SURVEY.md §6), so the baseline is self-measured: the first recorded run's
value per platform is stored in ``bench_baseline.json`` and later runs report
``vs_baseline = value / baseline`` (>1 is faster).

Chip or fail: a throughput is a statement about the accelerator, so without
a TPU this exits non-zero and prints no number — there is no CPU workload
under this metric's name. The timed region ends in a host read of the loss,
which waits for every queued step.
"""

from __future__ import annotations

import json
import os
import sys
import timeit


def _flops_per_step(cfg, batch_size: int, seq_len: int, n_params: int) -> float:
    """Training FLOPs per step: 6N per token + attention score/value terms
    (12·L·S·D per token), the standard MFU accounting."""
    tokens = batch_size * seq_len
    return tokens * (6.0 * n_params + 12.0 * cfg.n_layers * seq_len * cfg.d_model)


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: needs a TPU; JAX reports platform {dev.platform!r}")

    import jax.numpy as jnp
    import optax

    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    batch_size, seq_len = 8, 512
    n_warmup, n_timed = 3, 20
    spec = build_gpt2("gpt2-small", seq_len=seq_len)
    ds = make_lm_dataset(
        context_length=seq_len,
        batch_size=batch_size,
        vocab_size=spec.config.vocab_size,
        n_tokens=seq_len * batch_size * 16,
    )
    tx = optax.adamw(3e-4)

    def init_state():
        params = spec.init_fn(jax.random.PRNGKey(0))
        return {"params": params, "opt_state": tx.init(params)}

    # Fused head+loss when the model provides it (ops/ce.py) — the same
    # path the executors select for pretraining_loss tasks.
    loss_of_params = spec.fused_loss_fn or (
        lambda p, b: pretraining_loss(spec.apply_fn(p, b), b)
    )

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_of_params)(state["params"], batch)
        updates, new_opt = tx.update(grads, state["opt_state"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt_state": new_opt}, loss

    step = jax.jit(train_step, donate_argnums=(0,))
    state = jax.jit(init_state)()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state["params"]))
    batches = [jnp.asarray(ds.batch(i)) for i in range(8)]

    # compile + warmup (excluded from timing; SURVEY.md §7 "honest profiling").
    # Sync is a host read of the loss: it waits for every queued step.
    for _ in range(n_warmup):
        state, loss = step(state, batches[0])
    float(jax.device_get(loss))

    t0 = timeit.default_timer()
    for i in range(n_timed):
        state, loss = step(state, batches[i % len(batches)])
    float(jax.device_get(loss))
    dt = (timeit.default_timer() - t0) / n_timed

    tokens_per_sec = batch_size * seq_len / dt

    from saturn_tpu.utils.peaks import peak_flops

    achieved = _flops_per_step(spec.config, batch_size, seq_len, n_params) / dt
    mfu = achieved / peak_flops(dev)  # an unlisted device kind raises

    base_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_baseline.json"
    )
    key = f"gpt2s_train_tokens_per_sec_{dev.platform}"
    baseline = None
    if os.path.exists(base_path):
        with open(base_path) as f:
            baseline = json.load(f).get(key)
    if baseline is None:
        baseline = tokens_per_sec  # first run on this platform defines the baseline
        try:
            data = {}
            if os.path.exists(base_path):
                with open(base_path) as f:
                    data = json.load(f)
            data[key] = tokens_per_sec
            with open(base_path, "w") as f:
                json.dump(data, f, indent=1)
        except OSError:
            pass

    out = {
        "metric": "gpt2s_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / baseline, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mfu": round(mfu, 4),
    }
    if os.environ.get("SATURN_TPU_TSAN", "") == "1":
        # Stamp instrumented runs: traced locks/queues perturb the hot path,
        # so bench_guard refuses to gate on (or record) such a row.
        out["tsan"] = True
    print(json.dumps(out))


if __name__ == "__main__":
    main()
