"""Pipeline schedule overhead benchmark (VERDICT r1 item 8).

Times one pipelined train step against the dp baseline on the same device
count, at a medium-model scale where the embedding table and vocab head are
big enough to expose schedule overheads. Runs on whatever backend is up
(8-virtual-CPU mesh in CI; the chip through ``chiprun``).

Run: ``python benchmarks/pipeline_step.py [--preset gpt2-medium] [--seq 512]``
"""

from __future__ import annotations

import argparse
import os


def main():
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-medium")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--cpu", action="store_true", help="force the CPU backend")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss
    from saturn_tpu.parallel.dp import DataParallel
    from saturn_tpu.parallel.pp import Pipeline
    from saturn_tpu.utils.timing import time_train_step

    devices = jax.devices()
    n = 1 << (len(devices).bit_length() - 1)
    devices = devices[:n]
    print(f"backend={devices[0].platform} devices={n} preset={args.preset} "
          f"seq={args.seq} batch={args.batch}")

    task = Task(
        get_model=lambda **kw: build_gpt2(args.preset, seq_len=args.seq, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=args.seq, batch_size=args.batch,
            n_tokens=args.seq * args.batch * 4,
        ),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=4),
        save_dir="/tmp/pp_bench_ckpts",
    )

    results = {}
    configs = [("dp", DataParallel(), {"remat": False})]
    if n >= 2:
        configs += [
            ("pp s2", Pipeline(), {"stages": 2, "microbatches": 4, "remat": False}),
        ]
    if n >= 4:
        configs += [
            ("pp s4", Pipeline(), {"stages": 4, "microbatches": 8, "remat": False}),
        ]
    for label, tech, cfg in configs:
        bundle = tech.build(task, devices, cfg)
        state = bundle.init()
        batch = jax.device_put(task.get_dataset().batch(0), bundle.batch_sharding)
        dt = time_train_step(bundle.compiled, state, batch, n_timed=5, n_warmup=2)
        tput = args.batch * args.seq / dt
        results[label] = dt
        print(f"{label:8s} {dt*1e3:9.1f} ms/step  {tput:10.0f} tok/s  cfg={cfg}")

    if "dp" in results:
        for k, v in results.items():
            if k != "dp":
                print(f"{k} vs dp: {results['dp']/v:.2f}x")


if __name__ == "__main__":
    main()
