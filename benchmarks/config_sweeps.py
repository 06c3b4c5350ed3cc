"""Staged-config sweeps #2-#4 (BASELINE.md "Targets", VERDICT r2 item 2).

One driver for the three staged configs between the single-job bench (#1,
bench.py) and the 16-job flagship (#5, examples/lm_sweep/driver.py):

- **#2** 4-job GPT-2-small LR sweep, DP executor only — meant for the real
  chip, where single-chip blocks make the makespan honest (tasks time-share
  nothing; the reference anchor is the 6-task LR×batch sweep of
  ``/root/reference/examples/wikitext103/WikiText103.py:62-71``).
- **#3** 8-job GPT-2-medium/large sweep, FSDP + pipeline executors.
- **#4** 12-job heterogeneous batch (three model families × sizes) with the
  offload executor in the mix (reference anchor: Spilled,
  ``/root/reference/saturn/library.py`` default registry).

Each run routes ``search`` + ``orchestrate`` through a metrics JSONL and
prints the rows BASELINE.md records: profiling wall, SPASE plan makespan,
realized orchestration wall, per-interval planned-vs-elapsed error, and
per-job samples/sec.

On the 8-device CPU mesh (``--platform cpu``) configs #3/#4 run at reduced
shapes — the host can't push gpt2-medium FLOPs; the run proves the
*mechanism* (solver, gang launch, executor schedules), while the real-chip
rows for medium/large capability come from ``memory_contract.py`` and
``bench.py``. Record shapes with the row; never compare across shapes.

Run: ``python benchmarks/config_sweeps.py --config 2            # real chip``
     ``python benchmarks/config_sweeps.py --config 3 --platform cpu``
"""

from __future__ import annotations

import argparse
import json
import os
import time


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--config", type=int, required=True, choices=[2, 3, 4])
    p.add_argument("--platform", choices=["default", "cpu"], default="default")
    p.add_argument("--interval", type=float, default=None,
                   help="scheduling interval seconds (default per config)")
    p.add_argument("--batch-count", type=int, default=None,
                   help="batches per task (default per config/platform)")
    p.add_argument("--metrics", default=None,
                   help="metrics JSONL path (default /tmp/configN_metrics.jsonl)")
    p.add_argument("--save-dir", default="/tmp/saturn_config_ckpts")
    return p.parse_args()


def build_tasks(config: int, cpu: bool, batch_count: int):
    """Task list + technique/chip restrictions for a staged config."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2, config_for
    from saturn_tpu.models.loss import pretraining_loss

    def lm_task(preset, bs, lr, name, seq=None, chip_range=None, **model_kw):
        ctx = seq or config_for(preset).seq_len
        vocab = config_for(preset).vocab_size
        return Task(
            get_model=lambda **kw: build_gpt2(
                preset, seq_len=ctx, **model_kw, **kw
            ),
            get_dataloader=lambda: make_lm_dataset(
                context_length=ctx, batch_size=bs, vocab_size=vocab,
                n_tokens=ctx * bs * max(batch_count, 8),
            ),
            loss_fn=pretraining_loss,
            hparams=HParams(lr=lr, batch_count=batch_count),
            chip_range=chip_range,
            name=name,
        )

    if config == 2:
        # 4 jobs = one searched base + 3 lr clones; DP only, 1-chip blocks.
        preset = "test-tiny" if cpu else "gpt2-small"
        seq = 64 if cpu else 512
        base = lm_task(preset, 8, 1e-3, f"c2-{preset}-lr0.001", seq=seq,
                       chip_range=[1])
        lrs = [3e-4, 1e-4, 3e-3]
        return [base], lrs, ["dp"], None

    if config == 3:
        # 8 jobs: 2 sizes × 2 batch sizes searched, ×2 lrs cloned;
        # FSDP + pipeline only, multi-chip blocks.
        if cpu:
            sizes = [("test-tiny", dict(seq=64)),
                     ("gptj-test-tiny", dict(seq=64))]
            batches = [4, 8]
        else:
            sizes = [("gpt2-medium", {}), ("gpt2-large", {})]
            batches = [4, 8]
        tasks = []
        for preset, kw in sizes:
            for bs in batches:
                tasks.append(lm_task(
                    preset, bs, 1e-3, f"c3-{preset}-bs{bs}-lr0.001",
                    chip_range=[2, 4], **kw,
                ))
        return tasks, [3e-4], ["fsdp", "pp"], None

    # config 4: 12 heterogeneous jobs, offload in the technique mix.
    if cpu:
        fams = [("test-tiny", dict(seq=64)),
                ("gptj-test-tiny", dict(seq=64)),
                ("moe-test-tiny", dict(seq=64))]
        batches = [2, 4]
    else:
        fams = [("gpt2-small", {}), ("gpt2-medium", {}),
                ("gpt2-small-moe8", {})]
        batches = [4, 8]
    tasks = []
    for preset, kw in fams:
        for bs in batches:
            tasks.append(lm_task(
                preset, bs, 1e-3, f"c4-{preset}-bs{bs}-lr0.001",
                chip_range=[1, 2, 4], **kw,
            ))
    return tasks, [3e-4], ["dp", "fsdp", "offload"], None


def summarize(metrics_path: str, search_wall: float, orch_wall: float,
              n_tasks: int):
    events = []
    with open(metrics_path) as f:
        for line in f:
            events.append(json.loads(line))
    solves = [e for e in events if e["kind"] == "solve"]
    intervals = [e for e in events if e["kind"] == "interval"]
    per_task = {}
    for e in events:
        if e["kind"] == "task_interval":
            per_task.setdefault(e["task"], []).append(e)
    completed = {e["task"] for e in events if e["kind"] == "task_completed"}

    print("\n== summary ==")
    print(f"tasks: {n_tasks} ({len(completed)} completed)")
    print(f"search wall: {search_wall:.1f}s  orchestration wall: {orch_wall:.1f}s")
    if solves:
        print(f"planned makespan (first solve): {solves[0]['makespan_s']:.1f}s "
              f"over {solves[0]['n_tasks']} tasks")
    for i, e in enumerate(intervals):
        err = e["elapsed_s"] / e["planned_s"] - 1 if e["planned_s"] else 0
        print(f"interval {i}: planned {e['planned_s']:.0f}s "
              f"elapsed {e['elapsed_s']:.1f}s ({err:+.0%}) "
              f"tasks={e['n_tasks']} failed={e['failed']}")
    print("\n| task | technique | samples/s (last) | per-batch s |")
    print("|---|---|---|---|")
    for name in sorted(per_task):
        last = per_task[name][-1]
        print(f"| {name} | {last['technique']} | {last['samples_per_sec']} "
              f"| {last['per_batch_s']:.3f} |")


def main():
    args = parse_args()
    cpu = args.platform == "cpu"
    if cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
            + " --xla_cpu_collective_call_terminate_timeout_seconds=600"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    import saturn_tpu
    from saturn_tpu import library

    library.register_default_library()
    batch_count = args.batch_count or (4 if cpu else 64)
    interval = args.interval or (30.0 if cpu else 60.0)
    metrics_path = args.metrics or f"/tmp/config{args.config}_metrics.jsonl"
    if os.path.exists(metrics_path):
        os.remove(metrics_path)

    base_tasks, clone_lrs, technique_names, _ = build_tasks(
        args.config, cpu, batch_count
    )
    os.makedirs(args.save_dir, exist_ok=True)
    for t in base_tasks:
        t.save_dir = args.save_dir

    print(f"config #{args.config} on {jax.devices()[0].platform} "
          f"({len(jax.devices())} devices), batch_count={batch_count}, "
          f"interval={interval}s, techniques={technique_names}")

    t0 = time.time()
    saturn_tpu.search(
        base_tasks, technique_names=technique_names, log=True,
        metrics_path=metrics_path,
    )
    search_wall = time.time() - t0

    tasks = list(base_tasks)
    for task in base_tasks:
        for lr in clone_lrs:
            tasks.append(task.clone(
                name=task.name.rsplit("-lr", 1)[0] + f"-lr{lr:g}", lr=lr
            ))
    for t in tasks:
        t.save_dir = args.save_dir

    t0 = time.time()
    saturn_tpu.orchestrate(
        tasks, log=True, interval=interval, metrics_path=metrics_path
    )
    orch_wall = time.time() - t0

    summarize(metrics_path, search_wall, orch_wall, len(tasks))


if __name__ == "__main__":
    main()
