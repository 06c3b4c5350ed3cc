#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that saturn_tpu still starts on the chip.

Drives the system's main path once through the entry points a user calls
(``library.register_default_library()``, ``Task``, ``saturn_tpu.search``,
``saturn_tpu.orchestrate``) with GPT-2-small at its full width and depth,
random weights from a seed, synthetic data from a seed, and checks what comes
out by the repo's own means.

    python chip_smoke.py            one chip: two jobs that do not fuse
                                    (seq 512 x batch 8, seq 1024 x batch 4)
                                    through search -> orchestrate
    python chip_smoke.py --chips 4  four chips of one host, and nothing of the
                                    one-chip phase: (a) one job under fsdp on
                                    all four against the same job on one chip,
                                    (b) three jobs side by side on the chips

There is no CPU branch: without a TPU (or with another number of chips than
asked for) the script exits non-zero before any phase and prints no result.
Any phase that raises ends the run non-zero. One process drives every chip.
Everything is written under ``chip_smoke_out/`` beside this file. Times and
rates on earlier lines are prints for a builder's notes, not a benchmark. The
last line of standard output is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chip_smoke_out")

PRESET = "gpt2-small"  # d 768, 12 layers, 12 heads, vocab 50257
# (name, seq_len, batch_size): shapes differ, so no two of them stack.
ONE_CHIP_JOBS = (("smoke-s512-b8", 512, 8), ("smoke-s1024-b4", 1024, 4))
ONE_CHIP_BATCHES = 30
FOUR_CHIP_JOBS = (
    ("gang-s512-b6", 512, 6),
    ("gang-s256-b6", 256, 6),
    ("gang-s512-b8", 512, 8),
)
FOUR_CHIP_BATCHES = 96
# Sub-mesh sizes the gangs of the four-chip phase may take (Task.chip_range).
# One chip each, because on this host (v5e 2x2, libtpu 0.0.34) a two-chip
# block that does not hold chip 0 cannot run: with the runtime's enhanced
# launch barrier the cores halt on an on-device assertion ("schecklt: Invalid
# logical z: enhanced-barrier-parent-phase-1"), and with the barrier switched
# off two programs on disjoint blocks deadlock. PERF.md, PR 24, has the
# diagnosis. The four-chip block is exercised by phase (a); the gangs here
# run side by side on single chips.
FOUR_CHIP_SIZES = (1,)
AGREE_SHAPE, AGREE_STEPS = (512, 8), 8  # (seq, batch) and steps of phase (a)
TECHNIQUES = ("dp", "fsdp")
#: |loss(4 chips) - loss(1 chip)| <= AGREE_RTOL * loss, at every step. bf16
#: matmuls, and the one-chip program takes the fused CE head (bf16 logits
#: stash) where the sharded one takes XLA's f32 logits.
AGREE_RTOL = 1e-2


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ------------------------------------------------------------------ device
def accelerator_devices(chips: int) -> List[Any]:
    """The ``chips`` TPU devices this run is for — or no run at all."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX reports platform {platform!r}"
        )
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: asked for {chips} chip(s), JAX reports {len(devices)}"
        )
    return devices


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit skips the backend compile and
    shows up as retrieval time instead)."""

    _KEYS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    }

    def __init__(self) -> None:
        import jax.monitoring

        self.totals = {v: 0.0 for v in self._KEYS.values()}
        self.totals["cache_hits"] = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        key = self._KEYS.get(event)
        if key is not None:
            self.totals[key] += secs

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        return {k: self.totals[k] - before[k] for k in self.totals}

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)


# -------------------------------------------------------------------- jobs
def make_task(name: str, preset: str, seq: int, batch: int, batch_count: int,
              out_dir: str, seed: int = 0,
              chip_range: Optional[Sequence[int]] = None):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2, config_for
    from saturn_tpu.models.loss import pretraining_loss

    vocab = config_for(preset).vocab_size
    return Task(
        get_model=lambda **kw: build_gpt2(preset, seq_len=seq, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=seq, batch_size=batch, vocab_size=vocab,
            n_tokens=seq * batch * 16, seed=seed,
        ),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=3e-4, batch_count=batch_count),
        chip_range=None if chip_range is None else list(chip_range),
        name=name,
        save_dir=os.path.join(out_dir, "ckpts"),
    )


def read_events(path: str, kind: str) -> List[Dict[str, Any]]:
    from saturn_tpu.utils import metrics

    return list(metrics.read_events(path, kind=kind))


# ------------------------------------------------------------------ search
def run_search(tasks, topo, technique_names: Sequence[str], metrics_path: str,
               clock: CompileClock) -> Dict[str, Any]:
    import saturn_tpu

    before, t0 = clock.snapshot(), time.perf_counter()
    stats = saturn_tpu.search(
        tasks, technique_names=list(technique_names), topology=topo,
        metrics_path=metrics_path, profile_cache=False,
    )
    wall = time.perf_counter() - t0
    spent = clock.since(before)
    compile_s = (spent["trace_s"] + spent["lower_s"]
                 + spent["backend_compile_s"] + spent["cache_retrieval_s"])
    say(f"search: wall {wall:.1f}s = compile {compile_s:.1f}s "
        f"(trace {spent['trace_s']:.1f} + lower {spent['lower_s']:.1f} + "
        f"backend {spent['backend_compile_s']:.1f} + cache retrieval "
        f"{spent['cache_retrieval_s']:.1f}, {spent['cache_hits']} "
        f"persistent-cache hits) + timed steps and the rest "
        + (f"{wall - compile_s:.1f}s" if compile_s <= wall else
           "— not separable: compile is summed over concurrent trial threads"))
    say(f"search: {stats['trials_run']} trials run, {stats['pruned']} pruned, "
        f"{stats['errors']} config errors, {stats['fused_groups']} fused groups")
    if stats["errors"]:
        raise SmokeFailure(
            f"{stats['errors']} trial config(s) raised; first: "
            f"{stats['first_error']}"
        )
    if stats["fused_groups"]:
        raise SmokeFailure("the jobs fused: this smoke is for separate gangs")
    static = [e for e in read_events(metrics_path, "trial_pruned")
              if e.get("reason") == "memlens_static"]
    if static:
        raise SmokeFailure(
            f"memlens pruned {len(static)} grid point(s) before lowering "
            f"(SAT-M001), e.g. {static[0]} — at this model size every config "
            f"fits the chip"
        )
    for t in tasks:
        sizes = sorted(t.feasible_strategies())
        if not sizes:
            raise SmokeFailure(f"search found no feasible strategy for {t.name}")
        for g in sizes:
            s = t.strategies[g]
            say(f"search: {t.name} @ {g} chip(s): {s.executor.name} "
                f"{s.params} won at {s.per_batch_time * 1e3:.2f} ms/batch")
    for e in read_events(metrics_path, "trial_config"):
        outcome = (f"{e['per_batch_s'] * 1e3:.2f} ms/batch"
                   if "per_batch_s" in e else
                   {k: e[k] for k in ("infeasible", "memory_rejected", "error")
                    if k in e})
        say(f"  trial {e['task']} @ {e['size']} {e['technique']} "
            f"{e['config']}: {outcome}")
    return stats


# ----------------------------------------------------------------- kernels
def kernel_config(tech, task, n_devices: int) -> Dict[str, Any]:
    """The first grid point of ``tech`` that pins flash attention. On one chip
    every config of dp and fsdp takes the fused CE head as well."""
    for config in tech.candidate_configs(task, n_devices):
        if config.get("attention") == "flash":
            return config
    raise SmokeFailure(
        f"{tech.name}: no flash-attention point in the grid for {task.name} — "
        f"flash_supported() dropped the kernel"
    )


def require_kernel_calls(hlo_text: str, what: str) -> Dict[str, int]:
    """Count the Pallas kernels in a compiled program; both families must be
    there as ``tpu_custom_call``s (their names come from ``ops/``)."""
    calls = [line for line in hlo_text.splitlines() if "tpu_custom_call" in line]
    counts = {
        family: sum(family in line for line in calls)
        for family in ("saturn_flash_", "saturn_ce_")
    }
    missing = [f for f, n in counts.items() if n == 0]
    if missing:
        raise SmokeFailure(
            f"{what}: compiled step has no tpu_custom_call for {missing} "
            f"({len(calls)} custom calls in all)"
        )
    return counts


def check_kernels(task, devices: Sequence[Any], metrics_path: str) -> None:
    """A config with the flash and fused-CE kernels was timed for this job,
    and the program that was timed holds both kernels."""
    strategy = task.strategies[len(devices)]
    tech = strategy.executor
    config = kernel_config(tech, task, len(devices))
    timed = [
        e for e in read_events(metrics_path, "trial_config")
        if e["task"] == task.name and e["technique"] == tech.name
        and e["size"] == len(devices) and e["config"] == config
    ]
    if not timed or "per_batch_s" not in timed[-1]:
        raise SmokeFailure(
            f"{task.name}: kernel config {config} of {tech.name} took no timed "
            f"steps: {timed[-1] if timed else 'no trial_config event'}"
        )
    # the sweep's own bundle and program (both cached by the technique)
    bundle = tech.build(task, devices, config)
    k = tech._profile_window(config)
    program = bundle.fused_compiled(k) if k > 1 else bundle.compiled
    counts = require_kernel_calls(program.as_text(), f"{task.name} {config}")
    winner = "won" if strategy.params == config else (
        f"lost to {strategy.params} at {strategy.per_batch_time * 1e3:.2f} ms")
    say(f"kernels: {task.name} {tech.name} {config} timed at "
        f"{timed[-1]['per_batch_s'] * 1e3:.2f} ms/batch (K={k}) and {winner}; "
        f"tpu_custom_calls in its compiled step: {counts}")


# ------------------------------------------------------------- orchestrate
def run_orchestrate(tasks, topo, interval: float, metrics_path: str,
                    clock: CompileClock) -> Dict[str, Any]:
    import saturn_tpu

    before, t0 = clock.snapshot(), time.perf_counter()
    result = saturn_tpu.orchestrate(
        tasks, interval=interval, topology=topo, metrics_path=metrics_path,
        solver_time_limit=10.0,
    )
    wall = time.perf_counter() - t0
    spent = clock.since(before)
    say(f"orchestrate: wall {wall:.1f}s, of it backend compile "
        f"{spent['backend_compile_s']:.1f}s and cache retrieval "
        f"{spent['cache_retrieval_s']:.1f}s")
    if result["failed"] or set(result["completed"]) != {t.name for t in tasks}:
        raise SmokeFailure(f"orchestrate did not complete every job: {result}")
    plan = read_events(metrics_path, "solve")[0]["plan"]
    say(f"plan: makespan {plan['makespan']:.2f}s")
    for name, (g, off, size, start, runtime) in sorted(
            plan["assignments"].items(), key=lambda kv: kv[1][3]):
        say(f"plan:   {name}: {g} chip(s), block [{off}:{off + size}], "
            f"start {start:.2f}s, runtime {runtime:.2f}s, "
            f"after {plan['dependencies'].get(name, [])}")
    return plan


def check_jobs(tasks, shapes: Dict[str, Tuple[int, int]], batch_count: int,
               metrics_path: str) -> None:
    """Every job trained ``batch_count`` steps and its loss went the right way."""
    from saturn_tpu.utils import checkpoint

    intervals = read_events(metrics_path, "task_interval")
    for t in tasks:
        step = int(checkpoint.load_arrays(t.ckpt_path)["step"])
        if step != batch_count:
            raise SmokeFailure(
                f"{t.name}: checkpoint step {step} != batch_count {batch_count}")
        mine = [e for e in intervals if e["task"] == t.name]
        losses = [x for e in mine for x in e["losses"]]
        if len(losses) != batch_count:
            raise SmokeFailure(
                f"{t.name}: {len(losses)} step losses for {batch_count} steps")
        if not all(math.isfinite(x) for x in losses):
            raise SmokeFailure(f"{t.name}: non-finite loss in {losses}")
        if losses[-1] > losses[0]:
            raise SmokeFailure(
                f"{t.name}: loss rose from {losses[0]} to {losses[-1]}")
        seq, batch = shapes[t.name]
        busy = sum(e["elapsed_s"] for e in mine)
        tokens_s = sum(e["batches"] for e in mine) * seq * batch / busy
        # MFU is the package's own figure (shardflow's per-step FLOPs over the
        # published peak of the device kind), weighted by interval time
        mfu = ("not measured" if not all("mfu" in e for e in mine) else
               f"{sum(e['mfu'] * e['elapsed_s'] for e in mine) / busy:.4f}")
        say(f"job {t.name}: step {step}, loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f}, {len(mine)} interval(s) under "
            f"{sorted({e['technique'] for e in mine})}, "
            f"{tokens_s:.0f} tokens/s, mfu {mfu} "
            f"(chip_smoke print, not a benchmark; first-window warm-up included)")


def check_memory(tasks, devices: Sequence[Any], metrics_path: str) -> None:
    """memlens's predicted peak beside what XLA compiled and what the device
    saw. A prediction that would prune (SAT-M001) a program that fits fails."""
    from saturn_tpu.analysis.memlens import passes as ml_passes

    capacity = ml_passes.hbm_capacity_bytes(list(devices))
    for t in tasks:
        rows = [e for e in read_events(metrics_path, "memlens_calibration")
                if e["task"] == t.name]
        if not rows:
            raise SmokeFailure(f"{t.name}: no memlens calibration was recorded")
        ratios = [e["predicted_bytes"] / e["compiled_bytes"] for e in rows
                  if e["compiled_bytes"] > 0]
        worst = max(rows, key=lambda e: e["predicted_bytes"])
        say(f"memory {t.name}: {len(rows)} programs, memlens predicted / XLA "
            f"compiled in [{min(ratios):.2f}, {max(ratios):.2f}]; largest "
            f"predicted peak {worst['predicted_bytes']} B beside compiled "
            f"{worst['compiled_bytes']} B ({worst['technique']} @ "
            f"{worst['size']}, K={worst['k']})")
        if capacity > 0:
            wrong = [e for e in rows
                     if e["predicted_bytes"] > ml_passes.OOM_MARGIN * capacity
                     and e["compiled_bytes"] <= 0.92 * capacity]
            if wrong:
                raise SmokeFailure(
                    f"{t.name}: memlens would prune {len(wrong)} program(s) "
                    f"that fit {capacity} B, e.g. {wrong[0]}")
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"memory device {d.id}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')} of "
            f"{stats.get('bytes_limit', 'not reported')} B (whole process)")


# ---------------------------------------------------------- one-chip phase
def one_chip_phase(devices: Sequence[Any], preset: str,
                   jobs: Sequence[Tuple[str, int, int]], batch_count: int,
                   out_dir: str, clock: CompileClock,
                   technique_names: Sequence[str] = TECHNIQUES,
                   interval: float = 20.0) -> None:
    from saturn_tpu import library
    from saturn_tpu.core.mesh import SliceTopology

    library.register_default_library()
    topo = SliceTopology(list(devices))
    tasks = [make_task(name, preset, seq, batch, batch_count, out_dir)
             for name, seq, batch in jobs]
    search_events = os.path.join(out_dir, "search.metrics.jsonl")
    run_events = os.path.join(out_dir, "orchestrate.metrics.jsonl")

    run_search(tasks, topo, technique_names, search_events, clock)
    for t in tasks:
        check_kernels(t, devices, search_events)
    run_orchestrate(tasks, topo, interval, run_events, clock)
    check_jobs(tasks, {n: (s, b) for n, s, b in jobs}, batch_count, run_events)
    check_memory(tasks, devices, search_events)


# --------------------------------------------------------- four-chip phase
def sharded_against_one_chip(devices: Sequence[Any], preset: str, seq: int,
                             batch: int, steps: int, out_dir: str) -> None:
    """(a) One job under fsdp on every chip, through the technique's own
    ``execute`` (the call the engine makes), against the same job and seed on
    one chip of the same process: the losses agree step by step."""
    from saturn_tpu import library
    from saturn_tpu.core.strategy import Strategy
    from saturn_tpu.utils import checkpoint, metrics

    tech = library.retrieve("fsdp")()
    events = os.path.join(out_dir, "agree.metrics.jsonl")
    losses: Dict[str, List[float]] = {}
    for label, block in (("one-chip", list(devices[:1])),
                         ("all-chips", list(devices))):
        task = make_task(f"agree-{label}", preset, seq, batch, steps, out_dir)
        config = tech.candidate_configs(task, len(block))[0]
        task.strategies[len(block)] = Strategy(
            tech, len(block), dict(config), runtime=0.0)
        task.select_strategy(len(block))
        t0 = time.perf_counter()
        with metrics.scoped(events):
            tech.execute(task, block, tid=0, override_batch_count=steps)
        checkpoint.flush()
        event = [e for e in read_events(events, "task_interval")
                 if e["task"] == task.name][-1]
        want = sorted(d.id for d in block)
        if event["devices"] != want:
            raise SmokeFailure(
                f"{task.name}: state lived on devices {event['devices']}, "
                f"its block is {want}")
        losses[label] = event["losses"]
        say(f"agree: {task.name} fsdp {config} on devices {want}: "
            f"{time.perf_counter() - t0:.1f}s with compile, losses "
            f"{[round(x, 4) for x in event['losses']]}")
    pairs = list(zip(losses["one-chip"], losses["all-chips"]))
    if len(pairs) != steps:
        raise SmokeFailure(f"agree: {len(pairs)} loss pairs for {steps} steps")
    worst = max(abs(a - b) / abs(a) for a, b in pairs)
    say(f"agree: largest relative difference over {steps} steps {worst:.2e} "
        f"(tolerance {AGREE_RTOL:.0e})")
    if not worst <= AGREE_RTOL:
        raise SmokeFailure(
            f"sharded and one-chip losses differ by {worst:.2e} > {AGREE_RTOL}")


def check_gangs(plan: Dict[str, Any], topo, metrics_path: str) -> None:
    """Every gang's state lived on exactly the devices of its planned block,
    and gangs the plan put side by side ran at the same time."""
    from saturn_tpu.core.mesh import Block

    runs: Dict[str, Dict[str, Any]] = {}
    for e in read_events(metrics_path, "task_interval"):
        runs.setdefault(e["task"], e)  # the first interval is the planned one
    origin = min(e["ts_launch"] for e in runs.values())
    blocks: Dict[str, set] = {}
    for name, (g, off, size, start, runtime) in plan["assignments"].items():
        want = sorted(d.id for d in topo.block_devices(Block(off, size)))
        e = runs[name]
        say(f"gang {name}: planned block [{off}:{off + size}] = devices {want}, "
            f"state lived on {e['devices']}; took its block at "
            f"t={e['ts_launch'] - origin:.2f}s, device work "
            f"{e['ts_start'] - origin:.2f}s..{e['ts'] - origin:.2f}s")
        if e["devices"] != want:
            raise SmokeFailure(
                f"{name}: state lived on devices {e['devices']}, planned block "
                f"is {want}")
        blocks[name] = set(want)
    names = sorted(blocks)
    side_by_side = 0
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            (_, _, _, a0, a_rt), (_, _, _, b0, b_rt) = (
                plan["assignments"][a], plan["assignments"][b])
            if blocks[a] & blocks[b] or min(a0 + a_rt, b0 + b_rt) <= max(a0, b0):
                continue  # one after the other in the plan
            side_by_side += 1
            ea, eb = runs[a], runs[b]
            # A gang holds its block from launch (its programs compile for
            # that block first) to the end of its device work. A serial
            # engine would launch the second gang after the first ended.
            held = min(ea["ts"], eb["ts"]) - max(ea["ts_launch"], eb["ts_launch"])
            work = min(ea["ts"], eb["ts"]) - max(ea["ts_start"], eb["ts_start"])
            say(f"gangs {a} and {b}: planned side by side on disjoint blocks; "
                f"held their blocks together for {held:.2f}s, device work "
                f"overlapped {work:.2f}s (negative: compiles of different "
                f"length pulled the work apart)")
            if held <= 0:
                raise SmokeFailure(
                    f"{a} and {b} were planned side by side but ran one after "
                    f"the other")
    say(f"gangs: {side_by_side} pair(s) planned side by side"
        + ("" if side_by_side else " — the solver ran the jobs one after another"))


def four_chip_phase(devices: Sequence[Any], preset: str,
                    jobs: Sequence[Tuple[str, int, int]], batch_count: int,
                    agree_shape: Tuple[int, int], agree_steps: int,
                    out_dir: str, clock: CompileClock,
                    technique_names: Sequence[str] = TECHNIQUES,
                    sizes: Optional[Sequence[int]] = None) -> None:
    from saturn_tpu import library
    from saturn_tpu.core.mesh import SliceTopology

    library.register_default_library()
    sharded_against_one_chip(devices, preset, *agree_shape, agree_steps, out_dir)

    topo = SliceTopology(list(devices))
    tasks = [make_task(name, preset, seq, batch, batch_count, out_dir,
                       chip_range=sizes)
             for name, seq, batch in jobs]
    search_events = os.path.join(out_dir, "gangs.search.metrics.jsonl")
    run_events = os.path.join(out_dir, "gangs.orchestrate.metrics.jsonl")
    run_search(tasks, topo, technique_names, search_events, clock)
    # one interval long enough for the whole plan: gangs that the plan puts
    # side by side are then launched side by side
    longest = sum(max(s.runtime for s in t.feasible_strategies().values())
                  for t in tasks)
    plan = run_orchestrate(tasks, topo, max(4.0 * longest, 20.0), run_events,
                           clock)
    check_jobs(tasks, {n: (s, b) for n, s, b in jobs}, batch_count, run_events)
    check_gangs(plan, topo, run_events)
    check_memory(tasks, devices, search_events)


# -------------------------------------------------------------------- main
def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs the four-chip phase and nothing of the one-chip phase")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "saturn_tpu")):
        raise SystemExit("chip_smoke: the saturn_tpu package is not beside me")
    devices = accelerator_devices(args.chips)

    from saturn_tpu.utils import profile_cache

    clock = CompileClock()
    shutil.rmtree(OUT_DIR, ignore_errors=True)  # never resume an older run
    os.makedirs(OUT_DIR)
    say(f"device {devices[0].device_kind} x {len(devices)}, output {OUT_DIR}")
    say("compile cache: "
        f"{profile_cache.maybe_enable_persistent_compile_cache()} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip_phase(devices, PRESET, ONE_CHIP_JOBS, ONE_CHIP_BATCHES,
                       OUT_DIR, clock, TECHNIQUES)
    else:
        four_chip_phase(devices, PRESET, FOUR_CHIP_JOBS, FOUR_CHIP_BATCHES,
                        AGREE_SHAPE, AGREE_STEPS, OUT_DIR, clock, TECHNIQUES,
                        FOUR_CHIP_SIZES)
    total = clock.snapshot()
    say(f"done in {time.perf_counter() - t0:.1f}s; whole run: backend compile "
        f"{total['backend_compile_s']:.1f}s, trace+lower "
        f"{total['trace_s'] + total['lower_s']:.1f}s, cache retrieval "
        f"{total['cache_retrieval_s']:.1f}s, {total['cache_hits']} "
        f"persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
