"""One run of one cell: set-up (with the timed search), the window, the
reference check, the metrics.

The system under test is reached only through what a user calls:
``library.register_default_library()``, ``Task``/``HParams``,
``saturn_tpu.search``, ``saturn_tpu.orchestrate`` -- and, for the reference
check, the chosen technique's own ``execute`` (the call the engine makes). Everything that judges it lives
under ``perf/``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from perf.lib import bench, primed, refcheck
from perf.lib.clock import CompileClock

#: test-only: the platform a rehearsal may run on instead of a TPU. A run
#: under it reports no metric at all (a CPU number is never written under the
#: name of a device metric); it proves paths, arguments and control flow.
REHEARSAL_ENV = "PERF_REHEARSAL_PLATFORM"


def say(msg: str) -> None:
    print(f"perf: {msg}", flush=True)


class NotCorrect(Exception):
    """A check of the run did not hold; the run ends with ``correct: false``."""


# ------------------------------------------------------------------- device
def accelerator_devices(chips: int) -> List[Any]:
    """The ``chips`` TPU devices this run is for -- or no run at all."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = os.environ.get(REHEARSAL_ENV) or "tpu"
    if platform != want:
        raise SystemExit(f"perf: needs platform {want!r}; JAX reports {platform!r}")
    if want == "tpu" and len(devices) != chips:
        raise SystemExit(
            f"perf: the cell asks for {chips} chip(s), JAX reports {len(devices)}")
    return list(devices[:chips])


# --------------------------------------------------------------------- jobs
@dataclasses.dataclass
class Job:
    name: str
    seq: int
    batch: int
    lr: float
    share: float
    batch_count: int
    index: int

    @property
    def tokens_per_step(self) -> int:
        return self.seq * self.batch


def plan_jobs(traffic: Dict[str, Any], seconds: float) -> List[Job]:
    """The work of the window, fixed before the search and independent of
    anything the program measures: steps = rate x share x seconds, rounded
    to whole fused windows where the traffic file says so."""
    rate = float(traffic["steps_per_window_second"])
    unit = int(traffic.get("round_steps_to", 1))
    jobs = []
    for i, j in enumerate(traffic["jobs"]):
        steps = rate * float(j["share"]) * seconds
        count = max(unit, int(round(steps / unit)) * unit)
        jobs.append(Job(j["name"], int(j["seq"]), int(j["batch"]), float(j["lr"]),
                        float(j["share"]), count, i))
    return jobs


def _builder(cfg: Dict[str, Any]) -> Callable:
    module, _, attr = cfg["run"]["builder"].partition(":")
    return getattr(importlib.import_module(module), attr)


def reference_module(cfg: Dict[str, Any]):
    """The configuration's plain reference (``run.reference``): a module
    under ``perf/reference`` with ``arch_from_config``, ``seed_key``,
    ``program_params``, ``logits_of`` and ``train``."""
    return importlib.import_module(cfg["run"]["reference"])


def weight_seed(cfg: Dict[str, Any]) -> int:
    return int(cfg["run"].get("weight_seed", 0))


def make_task(cfg: Dict[str, Any], traffic: Dict[str, Any], job: Job, seed: int,
              save_dir: str, name: Optional[str] = None,
              batch: Optional[int] = None, batch_count: Optional[int] = None):
    """A ``Task`` of the cell's configuration and one job of its traffic.
    Data: the package's synthetic Zipf tokens from ``seed``. Weights: made by
    the benchmark (the reference's ``program_params``, on the device inside the
    technique's own jitted init) from the configuration's fixed
    ``run.weight_seed``, not from ``seed``: the key is a compile-time constant
    of the init program, so a weight seed that changed from run to run would
    recompile it in every run (12 s at GPT-J widths, measured) and let a
    second set of the same seeds find it cached."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.loss import pretraining_loss

    build = _builder(cfg)
    run = cfg["run"]
    ref = reference_module(cfg)
    arch = ref.arch_from_config(cfg, job.seq)
    batch = job.batch if batch is None else batch
    n_batches = int(traffic.get("dataset_batches", 16))
    key = ref.seed_key(weight_seed(cfg))

    def get_model(**kw):
        spec = build(run["preset"], seq_len=job.seq, **run.get("overrides", {}), **kw)
        return dataclasses.replace(
            spec, init_fn=lambda rng: ref.program_params(arch, key))

    return Task(
        get_model=get_model,
        get_dataloader=lambda: make_lm_dataset(
            context_length=job.seq, batch_size=batch,
            vocab_size=int(cfg["vocab_size"]),
            n_tokens=job.seq * batch * n_batches, seed=seed + job.index),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=job.lr,
                        batch_count=job.batch_count if batch_count is None else batch_count),
        chip_range=list(traffic["chip_range"]) if traffic.get("chip_range") else None,
        name=name or job.name,
        save_dir=save_dir,
    )


def interval_seconds(traffic: Dict[str, Any], seconds: float) -> float:
    return float(traffic["interval"]["window_fraction"]) * seconds


# ---------------------------------------------------------------------- run
class Run:
    """What the metric readers are given. Attributes are filled phase by
    phase; a reader that does not find what it reads returns None."""

    def __init__(self, cell: bench.Cell, seed: int, seconds: float, trace: bool,
                 t_process_start: float):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.traced = trace
        self.t_process_start = t_process_start
        self.rehearsal = bool(os.environ.get(REHEARSAL_ENV))
        self.jobs: List[Job] = plan_jobs(cell.traffic, seconds)
        self.tasks: List[Any] = []
        self.devices: List[Any] = []
        self.peaks: Optional[Dict[str, float]] = None
        self.clock: Optional[CompileClock] = None
        self.tmp = ""
        self.search: Dict[str, Any] = {}
        self.window: Dict[str, Any] = {}
        self.chosen: Dict[str, Dict[str, Any]] = {}
        self.trace: Optional[Dict[str, Any]] = None
        self.setup_s: Optional[float] = None
        self.notes: List[str] = []
        #: every number the reference check compared, beside its limit
        self.compared: Dict[str, Dict[str, Any]] = {}

    # -- what readers use
    def job(self, name: str) -> Job:
        return next(j for j in self.jobs if j.name == name)

    def events(self, phase: str, kind: str) -> List[Dict[str, Any]]:
        from saturn_tpu.utils import metrics

        path = os.path.join(self.tmp, f"{phase}.metrics.jsonl")
        if not os.path.exists(path):
            return []
        return list(metrics.read_events(path, kind=kind))

    def arch(self, job: Job):
        return reference_module(self.cell.config).arch_from_config(
            self.cell.config, job.seq)

    def memory(self) -> Dict[str, int]:
        """Peak bytes in use on the fullest chip so far in this process, and
        that chip's limit. ``timed_search`` and ``timed_window`` each keep the
        reading taken at their end (``search["memory"]``, ``window["memory"]``):
        the reference check, which builds the float32 reference on the same
        chip, comes after both and is in neither."""
        best = {"peak_bytes": 0, "bytes_limit": 0}
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = int(stats.get("peak_bytes_in_use", 0))
            if peak >= best["peak_bytes"]:
                best = {"peak_bytes": peak,
                        "bytes_limit": int(stats.get("bytes_limit", 0))}
        return best


# ------------------------------------------------------------------- phases
def set_up(run: Run) -> None:
    run.devices = accelerator_devices(run.cell.chips)
    kind = run.devices[0].device_kind
    if not run.rehearsal:
        run.peaks = bench.load_peaks(kind)
    from saturn_tpu import library
    from saturn_tpu.utils import profile_cache

    run.clock = CompileClock()
    cache = profile_cache.maybe_enable_persistent_compile_cache()
    say(f"device {kind} x {len(run.devices)}; XLA compile cache: {cache}")
    library.register_default_library()
    # Checkpoints (GBs each), event files and traces go outside the checkout
    # and are removed at exit; only the XLA compile cache stays in it.
    run.tmp = tempfile.mkdtemp(prefix="perf-run-")
    run.tasks = [
        make_task(run.cell.config, run.cell.traffic, j, run.seed,
                  os.path.join(run.tmp, "ckpts"))
        for j in run.jobs
    ]
    for j in run.jobs:
        say(f"job {j.name}: seq {j.seq} x batch {j.batch}, lr {j.lr}, "
            f"{j.batch_count} steps in the window")


def topology(run: Run):
    from saturn_tpu.core.mesh import SliceTopology

    return SliceTopology(list(run.devices))


def timed_search(run: Run) -> None:
    """A user's first sweep of these models: the profile cache is on, as by
    default, and is a new empty directory, so no run reads another's
    profiles; the XLA compile cache is as the checkout has it."""
    import jax
    import saturn_tpu

    traffic = run.cell.traffic
    events = os.path.join(run.tmp, "search.metrics.jsonl")
    profiles = os.path.join(run.tmp, "profile-cache")
    os.makedirs(profiles)
    before, t0 = run.clock.snapshot(), time.perf_counter()
    with jax.profiler.TraceAnnotation("perf.search"):
        stats = saturn_tpu.search(
            run.tasks, technique_names=list(traffic["technique_names"]),
            topology=topology(run), metrics_path=events, profile_cache=profiles)
    wall = time.perf_counter() - t0
    spent = run.clock.since(before)
    run.search = {"wall_s": wall, "stats": stats, "clock": spent,
                  "memory": run.memory()}
    say(f"search: wall {wall:.2f}s for {len(run.tasks)} job(s); trace "
        f"{spent['trace_s']:.1f}s + lower {spent['lower_s']:.1f}s + backend compile "
        f"{spent['backend_compile_s']:.1f}s ({spent['backend_compiles']:.0f}) + cache "
        f"retrieval {spent['cache_retrieval_s']:.1f}s ({spent['cache_hits']:.0f} hits), "
        f"thread-summed")
    say(f"search: {stats['trials_run']} trials, {stats['pruned']} pruned, "
        f"{stats['cache_hits']} profile-cache hits, {stats['errors']} errors, "
        f"{stats['fused_groups']} fused groups")
    for e in run.events("search", "trial_config"):
        outcome = (f"{e['per_batch_s'] * 1e3:.2f} ms/batch" if "per_batch_s" in e
                   else {k: str(e[k])[:160] for k in
                         ("infeasible", "memory_rejected", "error") if k in e})
        say(f"  trial {e['task']} @ {e['size']} {e['technique']} {e['config']}: {outcome}")
    if stats["cache_hits"]:
        raise NotCorrect(f"{stats['cache_hits']} profile-cache hit(s) in a new "
                         f"empty cache: the run read another run's profiles")
    # A grid point the chip's compiler refuses for memory arrives as
    # ``memory_rejected`` (PR 29); an ``error`` is a fault.
    faults = [e for e in run.events("search", "trial_config") if "error" in e]
    if faults:
        raise NotCorrect(f"{len(faults)} grid point(s) raised; first: "
                         f"{str(faults[0]['error'])[:300]}")
    n = len(run.devices)
    for t in run.tasks:
        if n not in t.feasible_strategies():
            raise NotCorrect(f"search found no feasible strategy for {t.name} "
                             f"on {n} chip(s)")
        s = t.strategies[n]
        run.chosen[t.name] = {"technique": s.executor.name, "params": dict(s.params),
                              "per_batch_s": float(s.per_batch_time)}
        say(f"search: {t.name} -> {s.executor.name} {s.params} at "
            f"{s.per_batch_time * 1e3:.2f} ms/batch")


def warm_up(run: Run) -> None:
    """What a mix of several intervals needs compiled before its window,
    inside ``setup_s``. An interval of n steps runs n // K fused windows and
    an n % K tail on the 1-step program, and one of fewer than K steps runs
    one partial window of n (K = 8, the mix's ``round_steps_to``): the search
    leaves only the K-step program of each task in the process, and how many
    steps a forecast gives an interval follows from the clock, so which of
    the others a run meets changes from run to run. So where the interval is
    shorter than the window (``interval.window_fraction`` < 1) each task's
    1-step program and its windows of 2..K-1 steps are built where the
    engine will look for them, in the chosen technique's bundle of the task
    (every program but a job's first is a persistent-cache hit in a primed
    checkout, and costs its trace and lowering). A mix of one interval of
    whole windows pays nothing."""
    traffic = run.cell.traffic
    k_full = int(traffic.get("round_steps_to", 1))
    if float(traffic["interval"]["window_fraction"]) >= 1.0 or k_full < 2:
        return
    n = len(run.devices)
    before, t0 = run.clock.snapshot(), time.perf_counter()
    built = []
    for t in run.tasks:
        s = t.strategies[n]
        bundle = s.executor.build(t, list(run.devices), dict(s.params))
        bundle.compiled
        built.append(f"{t.name} K=1")
        for k in range(2, k_full):
            bundle.fused_compiled(k)
            built.append(f"{t.name} K={k}")
    spent = run.clock.since(before)
    say(f"warm-up: {len(built)} program(s) in {time.perf_counter() - t0:.2f}s "
        f"(backend compiles {spent['backend_compiles']:.0f}, "
        f"{spent['backend_compile_s']:.1f}s; cache hits {spent['cache_hits']:.0f}): "
        + ", ".join(built))


def timed_window(run: Run) -> None:
    import jax
    import saturn_tpu

    traffic = run.cell.traffic
    events = os.path.join(run.tmp, "window.metrics.jsonl")
    trace_dir = os.path.join(run.tmp, "trace") if run.traced else None
    if trace_dir:
        # The harness starts the profiler itself: ``orchestrate(trace_dir=)``
        # starts it at JAX's defaults, which trace every Python call.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = run.clock.snapshot()
    run.setup_s = time.time() - run.t_process_start
    wall_t0, t0 = time.time(), time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("perf.window"):
            result = saturn_tpu.orchestrate(
                run.tasks, interval=interval_seconds(traffic, run.seconds),
                topology=topology(run), metrics_path=events,
                solver_time_limit=float(traffic["solver_time_limit"]))
        wall = time.perf_counter() - t0
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    spent = run.clock.since(before)
    run.window = {"wall_s": wall, "wall_t0": wall_t0, "wall_t1": wall_t0 + wall,
                  "clock": spent, "result": result, "trace_dir": trace_dir,
                  "memory": run.memory()}
    gib, at_search, at_window = 2.0 ** 30, run.search["memory"], run.window["memory"]
    say(f"memory: peak {at_search['peak_bytes'] / gib:.3f} GiB by the end of the search, "
        f"{at_window['peak_bytes'] / gib:.3f} GiB by the end of the window ("
        + ("the window's job set it" if at_window["peak_bytes"] > at_search["peak_bytes"]
           else "a search trial set it")
        + f"), of {at_window['bytes_limit'] / gib:.3f}")
    say(f"window: orchestrate wall {wall:.3f}s (asked for about {run.seconds:.0f}s); "
        f"inside it: backend compiles {spent['backend_compiles']:.0f} "
        f"({spent['backend_compile_s']:.2f}s), trace {spent['trace_s']:.2f}s, lower "
        f"{spent['lower_s']:.2f}s, cache retrieval {spent['cache_retrieval_s']:.2f}s")
    compiled = sorted(str(e.get("program")) for e in run.events("window", "compile")
                      if not e.get("cached"))
    say(f"window: programs compiled inside it: {compiled or 'none'}")
    check_window(run)


def saved_step(ckpt_path: str) -> int:
    """The step count a checkpoint holds, read alone."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from saturn_tpu.utils import checkpoint

    only = checkpoint.restore_sharded(
        ckpt_path, {"step": jax.ShapeDtypeStruct((), np.int32)},
        SingleDeviceSharding(jax.devices()[0]))
    return int(only["step"])


def check_window(run: Run) -> None:
    """Every job's checkpoint is whole (``checkpoint.verify``: every byte of
    every shard file against its CRC, every leaf's extents covered)
    and at its ``batch_count``, every recorded loss is finite, every gang's
    state lived on the devices of its planned block. What the leaves *hold*
    is compared where there is something to compare it with: in the
    reference check, on the checkpoint the same ``execute`` writes there
    (``refcheck.read_back``); the window's own state has left the chips by
    now (``orchestrate`` releases a completed job's)."""
    from saturn_tpu.core.mesh import Block
    from saturn_tpu.utils import checkpoint

    result = run.window["result"]
    if result["failed"] or set(result["completed"]) != {t.name for t in run.tasks}:
        raise NotCorrect(f"orchestrate did not complete every job: {result}")
    intervals = run.events("window", "task_interval")
    solves = run.events("window", "solve")
    if not solves:
        raise NotCorrect("no solve event: the plan cannot be checked")
    topo = topology(run)
    tokens = 0
    for t in run.tasks:
        job = run.job(t.name)
        t0 = time.perf_counter()
        step = saved_step(t.ckpt_path)  # joins a write still in flight
        if not checkpoint.verify(t.ckpt_path):
            raise NotCorrect(f"{t.name}: the checkpoint at {t.ckpt_path} does "
                             f"not verify (a shard file missing, torn or short)")
        say(f"job {t.name}: checkpoint verified and its step read in "
            f"{time.perf_counter() - t0:.1f}s")
        if step != job.batch_count:
            raise NotCorrect(f"{t.name}: checkpoint at step {step}, "
                             f"batch_count {job.batch_count}")
        tokens += step * job.tokens_per_step
        mine = [e for e in intervals if e["task"] == t.name]
        losses = [x for e in mine for x in e.get("losses", [])]
        if not all(math.isfinite(x) for x in losses) or not all(
                math.isfinite(e["loss"]) for e in mine):
            raise NotCorrect(f"{t.name}: non-finite loss")
        if not mine:
            raise NotCorrect(f"{t.name}: no interval was recorded")
        # each interval against the newest plan solved before it launched
        for e in mine:
            plans = [s for s in solves if s["ts"] <= e["ts_launch"]] or solves[:1]
            a = plans[-1]["plan"]["assignments"].get(t.name)
            if a is None:
                continue
            want = sorted(d.id for d in topo.block_devices(Block(a[1], a[2])))
            if e["devices"] != want:
                raise NotCorrect(f"{t.name}: state lived on devices {e['devices']}, "
                                 f"the planned block is {want}")
        say(f"job {t.name}: step {step}, {len(mine)} interval(s), loss "
            + (f"{losses[0]:.4f} -> {losses[-1]:.4f}" if losses else "not recorded"))
    run.window["tokens"] = tokens
    run.window["steps"] = sum(j.batch_count for j in run.jobs)


def reference_check(run: Run) -> bool:
    """perf/lib/refcheck.py on the first job of each distinct shape."""
    cfg, traffic = run.cell.config, run.cell.traffic
    want = traffic["reference_check"]
    sequences, steps = int(want["sequences"]), int(want["steps"])
    limits = refcheck.load_limits()
    ref = reference_module(cfg)
    n = len(run.devices)
    ok, seen = True, set()
    for t in run.tasks:
        t.release_live_state()
    gc.collect()
    for t in run.tasks:
        job = run.job(t.name)
        if (job.seq, job.batch) in seen:
            continue
        seen.add((job.seq, job.batch))
        t0 = time.perf_counter()
        who = f"{t.name}.refcheck"
        clone = make_task(cfg, traffic, job, run.seed,
                          os.path.join(run.tmp, "ref-ckpts"), name=who,
                          batch=sequences, batch_count=steps)
        batches = [clone.batch_at(i) for i in range(steps)]
        ref_losses, ref_logits, ref_state = refcheck.reference_side(
            ref, run.arch(job), weight_seed(cfg), batches, job.lr,
            devices=run.devices)
        t1 = time.perf_counter()
        s = t.strategies[n]
        sys_logits = refcheck.system_logits(clone, dict(s.params), batches[0])
        numbers = {"logits_rel_rms": refcheck.logits_error(ref_logits, sys_logits)}
        del ref_logits, sys_logits
        gc.collect()
        t2 = time.perf_counter()
        sys_losses, sys_state, read_back = refcheck.system_side(
            clone, s.executor, dict(s.params), run.devices, steps,
            os.path.join(run.tmp, "refcheck.metrics.jsonl"), run.seed)
        clone.clear_ckpt()
        t3 = time.perf_counter()
        numbers.update(read_back)
        numbers.update(refcheck.loss_errors(ref_losses, sys_losses))
        numbers.update(refcheck.state_errors(ref_state, sys_state, say))
        del ref_state, sys_state
        say(f"reference check {who}: reference {t1 - t0:.1f}s, program's logits "
            f"{t2 - t1:.1f}s, its steps and the state they left {t3 - t2:.1f}s, "
            f"the comparison {time.perf_counter() - t3:.1f}s")
        say(f"reference check {who}: {sequences} sequence(s) x {steps} steps under "
            f"{s.executor.name} {s.params}; reference losses "
            f"{[round(x, 5) for x in ref_losses]}, program losses "
            f"{[round(x, 5) for x in sys_losses]} ({time.perf_counter() - t0:.1f}s)")
        ok = refcheck.verdict(numbers, limits, say, who, run.compared) and ok
        gc.collect()
    return ok


# ----------------------------------------------------------------- tracing
def reduce_window_trace(run: Run) -> None:
    from perf.lib import trace_reduce

    path = trace_reduce.find_xplane(run.window["trace_dir"])
    if path is None:
        run.notes.append("the profiler wrote no .xplane.pb")
        return
    t0 = time.perf_counter()
    run.trace = trace_reduce.reduce_trace(path)
    # the trace's clock against the host's: the window annotation opened at
    # wall_t0
    run.trace["wall_offset_s"] = (run.window["wall_t0"]
                                  - run.trace["window_ns"][0] / 1e9)
    say(f"trace: {os.path.getsize(path) / 2**20:.1f} MiB reduced in "
        f"{time.perf_counter() - t0:.1f}s; {run.trace['n_devices']} device plane(s), "
        f"busy {run.trace['busy_s']:.3f}s of {run.trace['window_s']:.3f}s")


def label_gap(intervals: Sequence[Dict[str, Any]], start_s: float,
              end_s: float) -> str:
    """What the host was doing in an idle gap of the device, by the
    ``task_interval`` events that cover its middle (wall-clock seconds)."""
    mid = 0.5 * (start_s + end_s)
    for e in intervals:
        if e["ts_launch"] <= mid < e["ts_start"]:
            return f"launch of {e['task']}: restore, build, stage before its first step"
        if e["ts_start"] <= mid <= e["ts"]:
            return f"inside {e['task']}'s steps: host dispatch or staging"
    if intervals and mid < min(e["ts_launch"] for e in intervals):
        return "before the first launch: solve, forecast"
    if intervals and mid > max(e["ts"] for e in intervals):
        return "after the last step: checkpoint write and flush, teardown"
    return "between intervals: checkpoint snapshot, re-solve, forecast"


def idle_by_label(run: Run, device: str) -> Dict[str, float]:
    """Seconds of one chip's idle gaps by what the host was doing in them."""
    off = run.trace["wall_offset_s"]
    intervals = run.events("window", "task_interval")
    by_label: Dict[str, float] = {}
    for s, e in run.trace["devices"][device]["gaps"]:
        label = label_gap(intervals, s / 1e9 + off, e / 1e9 + off)
        by_label[label] = by_label.get(label, 0.0) + (e - s) / 1e9
    return by_label


def breakdown(run: Run) -> Optional[Dict[str, Any]]:
    """The heaviest device operations and the idle gaps by label, each the
    mean over the chips used; on several chips the one that was busy least
    (the chip that waited most) is said beside it."""
    if run.trace is None or not run.trace["devices"]:
        return None
    chips = sorted(run.trace["devices"])
    mean: Dict[str, float] = {}
    for dev in chips:
        for label, secs in idle_by_label(run, dev).items():
            mean[label] = mean.get(label, 0.0) + secs / len(chips)
    if len(chips) > 1:
        worst = run.trace["worst"]
        w = run.trace["devices"][worst]
        top = sorted(w["by_name"].items(), key=lambda kv: -kv[1])[:3]
        gaps = sorted(idle_by_label(run, worst).items(), key=lambda kv: -kv[1])[:3]
        busy = ", ".join("%.3fs" % run.trace["devices"][d]["busy_s"] for d in chips)
        say(f"per chip: busy {busy} (mean {run.trace['busy_s']:.3f}s); least busy "
            f"{worst}: idle {100 * (1 - w['busy_s'] / run.trace['window_s']):.2f} % of "
            f"the window, its heaviest ops {[(k, round(v, 3)) for k, v in top]}, its "
            f"idle gaps {[(k[:40], round(v, 3)) for k, v in gaps]}")
    return {
        "device_ops": [[k, v] for k, v in run.trace["ops"][:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(mean.items(), key=lambda kv: -kv[1])[:10]],
    }


# ------------------------------------------------------------------ results
def read_metrics(run: Run, entries: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    out = {}
    for m in entries:
        value = bench.load_reader(run.cell, m["name"])(run)
        if value is None:
            say(f"metric {m['name']}: nothing to read in this run, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, correct: bool, failed: int) -> Dict[str, Any]:
    # what the program held by the end of the window: the reference check,
    # which runs before this line is made, is not in it
    mem = run.window.get("memory") or run.memory()
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": mem["peak_bytes"]}
    line: Dict[str, Any] = {"correct": bool(correct), "attempted": len(run.jobs),
                            "failed": int(failed), "metrics": {}, "device": device}
    if run.rehearsal:
        # a rehearsal proves control flow; it reports no number
        line["rehearsal"] = True
    elif run.traced:
        if run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
        line["metrics"] = read_metrics(run, run.cell.per_layer)
        bd = breakdown(run)
        if bd is not None:
            line["breakdown"] = bd
    else:
        line["metrics"] = read_metrics(run, run.cell.end_to_end)
    # the numbers the reference check compared, each beside its limit: last
    line["compared"] = run.compared
    return line


def say_compared(run: Run) -> None:
    """Every number compared beside its limit, as the last lines of standard
    error (what is kept of a run that was not correct)."""
    sys.stdout.flush()
    for name, c in run.compared.items():
        print(f"perf: compared {name} = {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)


def main_run(workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, prime: Optional[str] = None,
             root: Optional[str] = None, cache_dir: Optional[str] = None) -> int:
    """One run; with ``prime`` (the marker's path) the priming child's: no
    window, no result, and at its end the marker that names what it read
    from and wrote to the compile cache in ``cache_dir``."""
    cell = bench.load_cell(workload, root)
    run = Run(cell, seed, seconds, trace, t_process_start)
    say(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}, "
        f"seed {seed}, {seconds:.0f}s window, trace {int(trace)}"
        + (", priming the XLA cache (no window, no result)" if prime else ""))
    correct, failed = True, 0
    try:
        set_up(run)
        try:
            timed_search(run)
            warm_up(run)
            if not prime:
                timed_window(run)
                if trace:
                    reduce_window_trace(run)
        except NotCorrect as e:
            say(f"NOT CORRECT: {e}")
            correct, failed = False, len(run.jobs)
        correct = correct and reference_check(run)
        if prime:
            if correct:
                primed.write(prime, cache_dir, t_process_start)
            return 0 if correct else 1
        if run.setup_s is None:
            run.setup_s = time.time() - t_process_start
        if not correct and not run.window.get("tokens"):
            # nothing was measured: no result to print
            say("the run failed before its window was measured; no result line")
            return 1
        line = result_line(run, correct, failed)
        for note in run.notes:
            say(f"note: {note}")
        say_compared(run)
        print(json.dumps(line), flush=True)
        return 0
    finally:
        try:
            from saturn_tpu.utils import checkpoint

            checkpoint.flush()
        except Exception:
            pass
        if run.tmp:
            shutil.rmtree(run.tmp, ignore_errors=True)
