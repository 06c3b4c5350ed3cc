"""Trial runner: profile every (task × sub-mesh size × technique) combination.

Reference: ``saturn/trial_runner/PerformanceEvaluator.py:21-115``. Same
semantics — fan the grid out, keep the **fastest feasible technique per
size** (``:101-115``), seed unsearched sizes with an infeasible dummy
(``:96-99``), scale per-batch time to total runtime (``:26``) — with two
TPU-native differences:

- Trials run as **threads on the host that drives the slice** instead of as
  Ray remote tasks: one Python process owns all chips, and concurrent trials
  of sub-mesh size ``g`` run on *disjoint* aligned blocks (the analog of the
  reference scheduling ``num_gpus=g`` remotes across the node,
  ``PerformanceEvaluator.py:74-84``). Timing is position-independent on the
  ICI ring — and DCN-correct for free: with power-of-two slice sizes, every
  aligned block of a given size has the same DCN-crossing status
  (``core/mesh.py``), so a profile measured on block 0 prices any block the
  solver may later pick, including the cross-slice collectives of
  larger-than-slice sizes. On the CPU test platform trials stay sequential — virtual
  devices share host cores, so concurrency would skew the measurements.
- Infeasible configs are rejected by XLA memory analysis inside each
  technique's ``search`` (see ``SPMDTechnique._fits_memory``) rather than
  try/except CUDA OOM probing.

Profiling cost is the most expensive phase of the whole pipeline (compile
dominates a trial; ~1 min upper bound each), so three layers keep the sweep
cheap (see ``docs/architecture.md`` "Profiling cost & caching"):

1. **Persistent profile cache** (``utils/profile_cache.py``): every grid
   point is looked up by content fingerprint before anything compiles and
   every trial outcome is written back, so a repeated ``search()`` over an
   unchanged task list performs zero trial executions.
2. **Cost-model pruning**: on grids of >= ``PRUNE_MIN_GRID`` sizes per
   (task, technique), only anchor sizes (min, max, one midpoint) are
   profiled; the rest are filled from an Amdahl-style fit
   ``t(g) = a + b/g`` as *interpolated* strategies (flagged on
   ``Strategy``). The solver still sees a complete per-size table, and the
   orchestrator's realized-feedback loop upgrades interpolated entries to
   measured ones as tasks actually run.
3. **Monotone infeasibility propagation**: sizes are profiled largest-first,
   and once XLA memory analysis rejects a technique at size ``g``
   (``technique.memory_monotone`` + the search report saying memory was the
   binding constraint), every smaller size — whose per-chip memory is the
   same or strictly higher — is skipped instead of compiled-to-fail.
"""

from __future__ import annotations

import logging
import os
import queue
import random
import threading
import time
import timeit
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

from saturn_tpu import library as lib
from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.core.strategy import Strategy
from saturn_tpu.utils import metrics, trace
from saturn_tpu.utils import profile_cache as pcache

logger = logging.getLogger("saturn_tpu")

DUMMY_RUNTIME = 1e6  # reference's unsearched-size sentinel (``:99``)

#: Anchor-size pruning engages only when a (task, technique) pair has at
#: least this many valid sizes — below it the anchors ARE the whole grid.
PRUNE_MIN_GRID = 4


def search(
    tasks: Sequence,
    technique_names: Optional[List[str]] = None,
    log: bool = False,
    topology: Optional[SliceTopology] = None,
    metrics_path: Optional[str] = None,
    trace_dir: Optional[str] = None,
    parallel_trials: Optional[int] = None,
    profile_cache: Any = None,
    prune: bool = True,
    trial_retries: int = 2,
    retry_backoff_s: float = 0.05,
) -> Dict[str, Any]:
    """Fill ``task.strategies`` for every task in place.

    ``technique_names=None`` uses the whole library (registering the built-in
    default library if the user registered nothing — the reference required
    explicit registration, ``WikiText103.py:53-54``). ``metrics_path``
    appends per-trial JSONL events, among them one ``metrics.span`` event per
    phase of every trial (``search`` > ``trial`` > ``trial.config`` >
    ``trial.build`` / ``.compile`` / ``.memory_check`` / ``.init`` /
    ``.stage`` / ``.timing``, and one ``compile`` event per XLA compile);
    ``trace_dir`` wraps the sweep in a jax.profiler trace that holds the
    device's ops under the same spans (``saturn.<name>``), with the Python
    tracer off. ``parallel_trials`` caps how many same-size trials
    run concurrently on disjoint blocks (default: 4 on accelerators, 1 on
    the CPU test platform where concurrency would skew timings).

    ``profile_cache``: ``None`` uses the env-configured persistent cache
    (default on; ``SATURN_TPU_PROFILE_CACHE=0`` disables), ``False`` turns
    caching off for this sweep, a path string uses that directory.
    ``prune`` toggles anchor-size cost-model pruning.

    ``trial_retries``: extra attempts for a trial whose technique *raises*
    (transient fleet flake — a device hiccup mid-compile, an injected
    crash); each retry backs off ``retry_backoff_s * 2^attempt`` seconds
    plus deterministic jitter and emits a ``trial_retry`` event. A clean
    infeasible verdict (memory analysis rejection) is a *result*, not a
    flake, and is never retried — retrying it would only re-pay the
    compile; conversely, without retries a transient crash would be
    cached as permanently infeasible.

    Returns sweep stats ``{"trials_run", "cache_hits", "pruned",
    "interpolated", "dispatch", "fused_groups", "errors", "first_error",
    "refusals_fresh", "refusals_replayed", "refusals_unbuilt"}`` —
    the online admission controller uses ``trials_run`` to distinguish warm
    (zero-trial) from cold arrivals. ``errors`` counts the candidate configs
    (and whole trials, past their retry budget) that *raised* instead of
    measuring or failing the memory check, and ``first_error`` is the repr
    of the first of them (None when ``errors == 0``): the sweep completes
    either way, but a kernel variant that could not lower is then a number
    the caller can assert on, not a line at INFO. A config the chip's
    compiler refuses for memory is not among them: it failed the memory
    check. ``refusals_fresh`` counts those the compiler refused in this
    sweep, ``refusals_replayed`` those a record of an earlier refusal
    answered without a compile (``utils/aot_cache``, "Refusal records"), and
    ``refusals_unbuilt`` those of the replayed whose verdict was found by what
    the point is made from, before anything was built
    (``utils/point_records``).
    """
    if log:
        logging.basicConfig(level=logging.INFO)
    cache = pcache.resolve(profile_cache)
    with metrics.scoped(metrics_path), trace.profile_trace(trace_dir), \
            metrics.span("search", n_tasks=len(tasks)):
        return _search_inner(
            tasks, technique_names, topology, parallel_trials, cache, prune,
            trial_retries=trial_retries, retry_backoff_s=retry_backoff_s,
        )


def _default_parallelism(topo: SliceTopology) -> int:
    platform = getattr(topo.devices[0], "platform", "cpu") if topo.devices else "cpu"
    return 4 if platform != "cpu" else 1


def _anchor_sizes(sizes: Sequence[int]) -> set:
    """min, max and one midpoint of the valid sizes: the three points an
    Amdahl-style fit needs, and the cheapest/most constrained ends of the
    grid (GSPMD's observation that per-size runtimes scale smoothly)."""
    ss = sorted(sizes)
    return {ss[0], ss[-1], ss[len(ss) // 2]}


def _fit_scaling_model(points: Sequence[Tuple[int, float]]):
    """Least-squares Amdahl fit ``t(g) = a + b/g`` over measured
    (size, per-batch seconds) points; degenerate fits clamp to the
    pure-serial / pure-parallel edge instead of going negative."""
    import numpy as np

    g = np.asarray([p[0] for p in points], dtype=float)
    t = np.asarray([p[1] for p in points], dtype=float)
    A = np.stack([np.ones_like(g), 1.0 / g], axis=1)
    try:
        (a, b), *_ = np.linalg.lstsq(A, t, rcond=None)
    except np.linalg.LinAlgError:
        a, b = float(t.mean()), 0.0
    if a < 0.0 or b < 0.0:
        if b < 0.0:  # "runtime grows with chips" noise -> flat (serial) model
            a, b = float(t.mean()), 0.0
        else:
            a, b = 0.0, float((t * g).mean())
    return lambda size: a + b / float(size)


class _Lane:
    """Per-(task, technique) sweep state: which sizes are resolved and how."""

    __slots__ = (
        "task", "name", "tech", "sizes", "keys", "done", "to_run", "to_fill",
        "infeasible_floor",
    )

    def __init__(self, task, name, tech, sizes):
        self.task = task
        self.name = name
        self.tech = tech
        self.sizes = sorted(sizes)
        self.keys: Dict[int, Optional[str]] = {}
        # size -> (feasible, params, per_batch_time, source)
        self.done: Dict[int, tuple] = {}
        self.to_run: List[int] = []
        self.to_fill: List[int] = []
        # Largest size rejected by XLA memory analysis (memory-monotone
        # techniques only): everything smaller needs at least as much
        # per-chip memory and is pruned without compiling.
        self.infeasible_floor: Optional[int] = None

    def pruned(self, g: int) -> bool:
        return self.infeasible_floor is not None and g < self.infeasible_floor


class _EtaTracker:
    """Running-average trial-time ETA, replacing the fixed ~1 min/trial log.

    Cache hits and pruned grid points cost ~0 and are excluded from the
    average; the ETA covers only the trials still waiting to compile."""

    def __init__(self, planned: int, hits: int, deferred: int):
        self.planned = planned
        self.hits = hits
        self.deferred = deferred
        self.completed = 0
        self.pruned = 0
        self.spent = 0.0
        self._lock = threading.Lock()

    def start_message(self) -> str:
        return (
            f"trial runner: {self.planned} trials to run "
            f"({self.hits} profile-cache hits, {self.deferred} grid points "
            f"deferred to the cost model; cold upper bound ~{self.planned:.0f} min)"
        )

    def trial_done(self, dt: float) -> str:
        with self._lock:
            self.completed += 1
            self.spent += dt
            remaining = max(self.planned - self.pruned - self.completed, 0)
            avg = self.spent / self.completed
            return (
                f"trial runner: {self.completed}/{self.planned - self.pruned} "
                f"trials done, avg {avg:.1f}s/trial, ETA {remaining * avg:.0f}s"
            )

    def trial_pruned(self) -> None:
        with self._lock:
            self.pruned += 1


def _search_inner(
    tasks, technique_names, topology, parallel_trials=None, cache=None,
    prune=True, trial_retries=2, retry_backoff_s=0.05,
) -> Dict[str, Any]:
    topo = topology if topology is not None else SliceTopology()
    if technique_names is None and not lib.registered_names():
        lib.register_default_library()
    classes = lib.retrieve(technique_names)
    techniques = [(cls.name if hasattr(cls, "name") else cls.__name__, cls()) for cls in classes]
    for _, tech in techniques:
        # Candidate grids may depend on the pool shape — e.g. the pipeline
        # executor only proposes cross-slice ``stage_major`` layouts when
        # the sweep's blocks can actually outgrow a slice.
        try:
            tech.topology = topo
        except Exception:
            pass  # plugin with __slots__/frozen surface: grid stays topology-blind

    update_lock = threading.Lock()

    # One lane per (task, technique): the unit pruning and interpolation
    # reason about (reference grid build, ``:86-91``).
    lanes: List[_Lane] = []
    # NB ``is not None``: ProfileCache defines __len__, so a still-empty
    # cache is falsy — a bare truthiness test would fingerprint the first
    # run with a blank topology signature and never hit again.
    topo_sig = pcache.topology_signature(topo) if cache is not None else ""
    # Trials profile whatever dispatch mode execute() will run (fused
    # K-step windows vs per-step — ``SPMDTechnique._prepare``), so the
    # mode is part of every cache key: a per-step profile recorded before
    # fused dispatch landed (or with a different window cap) must MISS, not
    # warm-start the sweep with numbers execution won't reproduce.
    dispatch = pcache.dispatch_signature()
    for task in tasks:
        sizes = topo.valid_sizes()
        if task.chip_range is not None:
            sizes = [s for s in sizes if s in task.chip_range]
        task_sig = None
        if cache is not None:
            try:
                # the one stretch of the search's own seconds that is over
                # 0.3 s in a cell of the benchmark, so the one with a name
                # (PR 39): the cache pass, the fill and the fusion proposal
                # are under 0.1 s together there and hold no span
                with metrics.span("search.fingerprint", task=task.name):
                    task_sig = pcache.task_signature(task)
            except Exception:
                logger.info("task %s not fingerprintable — caching off for it",
                            task.name, exc_info=True)
        for name, tech in techniques:
            lane = _Lane(task, name, tech, sizes)
            if task_sig is not None:
                for g in lane.sizes:
                    lane.keys[g] = pcache.fingerprint(
                        task_sig, name, g, topo_sig, dispatch
                    )
            lanes.append(lane)

    def install(
        lane: _Lane, g: int, params, per_batch: float, source: str,
        host_fraction: float = 0.0,
    ) -> None:
        """Fastest feasible technique per size wins (``:101-115``) —
        measured, cached and interpolated entries all compete.

        ``host_fraction`` feeds the solver's co-location term; interpolated
        entries pass the 0.0 default on purpose — a co-schedule decision
        needs a measured staging/compute split, not a fitted guess. The
        schedule-bubble fraction, by contrast, is analytic in the config
        (``config_bubble_fraction``), so every path — trial, cache hit,
        interpolated fill — recomputes it here identically."""
        total = per_batch * lane.task.total_batches  # reference ``:26``
        bubble = 0.0
        bf = getattr(lane.tech, "config_bubble_fraction", None)
        if callable(bf) and params:
            try:
                bubble = min(max(float(bf(params)), 0.0), 1.0)
            except Exception:
                bubble = 0.0
        with update_lock:
            cur = lane.task.strategies.get(g)
            if cur is None or not cur.feasible or total < cur.runtime:
                lane.task.strategies[g] = Strategy(
                    executor=lane.tech,
                    apportionment=g,
                    params=params,
                    runtime=total,
                    per_batch_time=per_batch,
                    interpolated=(source == "interpolated"),
                    cache_key=lane.keys.get(g),
                    host_fraction=float(host_fraction or 0.0),
                    bubble_fraction=bubble,
                )

    # Configs (or whole trials) that RAISED, as opposed to measuring slower
    # or not fitting: the sweep goes on, but the caller gets the count.
    errors = {"n": 0, "first": None}
    # Configs the chip's compiler refused for memory: by a compile, or from
    # the record of an earlier one (``utils/aot_cache``, "Refusal records").
    refusals = {"refusals_fresh": 0, "refusals_replayed": 0,
                "refusals_unbuilt": 0}

    def note_errors(n: int, first: Optional[str]) -> None:
        with update_lock:
            errors["n"] += n
            if errors["first"] is None:
                errors["first"] = first

    def note_memory_floor(lane: _Lane, g: int) -> None:
        if getattr(lane.tech, "memory_monotone", False):
            with update_lock:
                if lane.infeasible_floor is None or g > lane.infeasible_floor:
                    lane.infeasible_floor = g

    # ------------------------------------------------------------ cache pass
    # Consult the persistent profile cache for EVERY grid point before any
    # trial runs: hits — feasible or infeasible — cost a file read.
    n_hits = 0
    for lane in lanes:
        for g in lane.sizes:
            entry = cache.get(lane.keys.get(g)) if cache is not None else None
            if entry is None:
                continue
            n_hits += 1
            feasible = entry["feasible"]
            metrics.event(
                "profile_cache", hit=True, task=lane.task.name, size=g,
                technique=lane.name, feasible=feasible,
                source=entry.get("source", "trial"),
            )
            if feasible:
                hf = entry.get("host_fraction", 0.0)
                hf = float(hf) if isinstance(hf, (int, float)) else 0.0
                lane.done[g] = (True, entry["params"], entry["per_batch_time"],
                                entry.get("source", "trial"))
                install(lane, g, entry["params"], entry["per_batch_time"],
                        "cache", host_fraction=hf)
            else:
                lane.done[g] = (False, None, None, entry.get("source", "trial"))
                if entry.get("memory_infeasible"):
                    note_memory_floor(lane, g)

    # -------------------------------------------------------- pruning split
    # Uncached grid points either run for real (anchors, or everything when
    # pruning is off / the grid is small) or wait for the cost-model fill.
    for lane in lanes:
        missing = [g for g in lane.sizes if g not in lane.done]
        if prune and len(lane.sizes) >= PRUNE_MIN_GRID:
            anchors = _anchor_sizes(lane.sizes)
            lane.to_run = [g for g in missing if g in anchors]
            lane.to_fill = [g for g in missing if g not in anchors]
        else:
            lane.to_run = missing

    eta = _EtaTracker(
        planned=sum(len(l.to_run) for l in lanes),
        hits=n_hits,
        deferred=sum(len(l.to_fill) for l in lanes),
    )
    logger.info("%s", eta.start_message())

    workers = parallel_trials if parallel_trials is not None else _default_parallelism(topo)

    def run_trial(tid, lane: _Lane, g: int, block):
        # the ``trial`` event is this span: same fields, plus its start
        with metrics.span("trial", task=lane.task.name, size=g,
                          technique=lane.name) as sp:
            _run_trial(sp, tid, lane, g, block)

    def _run_trial(sp, tid, lane: _Lane, g: int, block):
        devices = block.devices_of(topo.devices)
        task, name, tech = lane.task, lane.name, lane.tech
        if cache is not None and lane.keys.get(g):
            metrics.event("profile_cache", hit=False, task=task.name, size=g,
                          technique=name)
        t0 = timeit.default_timer()
        params = per_batch_time = None
        attempt = 0
        while True:
            try:
                params, per_batch_time = tech.search(task, devices, tid)
                break
            except Exception as e:  # a broken trial must not kill the sweep (``:27-28``)
                from saturn_tpu.analysis.jax_lint import ShardingLintError

                if isinstance(e, ShardingLintError):
                    # Static sharding-lint refusal is deterministic — the
                    # rule emits the same illegal spec on every retry, so
                    # burning the backoff budget buys nothing. Record the
                    # file:line diagnostics and mark the size infeasible.
                    logger.info(
                        "trial (%s, g=%d, %s): sharding lint refused: %s",
                        task.name, g, name, e,
                    )
                    params, per_batch_time = None, None
                    break
                if attempt >= max(0, trial_retries):
                    logger.warning(
                        "trial (%s, g=%d, %s) raised on attempt %d "
                        "(budget exhausted): %r",
                        task.name, g, name, attempt + 1, e,
                    )
                    note_errors(1, f"{name} g={g}: {e!r}")
                    params, per_batch_time = None, None
                    break
                # Exponential backoff with deterministic jitter — seeded per
                # (trial, attempt) so concurrent lanes desynchronize but runs
                # stay reproducible.
                delay = retry_backoff_s * (2 ** attempt)
                jitter = random.Random(
                    f"{task.name}:{g}:{name}:{attempt}"
                ).random()
                delay *= 1.0 + jitter
                metrics.event(
                    "trial_retry", task=task.name, size=g, technique=name,
                    attempt=attempt + 1, backoff_s=round(delay, 6),
                    error=repr(e),
                )
                logger.info(
                    "trial (%s, g=%d, %s) raised (attempt %d/%d), retrying "
                    "in %.3fs: %r",
                    task.name, g, name, attempt + 1, trial_retries + 1,
                    delay, e,
                )
                time.sleep(delay)
                attempt += 1
        dt = timeit.default_timer() - t0
        report = None
        reporter = getattr(tech, "search_report", None)
        if callable(reporter):
            report = reporter(task.name, g)
        if report and report.get("errors"):
            note_errors(int(report["errors"]), report.get("first_error"))
        if report:
            with update_lock:
                for k in refusals:
                    refusals[k] += int(report.get(k, 0))
        if params is None or per_batch_time is None:
            memory_bound = bool(report and report.get("memory_infeasible"))
            logger.info("trial (%s, g=%d, %s): infeasible%s", task.name, g, name,
                        " (memory)" if memory_bound else "")
            sp.set(feasible=False, memory_infeasible=memory_bound)
            with update_lock:
                lane.done[g] = (False, None, None, "trial")
            if memory_bound:
                note_memory_floor(lane, g)
            if cache is not None:
                cache.put(lane.keys.get(g), technique=name, size=g, feasible=False,
                          memory_infeasible=memory_bound)
            logger.info("%s", eta.trial_done(dt))
            return
        total = per_batch_time * task.total_batches  # reference ``:26``
        # The staging-vs-compute split the technique measured alongside the
        # per-batch time (``SPMDTechnique.host_fraction_report``, pop-once);
        # plain BaseTechnique plugins report nothing -> 0.0 -> never
        # co-scheduled.
        hf = 0.0
        hf_reporter = getattr(tech, "host_fraction_report", None)
        if callable(hf_reporter):
            hf = hf_reporter(task.name, g) or 0.0
        sp.set(feasible=True, per_batch_s=per_batch_time,
               est_total_s=total, params=params,
               host_fraction=round(float(hf), 4))
        logger.info(
            "trial (%s, g=%d, %s): %.4fs/batch, est total %.1fs (trial took %.1fs)",
            task.name, g, name, per_batch_time, total, dt,
        )
        with update_lock:
            lane.done[g] = (True, params, per_batch_time, "trial")
        install(lane, g, params, per_batch_time, "trial", host_fraction=hf)
        if cache is not None:
            cache.put(lane.keys.get(g), technique=name, size=g, feasible=True,
                      params=params, per_batch_time=per_batch_time,
                      host_fraction=float(hf))
        logger.info("%s", eta.trial_done(dt))

    def prune_point(lane: _Lane, g: int, reason: str, planned: bool) -> None:
        if planned:  # only planned trials count against the ETA denominator
            eta.trial_pruned()
        with update_lock:
            lane.done[g] = (False, None, None, "pruned")
        metrics.event("trial_pruned", task=lane.task.name, size=g,
                      technique=lane.name, reason=reason)
        logger.info("trial (%s, g=%d, %s): pruned (%s)",
                    lane.task.name, g, lane.name, reason)

    # ------------------------------------------------------------ trial pass
    # Size classes run LARGEST-FIRST with a barrier between classes, so a
    # memory rejection at size g prunes every smaller (>= per-chip memory)
    # size before it compiles. Within a class the existing disjoint-block
    # fan-out applies unchanged.
    tid_counter = [0]

    def next_tid() -> int:
        with update_lock:
            tid_counter[0] += 1
            return tid_counter[0]

    # memlens static pre-lowering prune: with a known per-device HBM
    # capacity, a grid point whose statically predicted peak clears the
    # OOM margin for EVERY candidate config never lowers at all. The
    # compile-time _fits_memory check stays the authoritative backstop
    # for everything that does run (and feeds SAT-M005 calibration).
    memlens_cap = 0
    ml_passes = None
    if prune and os.environ.get("SATURN_TPU_MEMLENS_PRUNE", "1") != "0":
        try:
            from saturn_tpu.analysis.memlens import passes as ml_passes
            memlens_cap = ml_passes.hbm_capacity_bytes(topo.devices)
        except Exception:
            memlens_cap = 0

    def memlens_infeasible(lane: _Lane, g: int) -> bool:
        if memlens_cap <= 0:
            return False
        try:
            devices = topo.blocks(g)[0].devices_of(topo.devices)
            with metrics.span("prior.memlens", task=lane.task.name, size=g,
                              technique=lane.name, n_points=1) as sp:
                verdict = ml_passes.grid_point_infeasible(
                    lane.tech, lane.task, devices, memlens_cap)
                sp.set(infeasible=bool(verdict))
            return verdict
        except Exception:
            return False

    run_sizes = sorted({g for lane in lanes for g in lane.to_run}, reverse=True)
    for g in run_sizes:
        items: List[_Lane] = []
        for lane in lanes:
            if g not in lane.to_run:
                continue
            if lane.pruned(g):
                prune_point(lane, g, "memory_monotone", planned=True)
            elif memlens_infeasible(lane, g):
                prune_point(lane, g, "memlens_static", planned=True)
                note_memory_floor(lane, g)
            else:
                items.append(lane)
        if not items:
            continue
        blocks = topo.blocks(g)
        n_workers = min(workers, len(blocks), len(items))
        if n_workers <= 1:
            for lane in items:
                run_trial(next_tid(), lane, g, blocks[0])
            continue
        # Concurrent same-size trials on DISJOINT blocks (the reference's
        # Ray fan-out, ``:74-84``, without Ray): a bounded pool per size
        # class, each in-flight trial holding its own block from a free list.
        free: queue.Queue = queue.Queue()
        for b in blocks[:n_workers]:
            free.put(b)

        above = metrics.current_span()  # ``search``: the trial threads' parent

        def with_block(lane):
            block = free.get()
            try:
                with metrics.under(above):
                    run_trial(next_tid(), lane, g, block)
            finally:
                free.put(block)

        with ThreadPoolExecutor(
            max_workers=n_workers, thread_name_prefix=f"trial-g{g}"
        ) as pool:
            futures = [pool.submit(with_block, lane) for lane in items]
            for f in futures:
                f.result()

    # ------------------------------------------------------- cost-model fill
    # Remaining grid points get interpolated strategies from the Amdahl fit
    # over this lane's measured feasible points — flagged so the realized
    # feedback loop knows to upgrade them. Points below a memory floor stay
    # infeasible (their per-chip memory is >= an XLA-rejected size's); lanes
    # with fewer than two measured points have no scaling signal and leave
    # the dummy seeding below to mark the gap.
    for lane in lanes:
        if not lane.to_fill:
            continue
        pts = [
            (g, pbt)
            for g, (feasible, _params, pbt, source) in lane.done.items()
            if feasible and source != "interpolated"
        ]
        model = _fit_scaling_model(pts) if len(pts) >= 2 else None
        for g in lane.to_fill:
            if lane.pruned(g):
                prune_point(lane, g, "memory_monotone", planned=False)
                continue
            if model is None:
                continue
            # Feasibility is only trusted between measured feasible sizes:
            # extrapolating below the smallest one would claim memory room
            # no trial ever checked.
            lo = min(p[0] for p in pts)
            if g < lo:
                continue
            per_batch = max(float(model(g)), 1e-9)
            nearest = min(pts, key=lambda p: abs(p[0] - g))[0]
            params = dict(lane.done[nearest][1] or {})
            with update_lock:
                lane.done[g] = (True, params, per_batch, "interpolated")
            install(lane, g, params, per_batch, "interpolated")
            metrics.event(
                "trial_interpolated", task=lane.task.name, size=g,
                technique=lane.name, per_batch_s=per_batch,
                anchor_size=nearest,
            )

    n_interp = sum(
        1 for l in lanes for d in l.done.values() if d[3] == "interpolated"
    )
    if eta.planned or n_hits:
        logger.info(
            "trial runner: sweep complete — %d trials run, %d cache hits, "
            "%d pruned, %d interpolated",
            eta.completed, n_hits, eta.pruned, n_interp,
        )

    # Seed unsearched sizes with an infeasible dummy (``:96-99``) so the
    # solver's bookkeeping sees a complete table.
    for task in tasks:
        for g in topo.valid_sizes():
            if g not in task.strategies:
                task.strategies[g] = Strategy(None, g, None, DUMMY_RUNTIME)

    # Fused-stacking trials: propose same-fingerprint groups and measure
    # the stacked per-step cost so the solver can price fusion against the
    # solo/co-scheduled grid (``milp.fusion_priced_groups`` refuses groups
    # without a measured ``fused_per_batch_time``). Fail open per group — a
    # group that cannot build or trace keeps ``fused_per_batch_time=None``
    # and is simply never fused.
    fused_groups = 0
    try:
        from saturn_tpu.parallel import fused as _fused

        fusion_names = _fused.fusion_candidates(list(tasks))
    except Exception:
        fusion_names = []
    if fusion_names:
        by_name = {t.name: t for t in tasks}
        for group_names in fusion_names:
            group = [by_name[n] for n in group_names if n in by_name]
            if len(group) < 2:
                continue
            try:
                # metrics_path=None: the caller (``search``) already scoped
                # the ambient writer, so trial_fused events land there.
                measured = profile_fused_group(group, topology=topo)
            except Exception:
                logger.exception(
                    "fused trial for group %s failed (fail-open)",
                    group_names,
                )
                continue
            if any(v > 0 for v in measured.values()):
                fused_groups += 1
        if fused_groups:
            logger.info(
                "trial runner: %d fused group(s) measured", fused_groups
            )

    return {
        "trials_run": eta.completed,
        "cache_hits": n_hits,
        "pruned": eta.pruned,
        "interpolated": n_interp,
        "dispatch": dispatch,
        "fused_groups": fused_groups,
        "errors": errors["n"],
        "first_error": errors["first"],
        **refusals,
    }


def profile_fused_group(
    tasks: Sequence,
    sizes: Optional[Sequence[int]] = None,
    topology: Optional[SliceTopology] = None,
    steps: int = 3,
    warmup: int = 1,
    metrics_path: Optional[str] = None,
) -> Dict[int, float]:
    """Profile the FUSED stack of ``tasks`` and price its lockstep step.

    The fused-stacking analog of the per-job grid sweep: builds the stacked
    program for the group at each candidate sub-mesh size, times a few
    lockstep steps on freshly-initialized member states, and writes the
    measured seconds-per-lockstep-step into every member's
    ``Strategy.fused_per_batch_time`` at that size. The solver fuses strictly
    on these measurements (``solver/milp.fusion_priced_groups``) — a size
    this function never priced keeps ``fused_per_batch_time=None`` and is
    never fused on guesswork.

    Pure measurement: unlike ``parallel.fused.run_fused_interval`` this
    neither checkpoints nor advances any task's cursor — member states are
    init-from-scratch throwaways and batches are read (not consumed) via
    ``batch_at(0)``.

    ``sizes=None`` profiles every size at which ALL members already hold a
    feasible (searched) strategy — run :func:`search` first. Returns
    ``{size: measured_per_lockstep_step_seconds}``.
    """
    import jax
    import numpy as np

    from saturn_tpu.core import distributed as _dist
    from saturn_tpu.ops import stacking
    from saturn_tpu.parallel import fused as _fused

    members = list(tasks)
    if len(members) < 2:
        raise ValueError("a fused group needs at least 2 members")
    fps = {_fused.fusion_fingerprint(t) for t in members}
    if len(fps) != 1 or None in fps:
        raise ValueError(
            "tasks are not fusable: fusion fingerprints differ or are None "
            f"({[t.name for t in members]})"
        )

    topo = topology or SliceTopology()
    if sizes is None:
        candidates = [
            g for g in topo.valid_sizes()
            if all(
                g in t.strategies and t.strategies[g].feasible
                for t in members
            )
        ]
    else:
        valid = set(topo.valid_sizes())
        candidates = [int(g) for g in sizes if int(g) in valid]

    measured: Dict[int, float] = {}
    with metrics.scoped(metrics_path):
        for g in candidates:
            block = topo.blocks(g)[0]
            devs = _fused.usable_devices(
                block.devices_of(topo.devices), len(members)
            )
            try:
                prog = _fused.build_fused_program(members, devs)
                state = _dist.put_tree_global(
                    stacking.stack_trees(
                        [prog.init_member_host(m.hparams.lr) for m in members]
                    ),
                    prog.state_shardings,
                )
                lrs_dev = _dist.put_global(
                    np.asarray(
                        [m.hparams.lr for m in members], dtype=np.float32
                    ),
                    prog.lr_sharding,
                )
                batch_dev = _dist.put_global(
                    stacking.stack_member_batches(
                        [m.batch_at(0) for m in members],
                        member_names=[m.name for m in members],
                    ),
                    prog.batch_sharding,
                )
                fn = prog.single_compiled()
                for _ in range(max(int(warmup), 0)):
                    state, loss = fn(state, batch_dev, lrs_dev)
                jax.block_until_ready(state)
                n = max(int(steps), 1)
                t0 = timeit.default_timer()
                for _ in range(n):
                    state, loss = fn(state, batch_dev, lrs_dev)
                jax.block_until_ready((state, loss))
                per_step = (timeit.default_timer() - t0) / n
            except Exception as e:
                # A size the stacked program cannot run (e.g. XLA memory
                # rejection of the N-way stack) is a result, not a flake:
                # fused_per_batch_time stays None and the solver never
                # fuses at this size.
                logger.info(
                    "fused trial (%s, g=%d): infeasible (%r)",
                    "+".join(t.name for t in members), g, e,
                )
                metrics.event(
                    "trial_fused", tasks=[t.name for t in members], size=g,
                    n_members=len(members), feasible=False, error=repr(e),
                )
                continue
            for m in members:
                strat = m.strategies.get(g)
                if strat is not None and strat.feasible:
                    strat.fused_per_batch_time = per_step
            measured[g] = per_step
            logger.info(
                "fused trial (%s, g=%d, N=%d): %.4fs/lockstep step",
                "+".join(t.name for t in members), g, len(members), per_step,
            )
            metrics.event(
                "trial_fused", tasks=[t.name for t in members], size=g,
                n_members=len(members), feasible=True, per_step_s=per_step,
            )
    return measured
