"""SPMDTechnique: shared machinery for sharding-based executors (DP/FSDP/TP).

In the reference, each technique was ~200 lines of process spawning, NCCL
setup, wrapper classes and OOM probing (``FSDP.py``, ``DDP.py``). TPU-native,
a technique reduces to: a mesh shape, a PartitionSpec rule function, and a
small autotune grid. Everything else — building the jitted train step, XLA
memory feasibility, steady-state timing, checkpoint/resume with resharding —
is shared here.

Contract parity (``Technique.py:24-45``): subclasses get ``search`` (autotune
+ profile) and ``execute`` (bounded batches, resume + checkpoint) for free and
override only the three small hooks at the bottom.
"""

from __future__ import annotations

import contextlib
import logging
import os
import queue
import threading
import time as _time
import timeit as _timeit
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import numpy as np
import optax
from jax.extend.core import jaxpr_as_fun
from jax.sharding import NamedSharding, PartitionSpec as P

from saturn_tpu.core.mesh import make_submesh
from saturn_tpu.core.technique import BaseTechnique, InfeasibleConfig
from saturn_tpu.ops import plans as _plans
from saturn_tpu.parallel import sharding as shr
from saturn_tpu.utils import aot_cache
from saturn_tpu.utils import checkpoint as ckpt
from saturn_tpu.utils import metrics as _metrics
from saturn_tpu.utils import point_records
from saturn_tpu.utils.timing import (
    FUSED_WINDOW_STACKS,
    hbm_bytes_required,
    hbm_limit,
    time_fused_window,
    time_train_step,
)

log = logging.getLogger("saturn_tpu")


def _stage_to_device(tree):
    """Move a (possibly pinned-host) tree into device memory inside jit."""
    return jax.device_put(tree, jax.memory.Space.Device)


# ------------------------------------------------------- fused-window policy
#: Default ceiling on the fused multi-step window K (``lax.scan`` over a
#: stacked window of K batches inside one jitted call). K trades per-step
#: Python dispatch + per-step loss readback against staged-batch memory
#: ((K, B, T) tokens resident at once) and progress granularity — a window
#: is all-or-nothing under preemption, and the interval's batch budget is
#: only exact at window boundaries.
DEFAULT_MAX_WINDOW = 8

_ENV_MAX_WINDOW = "SATURN_TPU_MAX_WINDOW"


def max_window() -> int:
    """Ceiling on the fused window K (env ``SATURN_TPU_MAX_WINDOW``).

    ``<= 1`` disables fused dispatch entirely — every interval runs the
    exact legacy per-step path.
    """
    try:
        k = int(os.environ.get(_ENV_MAX_WINDOW, DEFAULT_MAX_WINDOW))
    except ValueError:
        return DEFAULT_MAX_WINDOW
    return max(1, k)


def choose_window(n_batches: int, cap: Optional[int] = None) -> int:
    """Fused window size K for an interval budget of ``n_batches``.

    The largest window under the cap that the budget can fill at least
    once; 1 (the exact per-step fallback) when the interval is too short to
    amortize a fused program or fused dispatch is disabled. The interval
    then runs ``n // K`` fused windows plus an ``n % K`` per-step tail, so
    every budgeted batch runs and loss trajectories stay bit-identical to
    the 1-step path.
    """
    cap = max_window() if cap is None else int(cap)
    n = int(n_batches)
    if cap <= 1 or n < 2:
        return 1
    return min(cap, n)


def _counter_fields(unit_counters: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """The step counters of an interval for its ``task_interval`` event, from
    each unit's ``{name: scalar or (K,)}``: over the interval's steps the mean
    of a name ending in ``_held`` / ``_mean`` (per step), the largest of one
    ending in ``_max``, the sum of any other (``moe_second_path``: steps
    that took the second path)."""
    from saturn_tpu.core import distributed as _dist

    out: Dict[str, float] = {}
    names = sorted({n for c in unit_counters for n in c})
    for name in names:
        steps = np.concatenate([
            np.asarray(_dist.host_array(c[name]), dtype=np.float64).reshape(-1)
            for c in unit_counters if name in c])
        if name.endswith(("_held", "_mean")):
            out[name] = float(steps.mean())
        elif name.endswith("_max"):
            out[name] = float(steps.max())
        else:
            out[name] = float(steps.sum())
    return out


def _host_fraction(t_host: float, t_device: float) -> float:
    """Fraction of one steady-state batch spent on host-side staging work.

    ``t_host`` is the measured staging cost per batch (dataset slice +
    ``device_put`` transfer), ``t_device`` the device-only per-batch time.
    The ratio against their sum lands in [0, 1] with the useful pivot at
    0.5: above it the job is stage-bound — its device sits idle
    ``t_host - t_device`` out of every ``t_host`` of wall clock, which is
    the bubble a co-scheduled compute-bound neighbor can fill.
    """
    t_host = max(0.0, float(t_host))
    t_device = max(0.0, float(t_device))
    total = t_host + t_device
    if total <= 0.0:
        return 0.0
    return min(1.0, max(0.0, t_host / total))


def dispatch_signature() -> str:
    """Content signature of the execution dispatch mode, for the profile
    cache key (``utils/profile_cache.fingerprint``): per-step trial profiles
    must not warm-start fused-dispatch sweeps (and vice versa) — the two
    modes have genuinely different per-batch times, which is the point."""
    k = max_window()
    return f"fused-scan-v1:k{k}" if k > 1 else "per-step"


@dataclass
class _Bundle:
    """Everything needed to run one (task, devices, config) combination.

    The bundle owns the ONE Python trace of its train step (``traced``,
    made where the bundle is built): the 1-step program, every K-step window
    program and every static analysis of this (task, config, block) replay
    or read that closed jaxpr. The programs are lowered on first use, so a
    search that profiles the K-step window never lowers the 1-step program.
    """

    mesh: Any
    step: Any                 # jitted replay: (state, batch) -> (state, loss)
    init: Any                 # jitted sharded init: () -> state
    state_shapes: Any         # ShapeDtypeStruct tree (for restore templates)
    state_shardings: Any
    batch_sharding: Any
    # what ``SPMDTechnique.trace_step`` answers: the closed jaxpr of the raw
    # train step at (state_shapes, batch_sds) with its sharding intent
    traced: Dict[str, Any]
    # the kept trace as a function: (state, batch) -> (state, loss), re-binding
    # the jaxpr's equations (no Python trace of the model)
    replay: Any
    batch_sds: Any            # ShapeDtypeStruct of one host batch
    # how often the model's Python step function has been called for this
    # bundle (a one-element list the tracing site counts into): 1
    trace_count: List[int]
    retrace_key: Any = None   # stable (task, config, block) dispatch identity
    # what the step's ops were traced as (``ops/plans.py``): an op family's
    # name -> the plan of each of its calls in the order traced (None for a
    # call that fell back to plain XLA ops); no key for a family the step
    # does not call
    plans: Dict[str, Tuple[Any, ...]] = field(default_factory=dict)
    _lowered: Any = None
    _compiled: Any = None
    _single_lock: Any = field(default_factory=threading.Lock)
    _fused: Dict[int, Any] = field(default_factory=dict)
    _fused_lock: Any = field(default_factory=threading.Lock)

    def _block_devices(self):
        """The concrete devices this bundle's programs are pinned to — part
        of every AOT-cache key (same program, different block = different
        executable)."""
        return list(self.mesh.devices.flat)

    @property
    def step_traces(self) -> int:
        """Python traces of the train step this bundle has made."""
        return self.trace_count[0]

    @property
    def lowered(self):
        """``jit(...).lower(...)`` of the 1-step program, lowered on first
        use from the kept trace (an interval's tail, ``_fits_memory``, the
        K = 1 trial of an offloaded config, a warm-up)."""
        with self._single_lock:
            if self._lowered is None:
                self._lowered = self.step.lower(self.state_shapes,
                                                self.batch_sds)
            return self._lowered

    @property
    def compiled(self):
        """The AOT-compiled train step. Compiled exactly once per bundle —
        memory analysis, trial timing and interval execution all share it, so
        a (task, config, block) combination never compiles twice. Routed
        through the persistent executable cache (``utils/aot_cache``): a
        restart or re-admission of a previously-seen program deserializes
        instead of recompiling."""
        if self._compiled is None:
            self._compiled = aot_cache.load_or_compile(
                self.lowered, self._block_devices()
            )
        return self._compiled

    def stacked_sharding(self):
        """Sharding for a (K, batch, seq) window stack: the window axis is
        unsharded (scan consumes it sequentially), each slice keeps the
        bundle's batch sharding."""
        return NamedSharding(
            self.mesh, P(None, *tuple(self.batch_sharding.spec))
        )

    def has_fused(self, k: int) -> bool:
        with self._fused_lock:
            return int(k) in self._fused

    def fused_compiled(self, k: int):
        """AOT-compiled fused K-step program, compiled once per (bundle, K).

        ``lax.scan`` of the kept trace of the train step (``replay``: the
        step's own equations as the scan body, no second Python trace) over a
        stacked (K, batch, seq) window inside one XLA program: one Python
        dispatch and one loss readback amortize over K batches, and XLA
        pipelines the inter-step boundary (no host round-trip between steps).
        State AND the window stack are donated — the caller must stage a fresh
        stack per call.
        The per-step losses come back as a (K,) vector so the loss
        trajectory is observable exactly as the 1-step path reports it.
        """
        k = int(k)
        with self._fused_lock:
            hit = self._fused.get(k)
        if hit is not None:
            return hit
        if k < 1:
            raise ValueError(f"bundle cannot build a fused window (k={k})")
        train = self.replay

        def saturn_window(state, window):
            return jax.lax.scan(train, state, window)

        fused = jax.jit(
            saturn_window,
            in_shardings=(self.state_shardings, self.stacked_sharding()),
            out_shardings=(self.state_shardings, NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1),
        )
        window_sds = jax.ShapeDtypeStruct(
            (k, *self.batch_sds.shape), self.batch_sds.dtype
        )
        if self.retrace_key is not None:
            # Static retrace-risk check (saturn-lint pass 2a): a novel
            # abstract signature for an already-compiled (bundle, K) key
            # means this compile is an AOT-cache miss the plan didn't
            # budget for — flag it before it burns chip time.
            from saturn_tpu.analysis import jax_lint as _jlint

            diag = _jlint.retrace_registry.note(
                self.retrace_key, k,
                _jlint.abstract_signature((self.state_shapes, window_sds)),
            )
            if diag is not None:
                log.warning("%s", diag.message)
        compiled = aot_cache.load_or_compile(
            fused.lower(self.state_shapes, window_sds), self._block_devices()
        )
        with self._fused_lock:
            return self._fused.setdefault(k, compiled)


class _Prepared(NamedTuple):
    """A grid point whose host work is done (``SPMDTechnique._prepare``):
    what is left is to put a state on the chip and time the program."""

    bundle: _Bundle
    program: Any    # the compiled K-step window program, or the 1-step one
    k: int


@dataclass
class _GridPoint:
    """One grid point on its way through ``SPMDTechnique.search``."""

    order: int                  # its place in the technique's own grid
    # the grid's, with ``ce_mode: "stash"`` added where the point is ready on
    # its head's stash rung: the config of the program that is timed
    config: Dict[str, Any]
    # its ``trial.config``: opened where its preparation starts, closed on
    # the thread the point ends on
    span: Any
    ready: Optional[_Prepared] = None
    timed: Optional[Tuple[float, float]] = None   # of a ``timed`` point
    # how it ended (``trial.config``'s ``outcome``); None while it has not
    outcome: Optional[str] = None
    refusal: Optional[str] = None   # of a ``refused`` point: fresh / recorded
    compiler: Optional[str] = None  # ...and the compiler's first line
    unbuilt: bool = False   # it ended on its point record: nothing was built
    error: Optional[str] = None     # of an ``error`` point, for ``first_error``
    # what the fused head's rungs did (``SPMDTechnique._head_rungs``), as its
    # ``trial_config`` event and its span carry it (``ce_ladder``); None for
    # a point whose head nobody is asked about
    ladder: Optional[Dict[str, Any]] = None


#: a wait across the hand-off shorter than this emits no span: the other side
#: was ready (a chip-bound search would emit one empty wait a point)
_WAITED_S = 1e-3


def _measured_behind(
    grid: Sequence[Tuple[int, Dict[str, Any]]],
    prepare: Callable[[int, Dict[str, Any]], _GridPoint],
    measure: Callable[[_GridPoint], None],
) -> Tuple[List[_GridPoint], int]:
    """Every entry of ``grid`` prepared on the caller's thread, in the grid's
    order, and each point measured behind it, strictly one at a time and in
    the same order, on a thread of its own: the caller prepares the next
    points while this one is measured. Returns the points, and how many of
    them were ready before the measuring thread asked for them.

    The host's half stays on the caller's thread because it is the one that
    allocates: on the chip's host the same tracing and lowering takes half
    as long again on a new thread as on the main one, and reading an
    executable from the compile cache three times as long (PR 37). A grid
    of one point starts no thread.

    ``prepare`` and ``measure`` keep an ``Exception`` to themselves (it is
    how that point ended). Anything else one of them raises (a kill) is
    raised here, on the caller's thread, once the other has finished the
    point it was on: it starts no further point, and the thread is joined.

    Who waited for whom is two spans under the caller's open span
    (``trial``), stamps around the two blocking calls and nothing more:
    ``trial.wait_prepared`` on the measuring thread (the chip has nothing to
    measure; ``ahead`` says whether the point was already there) and
    ``trial.wait_measured`` on the caller's (the host has nothing left to
    prepare).
    """
    if len(grid) < 2:
        points = [prepare(order, config) for order, config in grid]
        for point in points:
            measure(point)
        return points, 0
    ready: "queue.SimpleQueue[Optional[_GridPoint]]" = queue.SimpleQueue()
    gone = threading.Event()     # one side has left: start no further point
    behind: Dict[str, Any] = {"ahead": 0, "raised": None}
    above = _metrics.current_span()  # ``trial``: the two waits' parent

    def measure_in_turn() -> None:
        try:
            while True:
                ahead = not ready.empty()
                with _metrics.span("trial.wait_prepared", parent=above,
                                   min_s=_WAITED_S, ahead=ahead):
                    point = ready.get()
                if point is None or gone.is_set():
                    return
                behind["ahead"] += ahead
                measure(point)
        except BaseException as e:  # raised again below, on the caller's thread
            behind["raised"] = e
            gone.set()

    measurer = threading.Thread(
        target=measure_in_turn,
        name=f"meas-{threading.current_thread().name}", daemon=True)
    measurer.start()
    points: List[_GridPoint] = []
    try:
        for order, config in grid:
            if gone.is_set():
                break
            points.append(prepare(order, config))
            ready.put(points[-1])
    except BaseException:
        gone.set()
        raise
    finally:
        ready.put(None)
        with _metrics.span("trial.wait_measured", parent=above,
                           min_s=_WAITED_S):
            measurer.join()
    if behind["raised"] is not None:
        raise behind["raised"]
    return points, behind["ahead"]


class SPMDTechnique(BaseTechnique):
    """Base for techniques expressible as (mesh shape + sharding rules)."""

    name = "spmd"

    # Per-chip memory never grows with block size under sharding: replicated
    # state is constant per chip, sharded state (params, activations, layer
    # spans, expert tables) shrinks. Lets the trial runner skip all smaller
    # sizes once XLA memory analysis rejects one (``core/technique.py``).
    memory_monotone = True

    # Per-instance ceiling on cached compiled programs. A 16-task ×
    # multi-config × multi-block sweep would otherwise hold every executable
    # for the life of the technique (VERDICT r2 weak #7); LRU keeps the
    # working set (active tasks' current configs) while bounding growth.
    bundle_cache_cap = 32

    # Whether this technique may route standard-loss tasks through the
    # model's fused head+loss (ops/ce.py). Techniques that shard the head
    # weights over the vocab axis must opt out: the Pallas CE kernel has no
    # vocab-partitioning rule, so GSPMD would all-gather the full table and
    # an unsharded (N, V) logits stash per device.
    fused_loss_ok = True
    # Whether the fused loss may run on MULTI-chip blocks via the shard_map
    # wrapper (step_fns_from_forward): only valid for purely batch-sharding
    # techniques — params must be replicated (in_spec P()) and the batch
    # sharded along the mesh. dp opts in; fsdp/tp shard params.
    fused_loss_shardable = False

    # Advertises the optional ``execute(window_size=...)`` kwarg to the
    # engine (``executor/engine.py`` gates the kwarg on this attribute so
    # plugin techniques with the bare BaseTechnique signature keep working).
    supports_windows = True
    # Advertises ``interval_dispatches`` — the resumable per-window generator
    # the engine's co-schedule group launcher interleaves across tasks
    # sharing a device block. Techniques without it fall back to sequential
    # execution on the shared launcher (correct, just unoverlapped).
    supports_coschedule = True
    # Whether fused multi-step dispatch (``lax.scan`` window) is valid for
    # this technique at all. Techniques whose step depends on per-call host
    # interaction can opt out; offloaded (pinned_host) configs are excluded
    # per-config in ``_fused_ok`` regardless.
    fused_dispatch_ok = True

    def __init__(self) -> None:
        # Bundle cache keyed by (task, config, device block): the orchestrator
        # calls execute() every interval (reference kill-and-respawn,
        # ``executor.py:65``); without the cache each interval would pay a
        # full XLA recompile of an identical program. LRU-ordered (see
        # ``bundle_cache_cap``); completed tasks release their entries via
        # ``release_task`` (mirroring ``Task.release_live_state``). The lock
        # covers the compound move_to_end/popitem/del sequences: one technique
        # instance serves concurrent trial threads (``evaluator.py``) and
        # gang-launch threads (``engine.py``).
        from collections import OrderedDict

        self._bundles: "OrderedDict[Any, _Bundle]" = OrderedDict()
        self._bundles_lock = threading.Lock()
        # Static per-step FLOPs (shardflow's dense-dot ledger) per bundle
        # key — the numerator of the task_interval tflops/mfu report.
        # Counted lazily at most once per bundle, from the bundle's kept
        # trace; a failed count caches None so telemetry degrades to omitting
        # the fields instead of re-paying (or re-raising) it every interval.
        self._flops_cache: Dict[Any, Optional[float]] = {}
        self._flops_lock = threading.Lock()
        # What each (task, size) search saw (config, memory-rejection and
        # error counts) — consumed (and popped) by the trial runner for its
        # monotone pruning and its error total. Keyed per grid
        # point because one instance serves concurrent trial threads.
        self._search_reports: Dict[Any, Dict[str, Any]] = {}
        # Host fraction measured for the best (task, size) config — consumed
        # (popped) by the trial runner alongside the per-batch time; feeds
        # the solver's co-location term via ``Strategy.host_fraction``.
        self._host_fracs: Dict[Any, float] = {}
        self._reports_lock = threading.Lock()

    def search_report(self, task_name: str, size: int) -> Optional[Dict[str, Any]]:
        """Pop the report of the most recent ``search`` of (task, size): how
        many configs there were, how many XLA's memory analysis rejected or
        the compiler refused for memory (of those, ``refusals_fresh`` by a
        compile and ``refusals_replayed`` from a record, ``refusals_unbuilt``
        of these from the point's own record with nothing built), how
        many raised (``errors``, with ``first_error``), and whether memory
        alone made the point infeasible. None when no search ran."""
        with self._reports_lock:
            return self._search_reports.pop((task_name, size), None)

    def host_fraction_report(self, task_name: str, size: int) -> Optional[float]:
        """Pop the host fraction measured by the most recent feasible
        ``search`` of (task, size); None when no feasible search ran. Same
        pop-once protocol as ``search_report`` — one technique instance
        serves concurrent trial threads."""
        with self._reports_lock:
            return self._host_fracs.pop((task_name, size), None)

    def config_bubble_fraction(self, config: Dict[str, Any]) -> float:
        """Analytic DEVICE-idle fraction of a steady-state step under
        ``config`` — schedule bubbles (pipeline warmup/cooldown) a
        co-scheduled partner's device windows could fill, in [0, 1).

        Unlike ``host_fraction`` this is derived from the config, not
        measured: the bubble is a property of the schedule shape (stage and
        microbatch counts), so every install path — trial, cache hit,
        interpolated fill, elastic re-synthesis — recomputes it exactly.
        Dense sharding techniques have no schedule bubble; the pipeline
        executor overrides this with the GPipe/1F1B bubble formulas.
        """
        return 0.0

    def release_task(self, task_name: str) -> None:
        """Drop every cached compiled program for ``task_name`` — called when
        the task completes or is evicted, so finished sweeps don't pin
        executables (and their device constants) for the technique's life."""
        with self._bundles_lock:
            for key in [k for k in self._bundles if k[0] == task_name]:
                del self._bundles[key]
        with self._flops_lock:
            for key in [k for k in self._flops_cache if k[0] == task_name]:
                del self._flops_cache[key]

    def _step_flops(self, task, devices, config) -> Optional[float]:
        """Shardflow's static dense-FLOP count for one step of this (task,
        config, block) — global across the sub-mesh, per batch. Cached per
        bundle key (same identity as the compiled program it describes)."""
        key = self._bundle_key(task, devices, config)
        with self._flops_lock:
            if key in self._flops_cache:
                return self._flops_cache[key]
        flops: Optional[float]
        try:
            from saturn_tpu.analysis.shardflow.interp import interpret

            if getattr(task.get_model(), "stack_kinds", None):
                # shardflow counts dense products it can see; the chunked
                # delta rule's are inside a kernel and a custom_vjp, so the
                # count would be short by a mixer: no figure rather than a
                # wrong one under ``tflops`` / ``mfu``
                flops = None
            else:
                traced = self.trace_step(task, devices, config)
                flops = float(interpret(traced).flops) or None
        except Exception:
            log.debug("shardflow flops trace failed for task %s", task.name,
                      exc_info=True)
            flops = None
        with self._flops_lock:
            self._flops_cache[key] = flops
        return flops

    def _bundle_key(self, task, devices, config):
        return (
            task.name,
            tuple(sorted((k, v) for k, v in config.items())),
            tuple(getattr(d, "id", i) for i, d in enumerate(devices)),
        )

    def _cached_bundle(self, task, devices, config) -> Optional[_Bundle]:
        """The bundle of (task, config, block) if the cache holds it (no LRU
        touch, nothing built)."""
        with self._bundles_lock:
            return self._bundles.get(self._bundle_key(task, devices, config))

    def _trace_source(self, task, devices, config) -> str:
        """What a reader of ``trace_step`` is about to get, for its span's
        ``trace`` field: ``shared`` where a bundle already keeps the trace,
        ``own`` where the call has to build one first."""
        cached = self._cached_bundle(task, devices, config) is not None
        return "shared" if cached else "own"

    # ----------------------------------------------------------------- hooks
    def mesh_spec(
        self, n_devices: int, task: Any, config: Dict[str, Any]
    ) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        """(axis_names, axis_sizes) for a sub-mesh of ``n_devices`` chips."""
        raise NotImplementedError

    def param_rules(self, task: Any, config: Dict[str, Any]):
        """Rule fn (path, shape, mesh_axes) -> PartitionSpec for params."""
        raise NotImplementedError

    def batch_spec(self, config: Dict[str, Any]) -> P:
        """PartitionSpec for the (batch, seq) token batch."""
        return P("data")

    def candidate_configs(
        self, task: Any, n_devices: int
    ) -> List[Dict[str, Any]]:
        """Autotune grid (reference ``FSDP.py:72-78``). ``search`` tries every
        point; the order here breaks a tie in time, nothing else."""
        return [{}]

    def param_memory_kind(self, config: Dict[str, Any]) -> Optional[str]:
        """Memory kind for persistent state ('pinned_host' = offload)."""
        return None

    def make_step_fns(
        self, spec: Any, task: Any, config: Dict[str, Any], mesh: Any, ds: Any
    ) -> Tuple[Any, Any]:
        """(init_state, train_step) for this technique.

        The default is the standard data/tensor-sharded step: loss over the
        full global batch, grads, optax update — GSPMD inserts all
        collectives from the shardings alone. Techniques with an explicit
        schedule (pipeline) override this to build a ``shard_map`` step;
        techniques that only change the forward pass (offload streaming)
        override via ``step_fns_from_forward``.

        When the technique pins persistent state to host memory
        (``param_memory_kind == 'pinned_host'`` — fsdp's offload grid, bulk
        offload), TPU compute cannot consume the host-space arrays directly
        (round-5 chip run: ``add`` of f32 and f32<host> is rejected), so the
        forward stages params to device and the optimizer update runs as
        host computation — see ``step_fns_from_loss_and_grads``.
        """
        to_host_update = self.param_memory_kind(config) == "pinned_host"
        forward = spec.apply_fn
        forward_with_aux = None
        if to_host_update:
            def forward(params, batch):
                return spec.apply_fn(_stage_to_device(params), batch)

            if spec.apply_with_aux_fn is not None:
                def forward_with_aux(params, batch):
                    return spec.apply_with_aux_fn(
                        _stage_to_device(params), batch
                    )

        return self.step_fns_from_forward(
            spec, task, forward, forward_with_aux=forward_with_aux,
            mesh=mesh, batch_partition=self.batch_spec(config),
            update_on_host=to_host_update,
        )

    def step_fns_from_forward(
        self, spec: Any, task: Any, forward: Any, forward_with_aux: Any = None,
        mesh: Any = None, batch_partition: Any = None,
        update_on_host: bool = False,
    ) -> Tuple[Any, Any]:
        """Standard loss/grad/optax scaffold around ``forward(params, batch)``.

        Models exposing an auxiliary training loss (``apply_with_aux_fn``,
        e.g. MoE load balancing) get it added here, in the shared scaffold,
        so the objective is identical no matter which technique the solver
        picks for an interval. A technique that wraps the forward pass but
        preserves its semantics (bulk offload staging) passes its own
        ``forward_with_aux`` wrapper; techniques that replace the schedule
        outright (pipeline, ring, offload streaming) must declare aux models
        infeasible instead — ``_aux_incompatible`` is the helper for that.
        """
        loss_fn = task.loss_fn
        if forward_with_aux is None and (
            spec.apply_with_aux_fn is not None and forward is spec.apply_fn
        ):
            forward_with_aux = spec.apply_with_aux_fn

        # Fused head+loss (ops/ce.py): same objective, no (B,T,V) logits.
        # Only when the technique runs the model's own forward and the
        # task's loss is the standard one the fused path implements. A
        # pallas_call has NO GSPMD partitioning rule, so how it engages
        # depends on the block:
        # - single device (mesh absent or size 1): call it directly;
        # - multi-chip blocks of a purely batch-sharding technique
        #   (``fused_loss_shardable``, i.e. dp: params replicated): wrap it
        #   in shard_map — each device runs the kernel on its batch shard
        #   and the (loss_sum, valid_count) parts are psum'd before the
        #   global divide (per-shard means would misweight uneven masks);
        # - everything else (fsdp's vocab-sharded wte, tp) keeps the GSPMD
        #   logits pipeline, which partitions the head matmul + softmax
        #   natively.
        fused = getattr(spec, "fused_loss_fn", None)
        parts = getattr(spec, "fused_loss_parts_fn", None)
        single = mesh is None or getattr(mesh, "size", 1) <= 1
        if (
            self._fused_head_offered(spec, task, single)
            and forward is spec.apply_fn
            and forward_with_aux is None
        ):
            if single:
                fused_loss = fused
            else:
                from jax import shard_map

                axes = tuple(mesh.axis_names)
                bspec = batch_partition if batch_partition is not None else P(
                    axes[0]
                )

                def _local(p, b):
                    s, c = parts(p, b)
                    s = jax.lax.psum(s, axes)
                    c = jax.lax.psum(c, axes)
                    return s / jax.numpy.maximum(c, 1)

                def fused_loss(params, batch):
                    # check_vma=False: a pallas_call's outputs carry no
                    # varying-axes type, which the check refuses (compiled
                    # for a v5e 2x2; off-TPU the op is plain XLA and the
                    # check never sees a kernel). Both outputs are psum'd
                    # over every axis, so out_specs=P() holds by construction.
                    return shard_map(
                        _local, mesh=mesh, in_specs=(P(), bspec),
                        out_specs=P(), check_vma=False,
                    )(params, batch)

            def loss_and_grads(params, batch):
                return jax.value_and_grad(fused_loss)(params, batch)

            with_stats = getattr(spec, "fused_loss_stats_fn", None)
            if single and with_stats is not None:
                # the same loss with the step's counters beside it (a routed
                # layer's): ``(loss, counters)`` is then the step's second
                # output, stacked by the window program like a bare loss and
                # split where the interval reads its losses back
                def loss_and_grads(params, batch):  # noqa: F811
                    return jax.value_and_grad(with_stats, has_aux=True)(
                        params, batch)

            return self.step_fns_from_loss_and_grads(
                spec.init_fn, task, loss_and_grads,
                update_on_host=update_on_host,
            )

        def loss_and_grads(params, batch):
            def loss_of(p):
                if forward_with_aux is not None:
                    logits, aux = forward_with_aux(p, batch)
                    return loss_fn(logits, batch) + aux
                return loss_fn(forward(p, batch), batch)

            return jax.value_and_grad(loss_of)(params)

        return self.step_fns_from_loss_and_grads(
            spec.init_fn, task, loss_and_grads, update_on_host=update_on_host
        )

    def _fused_head_offered(self, spec: Any, task: Any, single: bool) -> bool:
        """Whether model, loss and block offer this technique the fused
        head+loss (the conditions above that need no forward to ask): the
        model has one for the objective the task's loss names, the technique
        takes it, and the block is one device or one the technique can run
        it on shard by shard."""
        tag = getattr(task.loss_fn, "supports_fused_head", None)
        return bool(
            getattr(spec, "fused_loss_fn", None) is not None
            and self.fused_loss_ok
            and (single or (
                self.fused_loss_shardable
                and getattr(spec, "fused_loss_parts_fn", None) is not None))
            and tag is not None
            and tag == getattr(spec, "fused_loss_objective", None)
        )

    @staticmethod
    def _aux_incompatible(spec: Any) -> bool:
        """True if the model carries an aux loss this technique's custom
        forward path would silently drop — used by candidate_configs to
        declare the (task × technique) pair infeasible, keeping the training
        objective consistent across interval-boundary technique switches."""
        return spec.apply_with_aux_fn is not None

    def _require_no_aux(self, spec: Any) -> None:
        """Execution-time guard mirroring the candidate_configs check:
        build()/make_step_fns called directly with an aux-loss model on a
        schedule that would drop the aux term must fail loudly, not train a
        silently different objective."""
        if self._aux_incompatible(spec):
            raise InfeasibleConfig(
                f"{self.name}: model has an auxiliary loss (apply_with_aux_fn) "
                f"that this technique's custom schedule would drop; use a "
                f"dense technique (dp/fsdp/tp/ep) for aux-loss models"
            )

    def step_fns_from_loss_and_grads(
        self, init_params: Any, task: Any, loss_and_grads: Any,
        update_on_host: bool = False,
    ) -> Tuple[Any, Any]:
        """(init_state, train_step) around ``loss_and_grads(params, batch)``.

        The single definition of the train-state layout ({params, opt_state,
        step}) and the optimizer-update tail — every technique (dense,
        offload, pipeline, ring) routes through here so the state contract
        cannot diverge between them.

        ``update_on_host``: run the (elementwise) optax update as XLA host
        computation against the pinned-host state. This is what lets
        billion-param offload fit: params + both adam moments never occupy
        HBM at once — only the grads cross PCIe (ZeRO-Offload's CPU-optimizer
        design, the TPU-native analog of the reference's fairscale spilling,
        ``Spilled.py:23-28``). Staging the update to device instead would
        put 4 copies (params, grads, mu, nu) on chip and OOM the very
        models the technique exists for.
        """
        tx = task.hparams.make_optimizer()

        def init_state():
            params = init_params(jax.random.PRNGKey(0))
            return {
                "params": params,
                "opt_state": tx.init(params),
                "step": jax.numpy.zeros((), dtype=jax.numpy.int32),
            }

        def train_step(state, batch):
            if update_on_host:
                # The state arrives in pinned host memory (the shardings'
                # memory_kind), but a jit argument's type does not say so,
                # and JAX types every value by memory space: mixing the
                # host-typed grads below with untyped state is refused at
                # trace time (on the v5e, PR 24: "memory_space of all inputs
                # passed to `add` must be the same"). Say where it lives.
                state = jax.device_put(state, jax.memory.Space.Host)
            loss, grads = loss_and_grads(state["params"], batch)
            if update_on_host:
                from jax.experimental.compute_on import compute_on

                grads = jax.device_put(grads, jax.memory.Space.Host)
                ctx = compute_on("device_host")
            else:
                ctx = contextlib.nullcontext()
            with ctx:
                updates, new_opt = tx.update(
                    grads, state["opt_state"], state["params"]
                )
                new_params = optax.apply_updates(state["params"], updates)
                new_step = state["step"] + 1
            return {
                "params": new_params,
                "opt_state": new_opt,
                "step": new_step,
            }, loss

        return init_state, train_step

    # -------------------------------------------------------------- building
    def _model_overrides(self, config: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        if "remat" in config:
            out["remat"] = config["remat"]
        if config.get("attention"):
            out["attention"] = config["attention"]
        if config.get("ce_mode"):
            out["ce_mode"] = config["ce_mode"]
        return out

    def _with_attention_variants(
        self, task: Any, grid: List[Dict[str, Any]], n_devices: int
    ) -> List[Dict[str, Any]]:
        """Pin the attention implementation of every grid point when the
        Pallas kernel can lower for this task's model (the model default is
        'auto', which resolves to flash on TPU, so an unpinned entry is a
        flash entry there).

        On a one-chip block the grid is crossed with {flash, dense}, flash
        first — on the chip it timed faster where both fit (309.5 against
        343.8 ms a batch at GPT-J widths, PERF.md section 5) — and the trial
        runner keeps whichever measures faster for THIS task:
        the empirically-selected-config premise of the whole system
        (``PerformanceEvaluator.py:101-115``).

        On a multi-chip block every point is pinned dense: these techniques'
        steps are GSPMD-partitioned ``jit`` programs, and a Mosaic kernel has
        no partitioning rule — compiled for a v5e 2x2 the flash points are
        refused ("Mosaic kernels cannot be automatically partitioned. Please
        wrap the call in a shard_map"). Offering them would make every
        multi-chip sweep on a TPU report errors and fall to dense anyway.
        """
        from saturn_tpu.ops.flash import flash_supported

        try:
            cfg = task.get_model().config
        except Exception:
            return grid
        if getattr(cfg, "attention", None) is None or not flash_supported(cfg):
            return grid
        if n_devices > 1:
            return [dict(c, attention="dense") for c in grid]
        out: List[Dict[str, Any]] = []
        for c in grid:
            out.append(dict(c, attention="flash"))
            out.append(dict(c, attention="dense"))
        return out

    def build(
        self, task: Any, devices: Sequence[Any], config: Dict[str, Any],
        use_cache: bool = True,
    ) -> _Bundle:
        key = self._bundle_key(task, devices, config)
        if use_cache:
            with self._bundles_lock:
                hit = self._bundles.get(key)
                if hit is not None:
                    self._bundles.move_to_end(key)  # LRU touch
                    return hit
        bundle = self._build_uncached(task, devices, config)
        bundle.retrace_key = key
        # Seed the retrace-risk registry with the per-step signature so a
        # later rebuild of the same dispatch key with novel shapes/dtypes
        # (dataset drift, config mutation) is flagged before it recompiles.
        from saturn_tpu.analysis import jax_lint as _jlint

        diag = _jlint.retrace_registry.note(
            key, "per-step",
            _jlint.abstract_signature((bundle.state_shapes, bundle.batch_sds)),
        )
        if diag is not None:
            log.warning("%s", diag.message)
        if use_cache:
            with self._bundles_lock:
                self._bundles[key] = bundle
                while len(self._bundles) > self.bundle_cache_cap:
                    evicted, _ = self._bundles.popitem(last=False)
                    log.info("%s: bundle cache cap %d hit — evicted %s",
                             self.name, self.bundle_cache_cap, evicted[0])
        return bundle

    def _build_uncached(
        self, task: Any, devices: Sequence[Any], config: Dict[str, Any]
    ) -> _Bundle:
        # Persistent XLA compilation cache: every compile — trial-time AND the
        # execution engine's bundle builds — lands in one on-disk cache, so a
        # program compiled by a sweep is reused by later intervals and later
        # processes. Decided once per process (see the function).
        from saturn_tpu.utils import profile_cache as _pcache

        _pcache.maybe_enable_persistent_compile_cache()
        spec = task.get_model(**self._model_overrides(config))
        axis_names, axis_sizes = self.mesh_spec(len(devices), task, config)
        mesh = make_submesh(devices, axis_names, axis_sizes)
        mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))

        ds = task.get_dataset()
        bspec = self.batch_spec(config)
        data_axis = tuple(bspec)[0] if len(tuple(bspec)) else None
        if data_axis is not None and ds.batch_size % mesh_axes.get(data_axis, 1) != 0:
            raise InfeasibleConfig(
                f"batch_size {ds.batch_size} not divisible by "
                f"{data_axis}={mesh_axes.get(data_axis)}"
            )

        init_state, train_step = self.make_step_fns(spec, task, config, mesh, ds)
        state_shapes = jax.eval_shape(init_state)
        rules = self.param_rules(task, config)
        mem_kind = self.param_memory_kind(config)

        from saturn_tpu.analysis import jax_lint as _jlint

        def spec_of(path, leaf):
            spec_ = rules(shr._path_str(path), tuple(leaf.shape), mesh_axes)
            # Sharding lint (saturn-lint pass 2d): refuse a spec the mesh
            # cannot satisfy (unknown axis, rank overflow) HERE, on CPU,
            # with the rule's file:line — not as a GSPMD compile failure
            # on the chips. Raises ShardingLintError (a ValueError, so the
            # trial runner treats it like any infeasible configuration).
            _jlint.enforce_pspec(spec_, tuple(leaf.shape), mesh_axes,
                                 path=shr._path_str(path), rules=rules)
            return spec_

        def shard_of(spec_):
            if mem_kind is not None:
                return NamedSharding(mesh, spec_, memory_kind=mem_kind)
            return NamedSharding(mesh, spec_)

        state_specs = jax.tree_util.tree_map_with_path(spec_of, state_shapes)
        state_shardings = jax.tree_util.tree_map(
            shard_of, state_specs, is_leaf=lambda x: isinstance(x, P)
        )
        batch_sharding = NamedSharding(mesh, bspec)
        batch_sds = jax.ShapeDtypeStruct(
            ds.example_batch().shape, ds.example_batch().dtype
        )

        # THE trace of this (task, config, block): the raw step's equations
        # as one closed jaxpr, abstract values only. Every program below and
        # every analysis (``trace_step``) is made from it; ``trace_count``
        # counts the calls of the model's Python step function, so a second
        # tracing site would show in ``step_traces``.
        trace_count = [0]

        def counted_step(state, batch):
            trace_count[0] += 1
            return train_step(state, batch)

        with _plans.traced() as traced_as:
            closed, out_shapes = jax.make_jaxpr(
                counted_step, return_shape=True
            )(state_shapes, batch_sds)
        out_tree = jax.tree_util.tree_structure(out_shapes)
        run_trace = jaxpr_as_fun(closed)

        def replay(state, batch):
            # every caller is a ``jit`` whose ``in_shardings`` hold the
            # arguments to the trees the step was traced with
            leaves = jax.tree_util.tree_leaves((state, batch))
            return jax.tree_util.tree_unflatten(out_tree, run_trace(*leaves))

        # Stable names for the programs (the profile's module line, the
        # ``compile`` event's span and a refactor then agree): the window
        # program is ``saturn_window`` (``_Bundle.fused_compiled``).
        def saturn_step(state, batch):
            return replay(state, batch)

        def saturn_init():
            return init_state()

        step = jax.jit(
            saturn_step,
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        init = jax.jit(saturn_init, out_shardings=state_shardings)

        traced = {
            "jaxpr": closed,
            "state_shapes": state_shapes,
            "state_specs": state_specs,
            "batch_spec": bspec,
            "batch_sds": batch_sds,
            "mesh_axes": mesh_axes,
            "technique": self.name,
            "size": len(devices),
            "config": dict(config),
            # memlens: pinned-host configs keep resident params/opt-state
            # in host memory, so the liveness pass excludes them from HBM
            "param_memory_kind": mem_kind,
        }
        return _Bundle(
            mesh=mesh,
            step=step,
            init=init,
            state_shapes=state_shapes,
            state_shardings=state_shardings,
            batch_sharding=batch_sharding,
            traced=traced,
            replay=replay,
            batch_sds=batch_sds,
            trace_count=trace_count,
            plans={name: tuple(got) for name, got in traced_as.items()},
        )

    # ------------------------------------------------------------- shardflow
    def trace_step(
        self, task: Any, devices: Sequence[Any], config: Dict[str, Any]
    ) -> Dict[str, Any]:
        """Build hook for saturn-shardflow (``analysis/shardflow/``) and
        memlens: this technique's train step as a closed jaxpr together with
        its sharding intent, **without compiling** — abstract values only, so
        a static analyzer can propagate PartitionSpecs through every equation
        on CPU before any chip time is spent.

        Answered from the bundle of this (task, config, block), built through
        ``build`` where none is cached: the jaxpr is the one trace the
        bundle's programs replay, so the analysers read the equations the
        chip runs and nothing is traced for them a second time.
        """
        return dict(self.build(task, devices, config).traced)

    # ------------------------------------------------------------ feasibility
    def _fits_memory(
        self, bundle: _Bundle, devices: Sequence[Any],
        task: Any = None, config: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """XLA compile-time memory check (replaces OOM probes,
        ``Spilled.py:68-87``)."""
        return self._fits_compiled(bundle.compiled, devices,
                                   task=task, config=config, k=1)

    def _fits_compiled(
        self, compiled: Any, devices: Sequence[Any], *,
        task: Any = None, config: Optional[Dict[str, Any]] = None,
        k: int = 1, said: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """Memory check against a specific compiled program — the fused
        K-step trial analyzes the window program it will actually time (its
        peak includes the (K, B, T) staged stack the 1-step program never
        holds).

        When the caller knows the (task, config) this program came from,
        every check also emits a ``memlens_calibration`` metrics event —
        static predicted bytes next to the compiled figure — so the
        SAT-M005 drift audit accrues for free on every sweep. ``said``
        receives the two figures the span carries (``need_bytes``,
        ``limit_bytes``).
        """
        with _metrics.span("trial.memory_check", k=int(k)) as sp:
            limit = hbm_limit(devices[0])
            need = hbm_bytes_required(compiled)
            sp.set(need_bytes=int(need), limit_bytes=int(limit))
            if said is not None:
                said.update(need_bytes=int(need), limit_bytes=int(limit))
            if task is not None and config is not None:
                with _metrics.span("trial.memlens", k=int(k), trace=(
                        self._trace_source(task, devices, config))):
                    self._memlens_calibration(task, devices, config, need, k)
            if limit <= 0:
                return True
            ok = need == 0 or need <= 0.92 * limit
            if not ok:
                log.info(
                    "%s: config needs %.2f GiB > %.2f GiB HBM — infeasible",
                    self.name, need / 2**30, limit / 2**30,
                )
                # on record beside the compiler's own refusals: the next
                # search neither compiles nor reads this program to weigh it
                # again (``aot_cache.reject``; nothing where the cache is off)
                sp.set(recorded=aot_cache.reject(compiled, need, limit))
            return ok

    def _memlens_calibration(
        self, task: Any, devices: Sequence[Any], config: Dict[str, Any],
        compiled_bytes: int, k: int,
    ) -> None:
        """Best-effort static-vs-compiled comparison; never raises and
        never changes the feasibility outcome."""
        try:
            from saturn_tpu.analysis.memlens import liveness as _ml_liveness
            from saturn_tpu.analysis.memlens import passes as _ml_passes

            traced = self.trace_step(task, list(devices), dict(config))
            profile = _ml_liveness.analyze(traced, window=k)
            _metrics.event(
                "memlens_calibration",
                technique=self.name,
                task=getattr(task, "name", "?"),
                size=len(devices),
                k=int(k),
                predicted_bytes=int(profile.peak_bytes),
                compiled_bytes=int(compiled_bytes),
            )
            drift = _ml_passes.audit_point(
                profile.peak_bytes, compiled_bytes, self.name,
                len(devices), k=k,
            )
            if drift is not None:
                log.warning("%s", drift.message)
        except Exception as e:
            log.debug("memlens calibration skipped: %r", e)

    def _fused_ok(self, config: Dict[str, Any]) -> bool:
        """Whether THIS config may run fused windows. Pinned-host configs
        stay per-step: their step interleaves host/device memory-space moves
        (``compute_on``) that a scanned program would fold into one XLA
        program holding all K staged batches plus the host round-trips —
        exactly the residency the offload technique exists to avoid."""
        return bool(self.fused_dispatch_ok) and (
            self.param_memory_kind(config) != "pinned_host"
        )

    # ---------------------------------------------------------------- search
    def search(
        self, task: Any, devices: Sequence[Any], tid: int
    ) -> Tuple[Optional[Dict[str, Any]], Optional[float]]:
        """Time every grid point that fits and keep the fastest.

        Two stages, so that the host and the chip work at the same time:
        this thread, the caller's, walks the grid and prepares each point
        (``_prepare``: build, compile, memory check — tracing, lowering, a
        cache hit or a compile or a refusal; nothing of it allocates train
        state) and hands it over as it ends; a measuring thread behind it
        measures (``_measure``: init, stage, timing) strictly one point at a
        time (``_measured_behind``). The memory-frugal points are
        prepared first (``remat: True`` before the rest, the technique's
        order otherwise): they are the ones most likely to take timed steps,
        under which the others' preparation then runs. A ``remat: False``
        point whose ``remat: True`` twin (every other key alike) was over
        memory is over memory too, and ends so without being built:
        rematerialisation only ever lowers a program's peak, and the point
        would be traced, lowered and compiled to be refused (a period of six
        blocks: 20 s + 5 s + 53 s on the chip's host, PR 45). So does a point
        whose own verdict is on record by what it is made from
        (``utils/point_records``, PR 47), unless nothing gets timed: then such
        points run again in full. The winner is the fastest timed point
        wherever it stood; a tie goes to the technique's own order.
        """
        size = len(devices)
        stack = self._stack_fields(task)
        above = _metrics.current_span()

        def end(point: _GridPoint, outcome: str, **fields):
            # one ``trial_config`` event and one ``trial.config`` span per
            # grid point, on the thread the point ended on: which variant
            # measured what, which did not fit, which raised — the winner
            # alone hides the rest — and where its seconds went
            if point.ladder is not None:
                fields["ce_ladder"] = point.ladder
                point.span.set(ce_ladder=point.ladder)
            _metrics.event("trial_config", task=task.name, size=size,
                           technique=self.name, config=dict(point.config),
                           **stack,
                           **self._plan_fields(task, devices, point.config),
                           **fields)
            point.outcome = outcome
            point.span.set(outcome=outcome)
            point.span.close()

        def attempt(point: _GridPoint, fn):
            """One stage of a point (its preparation, its measurement) under
            its ``trial.config`` span. How a stage raises is how the point
            ended; only what is no ``Exception`` (a kill) goes further."""
            config = point.config
            try:
                with _metrics.under(point.span):
                    return fn()
            except InfeasibleConfig as e:
                log.info("%s trial %s infeasible: %s", self.name, config, e)
                point.span.set(reason=str(e))
                end(point, "infeasible", infeasible=str(e))
            except aot_cache.CompileRefused as e:
                # The chip's compiler refusing the program for memory (just
                # now, or on record from an earlier compile) is the memory
                # check's verdict, not a config that raised.
                log.info("%s trial %s for task %s refused by the compiler "
                         "(%s): %s", self.name, config, task.name, e.refusal,
                         e.first_line)
                point.refusal, point.compiler = e.refusal, e.first_line
                point.span.set(refusal=e.refusal)
                end(point, "refused", memory_rejected=True,
                    refusal=e.refusal, compiler=e.first_line)
            except Exception as e:  # a broken config must not kill the sweep
                # ...but a config that RAISED is not a config that lost: on
                # the chip a kernel variant that fails to lower would
                # otherwise lose to its dense twin in silence. Warn, and
                # count it into the report ``search()`` returns.
                log.warning("%s trial %s for task %s failed: %r",
                            self.name, config, task.name, e)
                point.error = f"{self.name} {config}: {e!r}"
                end(point, "error", error=repr(e))
            except BaseException as e:
                point.span.set(error=type(e).__name__)
                point.span.close()
                raise
            return None

        over_memory: List[Dict[str, Any]] = []  # the configs that ended so
        stash_over: List[Dict[str, Any]] = []   # ...whose stash rung did
        room: List[Optional[int]] = []   # what the rule leaves after the state

        def stash_rung(point: _GridPoint, recorded: bool) -> bool:
            """The first rung of a point whose fused head would stash more
            than the op keeps unasked (``_head_rungs``): the point prepared
            with the head stating ``stash``, where that is worth a build.
            True: the point is ready on that rung (its config now says so) or
            ended on it (it raised, it is infeasible); False: the rung is not
            kept (the static bound, the twin's rung, a record, the compiler,
            the memory rule: ``point.ladder`` says which) and the point goes
            on as the grid has it, where an unasked head recomputes. A rung
            is no grid point: it has no event of its own, and a rung refused
            counts no refusal."""
            config, said = point.config, point.ladder
            rung = dict(config, ce_mode="stash")
            if said["room_bytes"] is not None and \
                    said["stash_bytes"] > said["room_bytes"]:
                said["skipped"] = "static"   # no compile can make it fit
                return False
            if config.get("remat") is False and \
                    dict(config, remat=True) in stash_over:
                said["skipped"] = "remat"    # the same bytes over the twin's
                return False
            record = point_records.of(
                self, task, devices, rung, self._profile_window(rung),
                parent=point.span, read=recorded)
            if record.verdict is not None:
                said["skipped"] = "recorded"
                stash_over.append(dict(config))
                return False
            said["tried"].append("stash")
            why: Dict[str, Any] = {}

            def prepared():
                try:
                    return self._prepare(task, devices, rung, rung=why)
                except aot_cache.CompileRefused as e:
                    why.update(outcome="refused", refusal=e.refusal,
                               compiler=e.first_line)
                    return None

            point.ready = attempt(point, prepared)
            if point.ready is None and point.outcome is None:
                log.info("%s trial %s for task %s: the head's stash rung "
                         "(%.2f GiB) is not kept: %s", self.name, config,
                         task.name, said["stash_bytes"] / 2**30, why)
                said["refused"] = why
                if why["outcome"] in point_records.VERDICTS:
                    stash_over.append(dict(config))
                record.note(why["outcome"], why.get("compiler"))
                return False
            record.note(point.outcome)   # it fits, or no memory verdict
            if point.ready is not None:
                said["kept"] = "stash"
                point.config = rung
                point.span.set(config=dict(rung))
            return True

        def prepare(order: int, config: Dict[str, Any],
                    recorded: bool = True) -> _GridPoint:
            point = _GridPoint(order, config, _metrics.span(
                "trial.config", parent=above, task=task.name, size=size,
                technique=self.name, config=dict(config)).open())
            if config.get("remat") is False and \
                    dict(config, remat=True) in over_memory:
                point.span.set(implied_by="remat")
                end(point, "memory_rejected", memory_rejected=True,
                    implied_by="remat")
                return point
            head = self._head_rungs(task, devices, config)
            if head is not None:
                if not room:   # the task's, not the point's: once a search
                    room.append(self._room_after_state(task, devices))
                point.ladder = dict(head, room_bytes=room[0], tried=[],
                                    kept=None)
                if stash_rung(point, recorded):
                    return point
            # Its memory verdict may be on record by what the point is made
            # from (``utils/point_records``, PR 47): it then ends here too,
            # with nothing built, traced or lowered to find the text's record.
            record = point_records.of(
                self, task, devices, config, self._profile_window(config),
                parent=point.span, read=recorded)
            if record.verdict is not None:
                point.refusal, point.unbuilt = "recorded", True
                point.span.set(refusal="recorded", unbuilt=True)
                end(point, memory_rejected=True, refusal="recorded",
                    unbuilt=True, **record.verdict)
                over_memory.append(dict(config))
                return point
            if point.ladder is not None:
                point.ladder["tried"].append("recompute")
            point.ready = attempt(
                point, lambda: self._prepare(task, devices, config))
            if point.ready is None and point.outcome is None:
                # _prepare returns None only on the memory check
                end(point, "memory_rejected", memory_rejected=True)
            if point.outcome in ("refused", "memory_rejected"):
                over_memory.append(dict(config))
            elif point.ready is not None and point.ladder is not None:
                point.ladder["kept"] = "recompute"
            record.note(point.outcome, point.compiler)  # a verdict, or none
            return point

        def measure(point: _GridPoint) -> None:
            if point.outcome is not None:  # it ended where it was prepared
                return
            point.timed = attempt(
                point, lambda: self._measure(task, point.ready))
            point.ready = None  # the bundle cache keeps the programs
            if point.timed is not None:
                end(point, "timed", per_batch_s=point.timed[0])

        grid = list(enumerate(self.candidate_configs(task, size)))
        grid.sort(key=lambda oc: oc[1].get("remat") is not True)  # stable
        points, n_ahead = _measured_behind(grid, prepare, measure)
        if any(p.unbuilt for p in points) and \
                not any(p.outcome == "timed" for p in points):
            # A stale record may cost a point, never a job: nothing was timed,
            # so the points that ended on their records (and those implied)
            # run again in full before memory is reported.
            again = {p.order for p in points
                     if p.unbuilt or "implied_by" in p.span.fields}
            kept = [p for p in points if p.order not in again]
            over_memory[:] = [dict(p.config) for p in kept
                              if p.outcome in ("refused", "memory_rejected")]
            stash_over[:] = [c for c in stash_over
                             if c in [p.config for p in kept]]
            points = kept + _measured_behind(
                [oc for oc in grid if oc[0] in again],
                lambda o, c: prepare(o, c, False), measure)[0]

        best: Optional[_GridPoint] = None
        n_memory = n_error = 0
        refusals = {"fresh": 0, "recorded": 0}
        first_error: Optional[str] = None
        for point in points:
            if point.outcome == "timed":
                # the fastest; a tie goes to the technique's own order
                if best is None or ((point.timed[0], point.order)
                                    < (best.timed[0], best.order)):
                    best = point
            elif point.outcome in ("refused", "memory_rejected"):
                n_memory += 1
                if point.refusal is not None:
                    refusals[point.refusal] += 1
            elif point.outcome == "error":
                n_error += 1
                first_error = first_error or point.error
        with self._reports_lock:
            if best is not None:
                self._host_fracs[(task.name, size)] = best.timed[1]
            # Memory is the binding constraint only when EVERY candidate was
            # rejected by XLA memory analysis or refused by the compiler for
            # memory — a mesh/divisibility error in any config means smaller
            # sizes might still work, so monotone pruning must not engage.
            self._search_reports[(task.name, size)] = {
                "memory_infeasible": (
                    best is None and len(grid) > 0 and n_memory == len(grid)
                ),
                "configs": len(grid),
                "memory_rejected": n_memory,
                "errors": n_error,
                "first_error": first_error,
                "refusals_fresh": refusals["fresh"],
                "refusals_replayed": refusals["recorded"],
                # of the replayed: on the point's own record, nothing built
                "refusals_unbuilt": sum(p.unbuilt for p in points),
                # points that were ready before this thread asked for them
                "prepared_ahead": n_ahead,
            }
        if best is None:
            return None, None
        return dict(best.config), best.timed[0]

    @staticmethod
    def _stack_fields(task: Any) -> Dict[str, int]:
        """``stack_layers`` / ``stack_passes`` (and ``stack_kinds``, where the
        stack has several) of the task's model for the
        ``trial_config`` and ``task_interval`` events (nothing where the
        model, or a stand-in for a ``ModelSpec``, does not say)."""
        spec = task.get_model()
        layers = getattr(spec, "stack_layers", None)
        if layers is None:
            return {}
        out = {"stack_layers": layers, "stack_passes": spec.stack_passes}
        kinds = getattr(spec, "stack_kinds", None)
        if kinds:   # a stack of several block kinds: layers of each a period
            out["stack_kinds"] = kinds
        lead = getattr(spec, "stack_lead", None)
        if lead:    # layers before the periods, outside the scan
            out["stack_lead"] = lead
        return out

    def _plan_fields(self, task, devices, config) -> Dict[str, Any]:
        """What the grid point's ops were traced as, for its ``trial_config``
        event: ``<family>_plan`` for each op family that recorded a call
        (``ops/plans.py``), the first call's plan in its event form (a
        model's calls of one family are alike; the family's op file says what
        its plan holds), None where that call fell back to plain XLA ops.
        Beside them ``step_traces``: how often the model's Python step
        function was called for this grid point (its bundle's one trace: 1).
        Nothing where the point's bundle was never built."""
        bundle = self._cached_bundle(task, devices, config)
        if bundle is None:
            return {}
        out: Dict[str, Any] = {"step_traces": bundle.step_traces}
        for name, got in bundle.plans.items():
            out[f"{name}_plan"] = _plans.as_event(got[0])
        return out

    def _head_rungs(self, task: Any, devices: Sequence[Any],
                    config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Whether this grid point's fused head (``ops/ce.py``) is one whose
        backward mode the compile is asked about: ``{"stash_bytes": n}``, the
        bf16 logits the backward would read in place of computing its scores
        a second time. None where nobody is asked: the config or the model's
        own kwargs state a mode, the model takes none (no ``ce_mode`` in its
        config), model, loss and block offer this technique no fused head,
        or the op keeps the stash by itself (under ``ce.STASH_BYTES_MAX``)
        or runs no kernel here.

        Shapes in, a figure out, nothing traced: the head sees every token
        of the batch (of the shard's, where the loss runs under dp's
        ``shard_map``). Whether the technique's step really calls that head
        is read from the rung's own trace (``_prepare``, ``inert``). Never
        raises: what is wrong with the point is the build's to say, where it
        is counted."""
        from saturn_tpu.ops import ce

        if config.get("ce_mode"):
            return None
        try:
            spec = task.get_model(**self._model_overrides(config))
            cfg = spec.config
            if getattr(cfg, "ce_mode", "no such field") is not None:
                return None
            single = len(devices) <= 1
            if not self._fused_head_offered(spec, task, single) or \
                    self.param_memory_kind(config) == "pinned_host":
                return None
            tokens = int(np.prod(task.get_dataset().example_batch().shape))
            stash = ce.stash_over_the_constant(
                tokens // len(devices), int(cfg.d_model), int(cfg.vocab_size))
            return None if stash is None else {"stash_bytes": int(stash)}
        except Exception as e:
            log.debug("%s %s: the head's rungs are not known: %r",
                      self.name, config, e)
            return None

    def _room_after_state(self, task: Any,
                          devices: Sequence[Any]) -> Optional[int]:
        """What the 0.92 x HBM rule leaves a program after the train state
        (parameters and optimizer state, traced abstractly: nothing is
        allocated), known before anything is built: a stash larger than this
        is not worth a compile. The whole state a device, as where the fused
        head runs (one device, or dp's replicas). None where no limit is
        known or the state cannot be traced (no bound: the compile says)."""
        limit = hbm_limit(devices[0])
        if limit <= 0:
            return None
        try:
            params = task.get_model().abstract_init()
            state = (params, jax.eval_shape(
                task.hparams.make_optimizer().init, params))
        except Exception as e:
            log.debug("%s: the train state of %s was not traced: %r",
                      self.name, task.name, e)
            return None
        return int(0.92 * limit) - sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(state))

    def _profile_window(self, config: Dict[str, Any]) -> int:
        """K the trial should profile: steady-state execute() runs full
        windows of the max size, so that is what the MILP's per-batch times
        must measure — not the per-step program fused dispatch retired."""
        return max_window() if self._fused_ok(config) else 1

    def _prepare(
        self, task: Any, devices: Sequence[Any], config: Dict[str, Any],
        rung: Optional[Dict[str, Any]] = None,
    ) -> Optional[_Prepared]:
        """The host's half of a grid point: build (the one Python trace),
        compile (lowering, the text hash, a cache hit or a compile or a
        refusal), the memory check with its audit and, for a point that
        fits, the init program's compile. None = over memory. Allocates no
        train state, so it may run while another point is timed.

        The program is the one ``execute()`` dispatches at steady state: the
        fused K-step window where the config may run one, memory-checked as
        such (its peak holds the (K, B, T) stack), else the 1-step program.

        ``rung``: handed over where ``config`` is the stash rung of the
        point's head (``_head_rungs``) and not the point's last word; it
        receives why a rung that returns None was not kept: ``outcome``
        ``memory_rejected`` with the check's ``need_bytes`` and
        ``limit_bytes``, or ``inert`` where the step traced to no stashing
        head after all (this technique's step for this config does not call
        the model's fused loss: nothing is compiled for it).
        """
        bundle = self._spanned_build("trial.build", task, devices, config)
        if rung is not None and not any(
                getattr(plan, "mode", None) == "stash"
                for plan in bundle.plans.get("ce", ())):
            rung.update(outcome="inert")
            return None
        k = self._profile_window(config)
        program = self._spanned_compile("trial.compile", bundle, k)
        if not self._fits_compiled(program, devices, task=task, config=config,
                                   k=k, said=rung):
            if rung is not None:
                rung.update(outcome="memory_rejected")
            return None
        # The init program too: lowered and compiled here it is a plain call
        # where the state is put on the chip, and not a trace and a cache
        # retrieval on the measuring thread while this one holds the GIL
        # (1.4 s a point where the call is 0.2; read on the chip, PR 37).
        # ``jit`` keeps what ``lower().compile()`` made; nothing is allocated.
        with _metrics.span("trial.compile", program="init"):
            bundle.init.lower().compile()
        return _Prepared(bundle, program, k)

    def _measure(self, task: Any, prepared: _Prepared) -> Tuple[float, float]:
        """The chip's half of a grid point: (seconds/batch, host_fraction).

        The host fraction — staging cost (dataset slice + ``device_put``)
        relative to staging + device compute for one steady-state batch — is
        what the solver's co-location term consumes: a stage-bound job
        (fraction near 1) leaves the device idle most of the wall clock, so
        a compute-bound neighbor's windows can fill the bubble. The timed
        per-batch number stays device-only (the prefetcher hides staging at
        execute() time); staging is measured separately, outside the timed
        region.
        """
        bundle, program, k = prepared
        ds = task.get_dataset()
        with _metrics.span("trial.init"):
            state = bundle.init()
        if k > 1:
            # Pre-staged, per-call-fresh window stacks keep donation honest
            # and transfer out of the timed region — at execute() time the
            # prefetcher overlaps staging with compute, so a trial that
            # timed staging would overestimate.
            sharding = bundle.stacked_sharding()

            def stage(j: int):
                host = np.stack(
                    [np.asarray(ds.batch(j * k + i)) for i in range(k)]
                )
                return jax.device_put(host, sharding)

            # The stacks are staged here, not inside time_fused_window, so
            # that ``trial.timing`` is the device program alone; the second
            # ``trial.stage`` is the probe that prices staging. Three, for
            # the warm-up and two timed windows: whether the second is timed
            # is known only once the warm-up has run (a window of 2 s or more
            # is timed once, ``time_fused_window``), and a stack staged then
            # would be a transfer inside ``trial.timing``; the one not
            # offered is a third of this span's 0.01-0.12 s.
            with _metrics.span("trial.stage", k=k,
                               n_stacks=FUSED_WINDOW_STACKS):
                windows = [stage(j) for j in range(FUSED_WINDOW_STACKS)]
                jax.block_until_ready(windows)
            with _metrics.span("trial.timing", k=k) as sp:
                # one warm-up, then two windows or one: ``n_timed`` and
                # ``warmup_s`` arrive on the span
                t = time_fused_window(
                    program, state, windows.__getitem__, k, note=sp.set
                )
            with _metrics.span("trial.stage", k=k, n_stacks=1):
                t0 = _timeit.default_timer()
                probe = stage(0)
                jax.block_until_ready(probe)
                t_host = (_timeit.default_timer() - t0) / k
                del probe
            return t, _host_fraction(t_host, t)
        with _metrics.span("trial.stage", k=1):
            t0 = _timeit.default_timer()
            batch = jax.device_put(ds.batch(0), bundle.batch_sharding)
            jax.block_until_ready(batch)
            t_host = _timeit.default_timer() - t0
        with _metrics.span("trial.timing", k=1, n_timed=3):
            t = time_train_step(program, state, batch, n_timed=3, n_warmup=2)
        return t, _host_fraction(t_host, t)

    def _spanned_build(self, name: str, task, devices, config,
                       parent=None) -> _Bundle:
        """``self.build`` under a span that says whether the bundle cache
        had the bundle and how many Python traces of the train step the
        bundle has made, this one included (``traces``: 1; ``trial.build`` in
        a trial, ``launch.build`` at an interval's launch)."""
        with _metrics.span(name, parent=parent, task=task.name) as sp:
            if _metrics.enabled():
                hit = self._cached_bundle(task, devices, config) is not None
                sp.set(cache="hit" if hit else "miss")
            bundle = self.build(task, devices, config)
            sp.set(traces=bundle.step_traces)
            return bundle

    @staticmethod
    def _spanned_compile(name: str, bundle: _Bundle, k: int, parent=None):
        """The bundle's K-step window program (``k > 1``) or its 1-step
        program, compiled at most once per bundle, under a span that says
        whether this call was the one that compiled it (``was_warm``),
        whether the AOT cache gave the executable (``aot``) and, where the
        compiler refused the program for memory, whether it did so now or
        on record (``refusal`` = ``fresh`` / ``recorded``). ``trace`` is
        ``shared``: the program replays the bundle's kept trace (``own`` would
        say that making it called the model's Python step function again)."""
        def aot_hits() -> int:
            stats = aot_cache.stats()
            return stats["hits"] + stats["warm_hits"]

        with _metrics.span(name, parent=parent, k=int(k),
                           program="window" if k > 1 else "step") as sp:
            if _metrics.enabled():
                sp.set(was_warm=bool(bundle.has_fused(k) if k > 1
                                     else bundle._compiled is not None))
                before = aot_hits()
            traces = bundle.step_traces
            try:
                out = bundle.fused_compiled(k) if k > 1 else bundle.compiled
            except aot_cache.CompileRefused as e:
                sp.set(refusal=e.refusal)
                raise
            finally:
                sp.set(trace="shared" if bundle.step_traces == traces
                       else "own")
            if _metrics.enabled():
                sp.set(aot="hit" if aot_hits() > before else "miss")
            return out

    # --------------------------------------------------------------- execute
    def execute(
        self,
        task: Any,
        devices: Sequence[Any],
        tid: int,
        override_batch_count: Optional[int] = None,
        window_size: Optional[int] = None,
    ) -> None:
        """Run one interval of ``n`` batches as an async step pipeline.

        Dispatch shape: ``n // K`` fused K-step windows (one ``lax.scan``
        program per window, single loss readback at interval end) followed
        by an ``n % K`` per-step tail on the exact legacy 1-step program —
        the same train step scanned vs called, so the loss trajectory is
        bit-identical either way. Batch staging (numpy slice + device_put)
        runs on a prefetch thread one unit ahead of the device, closing the
        host/device bubble of the old step-at-a-time loop.

        ``window_size``: the engine plumbs ``pick_window(n)`` here so K is
        chosen from the interval batch budget; ``None`` chooses locally
        (``choose_window``). K is forced to 1 for configs where fused
        dispatch is invalid (``_fused_ok``) and for n < 2 — short intervals
        never pay a window compile.

        Implemented as a full drain of ``interval_dispatches`` — the solo
        path and the co-scheduled path run the identical per-unit dispatch
        sequence, which is what makes the interleaved trajectory guarantee
        a structural property rather than a test assertion.
        """
        for _ in self.interval_dispatches(
            task, devices, tid,
            override_batch_count=override_batch_count,
            window_size=window_size,
        ):
            pass

    def interval_dispatches(
        self,
        task: Any,
        devices: Sequence[Any],
        tid: int,
        override_batch_count: Optional[int] = None,
        window_size: Optional[int] = None,
        shared: bool = False,
    ):
        """One interval as resumable per-window sub-dispatches (a generator).

        Yield protocol, in order:

        - ``("waiting", u)`` — shared mode only: unit ``u``'s staged batch is
          not ready yet. The caller (the engine's co-schedule group launcher)
          should dispatch another member's windows instead of parking here;
          resuming retries the poll.
        - ``("dispatched", u)`` — unit ``u``'s device program was enqueued
          (dispatch is async; the device may still be running it).
        - ``("drain", n_units)`` — every unit has been dispatched. Resuming
          past this performs the blocking finalization (loss readback,
          realized feedback, checkpoint write, live-state republish) and
          ends the generator.

        ``shared=True`` is co-schedule mode: staging is polled non-blockingly
        (``DevicePrefetcher.try_next``), the first-unit warmup fence is
        skipped, and per-task realized feedback / samples-per-sec are left to
        the caller's group wall-time attribution — the device-side dispatch
        ORDER is exactly the solo path's, so each member's loss/checkpoint
        trajectory is bit-identical to running alone.
        """
        config = dict(task.selected_strategy.params or {})
        ts_launch = _time.time()  # before any compile this interval needs
        # The ``task_interval`` event's own id: it is emitted by hand below
        # (this generator yields while the stretch is open), and the phases
        # before the first step and after the last are its children. No span
        # is open across a ``yield``, and none sits in the dispatch loop.
        ti = _metrics.span("task_interval").open()
        bundle = self._spanned_build("launch.build", task, devices, config,
                                     parent=ti)
        key = self._bundle_key(task, devices, config)

        live = getattr(task, "_live_state", None)
        if live is not None and live[0] == key:
            # Same technique/config/block as the previous interval: the
            # device-resident state is still authoritative — skip the
            # disk round-trip (the ckpt is only needed when the solver
            # *switches* technique or block between intervals).
            state = live[1]
        elif task.has_ckpt():
            # Resume — map saved shards directly onto THIS technique's
            # shardings (cross-technique resharding; the reference's
            # kill-and-respawn reload, ``FSDP.py:189-191``). restore_sharded
            # assembles each leaf lazily per destination shard from the
            # manifest, so resume never materializes a full replicated host
            # tree (and legacy single-file checkpoints take its compat path).
            from saturn_tpu.core import distributed as _dist

            with _metrics.span("launch.restore", parent=ti,
                               task=task.name) as sp:
                state = ckpt.restore_sharded(
                    task.ckpt_path, bundle.state_shapes, bundle.state_shardings
                )
                if _metrics.enabled():
                    sp.set(bytes=int(sum(
                        x.nbytes for x in jax.tree_util.tree_leaves(state)
                    )))
            # Data cursor is derived from the trained-step count, so resume
            # is restart-safe (the reference replayed the iterator from the
            # in-memory cursor only, ``Task.py:130-140``).
            # cursor_for_step folds the quarantine skip-list into the
            # modulus, so a restore after quarantine replay lands on the
            # surviving sequence.
            step_leaf = state["step"]
            task.current_batch = task.cursor_for_step(
                int(np.asarray(_dist.host_array(step_leaf)))
            )
        else:
            with _metrics.span("launch.init", parent=ti, task=task.name):
                state = bundle.init()

        # The cached buffers get donated into the first step below, so they
        # must not be offered again if this interval crashes mid-run: drop
        # the cache now and re-publish after the end-of-interval checkpoint.
        task._live_state = None

        n = override_batch_count
        if n is None:
            n = task.total_batches
        n = int(n)

        from saturn_tpu.core import distributed as _dist
        from saturn_tpu.data.prefetch import NOT_READY, DevicePrefetcher

        start = task.current_batch

        # -------- window plan: n_windows fused units + per-step tail units
        k = choose_window(n) if window_size is None else int(window_size)
        k = max(1, min(k, max(n, 1)))
        if k > 1 and not self._fused_ok(config):
            k = 1
        n_windows = n // k if k > 1 else 0
        # unit = (is_fused, batch offset within the interval)
        units: List[Tuple[bool, int]] = [(True, w * k) for w in range(n_windows)]
        units += [(False, j) for j in range(n_windows * k, n)]

        # Whether the program the FIRST unit runs had already compiled: if
        # so, even an n==1 interval yields a clean compile-free sample (a
        # task forecast at one batch per interval must not be starved of
        # feedback forever — its wrong trial profile is exactly what the
        # feedback exists to fix).
        first_fused = bool(units) and units[0][0]
        was_warm = (
            bundle.has_fused(k) if first_fused else bundle._compiled is not None
        )
        # AOT-compile every program this interval needs BEFORE the clock
        # starts — compile cost belongs to neither samples/sec nor the
        # realized-feedback window (docs/parity.md, round 10).
        fused_fn = (
            self._spanned_compile("launch.compile", bundle, k, parent=ti)
            if n_windows else None
        )
        single_fn = (
            self._spanned_compile("launch.compile", bundle, 1, parent=ti)
            if any(not f for f, _ in units) else None
        )
        stacked_sharding = bundle.stacked_sharding() if n_windows else None

        def stage(u: int):
            fused_u, off = units[u]
            if fused_u:
                host = np.stack([
                    np.asarray(task.batch_at(start + off + j)) for j in range(k)
                ])
                return _dist.put_global(host, stacked_sharding)
            # put_global == device_put single-process; on a multi-host
            # block each process's devices take their slice locally
            return _dist.put_global(
                task.batch_at(start + off), bundle.batch_sharding
            )

        loss = None
        # Every unit's carried loss stays on-device for the sentinel's
        # interval-end fold (tiny buffers: one scalar / (K,) per unit).
        unit_losses: List[Any] = []
        # a step whose second output is ``(loss, counters)`` (a routed
        # model's): the counters stay on the device beside the losses and are
        # read back with them, after the interval's one drain
        unit_counters: List[Dict[str, Any]] = []
        t_all0 = _timeit.default_timer()
        ts_start = _time.time()  # same clock as the metrics events' ``ts``
        t_steady = t_all0
        # Batch staging runs one unit ahead on the prefetch thread; the
        # loop body only dispatches device programs.
        prefetch = DevicePrefetcher(len(units), stage, depth=2)
        try:
            u = 0
            while u < len(units):
                if shared:
                    try:
                        dev_batch = prefetch.try_next()
                    except StopIteration:
                        break
                    if dev_batch is NOT_READY:
                        yield ("waiting", u)
                        continue
                else:
                    try:
                        dev_batch = next(prefetch)
                    except StopIteration:
                        break
                if units[u][0]:
                    state, loss = fused_fn(state, dev_batch)  # loss: (K,)
                else:
                    state, loss = single_fn(state, dev_batch)
                if isinstance(loss, tuple):
                    loss, counters = loss
                    unit_counters.append(counters)
                unit_losses.append(loss)
                if u == 0 and len(units) > 1 and not shared:
                    # The first unit still pays one-time warmup (executable
                    # load, constant transfer) plus the un-overlapped first
                    # staging. Keep it out of the realized-feedback window:
                    # block on its result and restart the steady-state timer.
                    # (Shared mode skips the fence — blocking here would
                    # stall the group launcher; the group owns timing.)
                    jax.block_until_ready(loss)  # lint: sanctioned-host-sync
                    t_steady = _timeit.default_timer()
                yield ("dispatched", u)
                u += 1
            # All device work for this member is enqueued. The caller may
            # resume other members before paying this member's blocking
            # finalization below.
            yield ("drain", len(units))
        finally:
            # SimulatedKill is a BaseException: a killed interval must not
            # leak a producer thread that keeps slicing batches from a task
            # the harness is rolling back.
            prefetch.close()
        if loss is not None:
            from saturn_tpu.health import sentinel as _sentinel

            scfg = _sentinel.get_config()
            poison = task.__dict__.pop("_health_poison", None)
            rep = None
            # drain to the loss value: the sentinel's fold and the ONE host
            # read-back of the interval
            with _metrics.span("readback", parent=ti, task=task.name):
                if scfg.enabled:
                    import jax.numpy as jnp

                    # Sentinel path: fold the interval's full per-step loss
                    # vector through one jitted on-device scan and read back the
                    # fixed-shape report instead of the bare scalar — STILL one
                    # host readback per interval (the reliable queue drain, see
                    # utils/timing.py note), and the report's last slot is the
                    # same final loss the bare readback returned.
                    losses_vec = jnp.concatenate(
                        [jnp.reshape(x, (-1,)) for x in unit_losses]
                    )
                    if poison is not None:
                        ov = _sentinel.poison_overrides(
                            poison, n, lambda j: task.dataset_index(start + j)
                        )
                        if ov is not None:
                            # Chaos injection corrupts the OBSERVED losses only
                            # (a device-side scatter); train state is untouched,
                            # so post-rollback trajectories stay fault-free.
                            losses_vec = losses_vec.at[ov[0]].set(ov[1])
                    carry = getattr(task, "_sentinel_carry", None)
                    if carry is None:
                        carry = _sentinel.carry_init()
                    rep = np.asarray(
                        _dist.host_array(_sentinel.fold(carry, losses_vec, scfg))
                    )
                    loss_val = float(rep[_sentinel.REP_LAST_LOSS])
                else:
                    # ONE host readback per interval — the reliable queue drain
                    # (see utils/timing.py note). A fused window's loss is the
                    # (K,) per-step trajectory; its last entry is the interval's
                    # final loss, identical to what the 1-step path would report.
                    loss_val = float(_dist.host_array(loss).reshape(-1)[-1])
            fault = _sentinel.inspect(rep) if rep is not None else None
            if fault is not None:
                cause, first_off, bad_count = fault
                fused_part = n_windows * k
                if first_off < fused_part:
                    window = first_off // k
                else:
                    window = n_windows + (first_off - fused_part)
                # Fault path (cold): pull the observed vector and blame the
                # exact bad steps. Quarantine resolution must be per batch —
                # blaming the whole K-step window would skip-list healthy
                # data (and with K == epoch length, the entire dataset). A
                # finite spike is only locatable via the report's first-bad
                # slot; non-finite steps are all recoverable host-side.
                host_losses = np.asarray(
                    _dist.host_array(losses_vec)
                ).reshape(-1)
                bad_offsets = {
                    int(j) for j in np.flatnonzero(~np.isfinite(host_losses))
                }
                if first_off >= 0:
                    bad_offsets.add(int(first_off))
                bad_batches = tuple(sorted(
                    {task.dataset_index(start + j) for j in bad_offsets}
                ))
                log.warning(
                    "task %s: sentinel tripped (%s) at interval step %d "
                    "(window %d, %d bad step(s)) — discarding interval",
                    task.name, cause, first_off, window, bad_count,
                )
                # Raised BEFORE realized feedback, the checkpoint write and
                # the live-state republish: a faulted interval never becomes
                # durable state, and the engine only advances the cursor
                # (task.reconfigure) on success — so the last published
                # checkpoint is the exact rollback target.
                raise _sentinel.NumericFaultError(
                    task.name, window, cause, step=first_off,
                    loss=loss_val, batch_indices=bad_batches,
                    bad_count=bad_count,
                )
            if rep is not None:
                # Only a healthy interval advances the persisted EWMA carry;
                # a faulted one discards it with the rest of its state.
                task._sentinel_carry = rep[:2].copy()
            t_end = _timeit.default_timer()
            elapsed_all = t_end - t_all0
            bs = task.get_dataset().batch_size
            sps = n * bs / max(elapsed_all, 1e-9)
            first_unit_batches = k if first_fused else 1
            if shared:
                # Co-scheduled: this member's wall clock includes the
                # interleaved neighbors' device windows, so neither
                # samples/sec nor realized per-batch feedback can be read
                # off it here — the group launcher attributes the group's
                # wall time across members (``engine.py``).
                per_batch = elapsed_all / max(n, 1)
            elif len(units) > 1:
                # per-job samples/sec — the BASELINE.md per-job metric — and
                # the realized per-batch time (vs the profiled estimate
                # forecast used).
                task.last_samples_per_sec = sps
                # feed the profiled-vs-realized loop from the steady-state
                # window only (units 2..); a warmup-dominated first unit
                # would otherwise inflate the EWMA and propagate to every
                # sibling strategy. Window-granular: the divisor is the
                # batch count the timed units actually retired.
                per_batch = (t_end - t_steady) / max(n - first_unit_batches, 1)
                task.note_realized_per_batch(per_batch)
            else:
                task.last_samples_per_sec = sps
                per_batch = elapsed_all / max(n, 1)
                if was_warm:
                    # single-unit interval on an already-compiled program:
                    # still a clean sample — without it a task scheduled one
                    # batch per interval never gets corrected.
                    task.note_realized_per_batch(per_batch)
            # Achieved TFLOP/s + MFU for this interval: shardflow's static
            # per-step FLOP count (cached per compiled program) over the
            # measured window wall time, normalized by the block's aggregate
            # published peak (``utils/peaks``, keyed by device_kind — an
            # accelerator that is not listed raises; the host CPU has no
            # peak, so there only ``tflops`` is reported). Omitted when the
            # step can't be traced (fields are additive, consumers treat
            # them as optional).
            perf = {}
            if _metrics.enabled():
                perf.update(self._stack_fields(task))
                # the per-step trajectory (one scalar or (K,) per unit, all
                # already computed: the readback above drained the queue)
                perf["losses"] = [
                    float(x) for u in unit_losses
                    for x in np.asarray(_dist.host_array(u)).reshape(-1)
                ]
                perf.update(_counter_fields(unit_counters))
                with self._flops_lock:
                    cached = key in self._flops_cache
                # what the package's own tflops / mfu costs the interval: the
                # first one of a program runs shardflow over the bundle's
                # kept trace
                with _metrics.span("step_flops", parent=ti, task=task.name,
                                   cached=cached, trace=self._trace_source(
                                       task, devices, config)):
                    step_flops = self._step_flops(task, devices, config)
                if step_flops:
                    achieved = step_flops * n / max(elapsed_all, 1e-9)
                    perf["tflops"] = round(achieved / 1e12, 4)
                    if devices[0].platform != "cpu":
                        from saturn_tpu.utils.peaks import peak_flops

                        perf["mfu"] = round(
                            achieved
                            / (max(len(devices), 1) * peak_flops(devices[0])),
                            6,
                        )
            # Where the state really lived (read off the arrays, not the plan),
            # when this gang took its block (``ts_launch``, before compiles)
            # and when its device work began (``ts_start``): what lets a
            # reader of the events check a gang against its planned block and
            # two gangs against each other.
            on_devices = sorted({
                d.id
                for leaf in jax.tree_util.tree_leaves(state)
                for d in leaf.sharding.device_set
            })
            _metrics.event(
                "task_interval", task=task.name, technique=self.name,
                batches=n, loss=loss_val, samples_per_sec=round(sps, 2),
                per_batch_s=per_batch, window=k, fused_windows=n_windows,
                coscheduled=bool(shared), devices=on_devices,
                ts_launch=ts_launch, ts_start=ts_start, elapsed_s=elapsed_all,
                **ti.ids(), **perf,
            )
            log.info("task %s [%s]: ran %d batches (K=%d, %d fused windows), "
                     "loss %.4f, %.1f samples/s",
                     task.name, self.name, n, k, n_windows, loss_val, sps)

        # Full train-state checkpoint (params + opt state + step): fixes the
        # reference's dropped-optimizer wart (``FSDP.py:220``). The disk write
        # overlaps the next interval (device->host copy happens here; see
        # utils/checkpoint.save_async) — interval boundaries don't stall the
        # gang on GB-scale npz writes.
        with _metrics.under(ti):  # ckpt.wait_pending / .snapshot / .write
            ckpt.save_async(task.ckpt_path, state)
        task._live_state = (key, state)
