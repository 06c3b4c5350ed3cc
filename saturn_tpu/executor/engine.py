"""Execution engine: forecast + dependency-gated gang launch for one interval.

Reference: ``saturn/executor/executor.py:25-178``. The reference's control
plane was Ray actors — ``DependencyHolder`` (asyncio events, ``:25-47``),
``LauncherActor`` (blocks on deps, spawns an ``ExecutorActor`` pinned to a
node with ``num_gpus`` reserved, ``:51-67``). One host drives an entire TPU
slice, so the TPU-native control plane is plain threads + ``threading.Event``
(SURVEY.md §5: "Ray is unnecessary"): each task gets a launcher thread that
waits for its dependency events, runs the technique on its assigned device
block, then signals completion. Device isolation comes from the plan itself —
the MILP guarantees concurrently-running tasks occupy disjoint blocks.
"""

from __future__ import annotations

import logging
import threading
import time as _time
import timeit
from typing import Dict, List, Optional, Sequence, Tuple

from saturn_tpu.analysis import concurrency as tsan
from saturn_tpu.analysis.concurrency import sched_point
from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.solver.milp import Plan
from saturn_tpu.utils import metrics

logger = logging.getLogger("saturn_tpu")


def forecast(
    task_list: Sequence,
    interval: float,
    plan: Plan,
) -> Tuple[List, Dict[str, int], List]:
    """Which tasks run this interval, for how many batches, and which finish.

    Near-verbatim port of the reference's pure-arithmetic forecast
    (``executor.py:132-178``): a task runs if its planned start falls inside
    the interval; its batch budget is the remaining interval time divided by
    its profiled per-batch time, capped at remaining batches. Side effects
    mirror the reference's online re-estimation (``:165-177``): remaining
    ``total_batches`` and every strategy's remaining ``runtime`` are
    decremented by the work about to run.
    """
    relevant, batches, completed = [], {}, []
    for task in task_list:
        a = plan.assignments.get(task.name)
        if a is None or a.start >= interval:
            continue
        strat = task.strategies[a.apportionment]
        pbt = max(strat.per_batch_time, 1e-9)
        # A task scheduled inside the interval always gets >= 1 batch: a
        # per-batch time longer than the interval must still make progress,
        # otherwise the orchestrator livelocks re-solving forever.
        budget = max(1, int((interval - a.start) / pbt))
        n = min(budget, task.total_batches)
        if n <= 0:
            continue
        relevant.append(task)
        batches[task.name] = n
        # online re-estimation: all strategies advance by the same batch count
        # (``executor.py:165-172``)
        task.total_batches -= n
        for s in task.strategies.values():
            if s.feasible:
                s.runtime = max(0.0, s.per_batch_time * task.total_batches)
        if task.total_batches <= 0:
            completed.append(task)
    return relevant, batches, completed


def rollback_forecast(task, n_batches: int) -> None:
    """Undo :func:`forecast`'s optimistic accounting for a task whose
    interval never ran to durable completion (preemption, retryable failure):
    the pre-deducted batches go back on the budget and every feasible
    strategy's remaining runtime is re-derived from its per-batch profile —
    the checkpoint is the ground truth the next attempt resumes from.
    Shared by the batch orchestrator's retry/preemption paths and the online
    service's requeue path.

    Window granularity (fused multi-step dispatch) changes nothing here:
    an interval is all-or-nothing — ``on_task_done`` only fires after the
    technique ran every budgeted batch, so a preemption mid-window (or
    mid-tail) discards the whole attempt and this rollback restores the
    FULL forecast deduction, exactly. There is no partial-window credit to
    account for: device state from a half-run scan program is unreachable,
    and the end-of-interval checkpoint never happened.
    """
    task.total_batches += n_batches
    for s in task.strategies.values():
        if s.feasible:
            s.runtime = s.per_batch_time * task.total_batches


def pick_window(n_batches: int, cap: Optional[int] = None) -> int:
    """Fused multi-step window K for an interval batch budget — the engine
    side of the async step pipeline: K comes from the forecast's budget so
    the technique runs ``n // K`` fused windows plus an exact per-step tail.
    Delegates to the technique layer's policy; imported lazily to keep
    executor -> parallel a call-time edge.

    ``cap`` is the window ceiling the caller resolved ONCE at interval start
    (:func:`_window_cap`): ``execute`` passes it to every launcher so a
    mid-run ``SATURN_TPU_MAX_WINDOW`` flip cannot split one interval across
    two window policies. ``None`` re-reads the env (standalone callers)."""
    from saturn_tpu.parallel.spmd_base import choose_window

    return choose_window(n_batches, cap=cap)


def _window_cap() -> int:
    """Resolve the fused-window ceiling (env ``SATURN_TPU_MAX_WINDOW``) —
    called exactly once per interval, at the top of ``execute``."""
    from saturn_tpu.parallel.spmd_base import max_window

    return max_window()


def _execute_kwargs(tech, n_batches: int, cap: Optional[int] = None) -> Dict[str, int]:
    """The optional kwargs this technique's ``execute`` accepts. Gated on
    ``supports_windows`` so plugin techniques (and test fakes) with the bare
    ``BaseTechnique`` signature keep working unchanged."""
    if getattr(tech, "supports_windows", False):
        return {"window_size": pick_window(n_batches, cap)}
    return {}


def _coschedule_find(run_tasks, plan):
    """Union-find root function over the plan's co-schedule groups,
    restricted to the launched tasks. Members of one group are one condensed
    node: they run interleaved on one shared launcher, so ordering and race
    properties are checked between groups, never inside one. Groups that
    share a member merge (one launcher must own a task).

    Thin delegate: the implementation lives in
    ``analysis.plan_verifier.coschedule_find`` — one condensed-graph
    construction shared by the dynamic guard and the static verifier."""
    from saturn_tpu.analysis import plan_verifier

    return plan_verifier.coschedule_find((t.name for t in run_tasks), plan)


def _check_disjoint(run_tasks, plan) -> None:
    """Device-race + deadlock guard for the gang launch. The MILP's plans
    satisfy both properties by construction; a hand-built or corrupted plan
    that violates them would either run two XLA programs on the same chips
    concurrently (silent corruption, not a crash) or park launcher threads
    on events that never fire (silent hang) — the engine refuses loudly
    instead (SURVEY §5 concurrency-safety: detection, not just avoidance).

    Thin delegate into the static analyzer
    (``analysis.plan_verifier.check_launch_invariants``): the race / cycle /
    intra-group-edge rules are ONE implementation with two call sites —
    here, at the last line of defense before launch, and in the plan
    verifier that gates every adoption path (solve, re-solve, journal
    replay, migration). Raises ``RuntimeError`` with the historical
    message on the first violation, in the historical check order
    (groupmate edges, then cycles, then pairwise races)."""
    from saturn_tpu.analysis import plan_verifier

    plan_verifier.check_launch_invariants([t.name for t in run_tasks], plan)


def _coschedule_groups(run_tasks, plan) -> List[List]:
    """The co-schedule groups actually launching this interval: lists of
    Task objects (>= 2 running members each), one shared launcher per list.
    Tasks not in any group (or whose groupmates aren't running this
    interval) launch on the normal per-task path.

    Callers pass ``run_tasks`` with fusion-group members already removed
    (:func:`_fused_groups` claims them first): the condensed union-find
    merges fused groups too, so leaving them in would hand a stacked group
    to the interleaving launcher."""
    find = _coschedule_find(run_tasks, plan)
    by_root: Dict[str, List] = {}
    for t in run_tasks:
        by_root.setdefault(find(t.name), []).append(t)
    return [g for g in by_root.values() if len(g) >= 2]


def _fused_groups(run_tasks, plan) -> List[List]:
    """The fusion groups actually launching this interval: lists of Task
    objects (>= 2 running members each, in the plan's stack order), one
    stacked program per list (``parallel/fused.run_fused_interval``). A
    group whose running membership shrank below 2 degenerates to the normal
    per-task path — a stack of one is just the solo program with an extra
    axis."""
    by_name = {t.name: t for t in run_tasks}
    out: List[List] = []
    claimed: set = set()
    for grp in getattr(plan, "fused", None) or []:
        members = [by_name[n] for n in grp
                   if n in by_name and n not in claimed]
        if len(members) >= 2:
            out.append(members)
            claimed.update(t.name for t in members)
    return out


def _join_with_watchdog(watch, t0, hung, hung_lock, errors, events) -> None:
    """Join launcher threads under per-thread watchdog deadlines.

    ``watch`` is ``[(thread, member task names, deadline_s | None)]``. A
    thread still alive past its deadline is ABANDONED: each of its tasks
    gets a ``HungDispatchError`` recorded on its behalf (the thread itself
    is wedged — it cannot raise), its completion event fires so dependents
    unblock, and the engine stops joining the thread. The daemon thread may
    wake later; every state commit in the launchers is gated on the hung
    set, so a late wake cannot overwrite this verdict.
    """
    from saturn_tpu.health.guardian import HungDispatchError

    pending = list(watch)
    while pending:
        for entry in list(pending):
            th, names, deadline = entry
            th.join(timeout=0.02)
            if not th.is_alive():
                pending.remove(entry)
                continue
            if deadline is None:
                continue
            elapsed = timeit.default_timer() - t0
            if elapsed > deadline:
                logger.error(
                    "watchdog: abandoning launcher %s after %.1fs "
                    "(deadline %.1fs) — task(s) %s marked hung",
                    th.name, elapsed, deadline, names,
                )
                with hung_lock:
                    for name in names:
                        if name not in hung:
                            hung.add(name)
                            errors[name] = HungDispatchError(
                                name, deadline, elapsed
                            )
                for name in names:
                    events[name].set()
                pending.remove(entry)


def execute(
    run_tasks: Sequence,
    batches: Dict[str, int],
    interval: float,
    plan: Plan,
    topology: SliceTopology,
    failure_policy: str = "raise",
    health=None,
    faults=None,
    interval_index: int = 0,
    on_task_start=None,
    on_task_done=None,
    guardian=None,
) -> Dict[str, BaseException]:
    """Gang-execute one interval (reference ``executor.py:88-129``).

    Per task: wait on dependency events (the MILP's ordering edges), run the
    selected technique on the assigned contiguous block, advance the data
    cursor, signal completion. Ends with a barrier + under/over-estimate log
    (``:123-129``).

    ``failure_policy``: ``"raise"`` re-raises the first task failure after
    the barrier (the reference's crash-the-batch behavior,
    ``my_multiprocessing.py:108-176``); ``"drop"`` returns the failures so
    the orchestrator can evict those tasks and keep the batch running —
    failure isolation the reference lacks (SURVEY.md §5 "no elasticity").
    Either way every other task finishes its interval first.

    ``health`` (a ``resilience.FleetHealthMonitor``) turns on the elastic
    hooks: per-block step timings feed straggler detection, and a device
    that dies mid-interval (``faults`` watchdog, or a real platform notice)
    aborts-and-requeues — not-yet-launched tasks and tasks whose block lost
    a chip surface as ``PreemptedError`` (never raised even under
    ``"raise"``: preemption is the fleet's fault, the orchestrator requeues
    and replans). ``faults`` additionally injects this interval's scheduled
    transient crashes and arms the mid-interval watchdog timers. Elastic
    hooks are single-host only (the multi-host path ignores them; the
    orchestrator refuses the combination up front).

    ``on_task_start`` (single-host only): callback invoked with the task name
    from its launcher thread once dependencies and the preemption gate have
    cleared, immediately before the technique runs. The online job service
    uses it to mark jobs RUNNING at the true launch instant.

    ``on_task_done`` (single-host only): callback ``(name, n_batches)``
    invoked from the launcher thread only after the task's interval fully
    succeeded — technique executed, mid-run preemption gate cleared, data
    cursor advanced. The durability layer journals realized iterations from
    here: a batch count passed to ``on_task_done`` really ran, so a failed
    or preempted attempt never reaches the ledger.

    ``guardian`` (a ``health.TrainingGuardian``) turns on the hung-dispatch
    watchdog: each launcher thread is deadlined at ``floor + k x`` its
    profiled window work; past the deadline the engine ABANDONS the thread
    (records a ``HungDispatchError`` on its task(s), fires their completion
    events so dependents unblock, stops joining it) and returns. The
    abandoned daemon thread is gated out of every state commit (cursor
    advance, ``on_task_done``, error recording) the moment it is declared
    hung. One benign race remains by design: a launcher that passes the gate
    and is declared hung DURING its technique's final checkpoint write can
    leave a newer checkpoint than the rollback target — the retry then
    resumes slightly ahead and re-trains the difference, which costs
    makespan, never correctness. With a guardian attached, health faults
    (``NumericFaultError``/``HungDispatchError``) are also exempt from
    ``failure_policy="raise"`` — like preemptions, they belong to the
    caller's recovery policy, not the crash-the-batch path.
    """
    from saturn_tpu.core import distributed

    if distributed.is_multihost():
        return _execute_multihost(run_tasks, batches, interval, plan,
                                  topology, failure_policy)

    _check_disjoint(run_tasks, plan)

    from saturn_tpu.resilience.faults import PreemptedError

    # Resolve the fused-window ceiling ONCE for the whole interval: every
    # launcher below receives this cap, so a mid-run SATURN_TPU_MAX_WINDOW
    # flip cannot split one interval across two window policies.
    window_cap = _window_cap()

    sched_point("engine.execute")
    events = {t.name: threading.Event() for t in run_tasks}
    running = {t.name for t in run_tasks}
    errors: Dict[str, BaseException] = {}

    # Hung-dispatch watchdog state: tasks whose launcher was abandoned. Every
    # error write and post-run commit below is gated on membership, so a
    # wedged thread that eventually wakes cannot overwrite the watchdog's
    # verdict or advance state the caller already rolled back.
    hung: set = set()
    hung_lock = tsan.lock("engine.hung")

    def _abandoned(name: str) -> bool:
        with hung_lock:
            return name in hung

    def _record_error(
        name: str, e: BaseException, keep_first: bool = False
    ) -> None:
        with hung_lock:
            if name not in hung:
                if keep_first:
                    errors.setdefault(name, e)
                else:
                    errors[name] = e

    def _stall_then_check(name: str) -> bool:
        """Apply an injected dispatch stall; True iff this launcher was
        watchdog-abandoned during the stall (caller must bail without
        touching task state — the attempt already failed)."""
        stall = (
            faults.dispatch_stall_s(name, interval_index)
            if faults is not None and hasattr(faults, "dispatch_stall_s")
            else 0.0
        )
        if stall > 0.0:
            logger.warning(
                "injected dispatch stall: wedging %s for %.1fs", name, stall
            )
            _time.sleep(stall)
        return _abandoned(name)

    def _set_poison(name: str, task) -> None:
        """Hand the sentinel this interval's observation-level loss poisoning
        (chaos injection), if any is scheduled for this task."""
        if faults is not None and hasattr(faults, "numeric_plan"):
            p = faults.numeric_plan(name, interval_index)
            if p:
                task._health_poison = p

    abort = threading.Event()
    timers = (
        faults.arm_watchdog(interval_index, health, abort)
        if faults is not None and health is not None
        else []
    )

    def launcher(task, tid: int):
        sched_point("engine.launcher")
        try:
            for dep in plan.dependencies.get(task.name, ()):
                if dep in running:
                    events[dep].wait()
            a = plan.assignments[task.name]
            devices = topology.block_devices(a.block)
            didx = health.indices_of(devices) if health is not None else []
            if faults is not None and faults.crashes(task.name, interval_index):
                raise RuntimeError(
                    f"injected transient trial crash for {task.name}"
                )
            if abort.is_set() or (didx and health.any_lost(didx)):
                # abort-and-requeue: the fleet changed under this interval —
                # don't start work the replan will move anyway
                raise PreemptedError(
                    f"task {task.name} preempted before launch "
                    f"(block [{a.block.offset}:{a.block.end}])"
                )
            task.select_strategy(a.apportionment)
            if on_task_start is not None:
                on_task_start(task.name)
            tech = task.selected_strategy.executor
            n = batches[task.name]
            logger.info(
                "interval: launching %s on block [%d:%d] for %d batches",
                task.name, a.block.offset, a.block.end, n,
            )
            if _stall_then_check(task.name):
                return  # watchdog abandoned this attempt during the stall
            _set_poison(task.name, task)
            t_run = timeit.default_timer()
            tech.execute(task, devices, tid, override_batch_count=n,
                         **_execute_kwargs(tech, n, window_cap))
            dt_run = timeit.default_timer() - t_run
            if _abandoned(task.name):
                logger.warning(
                    "task %s finished after watchdog abandonment; "
                    "discarding the attempt", task.name,
                )
                return
            if didx and health.any_lost(didx):
                # chips died under the run: the device state is gone, the
                # work is discarded — the last checkpoint is ground truth
                raise PreemptedError(
                    f"task {task.name} lost devices mid-run "
                    f"(block [{a.block.offset}:{a.block.end}])"
                )
            task.reconfigure(n)  # data-cursor advance (``executor.py:84``)
            if didx:
                health.note_step(didx, dt_run / max(n, 1))
            if on_task_done is not None:
                on_task_done(task.name, n)
        except BaseException as e:  # surface after the barrier
            _record_error(task.name, e)
            if isinstance(e, PreemptedError):
                logger.warning("%s", e)
            else:
                logger.exception("task %s failed during interval", task.name)
        finally:
            events[task.name].set()

    def group_launcher(members: List, tids: List[int]):
        """One shared launcher for a co-schedule group.

        Two-phase interleave: (1) round-robin the members' dispatch
        generators, advancing each one window per visit — a member whose
        batch staging isn't ready yields "waiting" and the launcher moves to
        the next member, which is exactly how a stage-bound job's host
        phases get filled by a compute-bound neighbor's device windows; (2)
        once every member has enqueued all its device work ("drain"), resume
        each past drain to run its blocking finalization (loss readback,
        checkpoint). Completion events fire only at GROUP end: a dependent
        of any member must wait for the whole group, since the members
        share the block until the last one drains.

        Each member's dispatch ORDER (and therefore its loss/checkpoint
        trajectory) is identical to a solo run — only the wall-clock packing
        between members changes. Per-member realized feedback comes from
        attributing the group's wall time by profiled work share; a member
        whose technique lacks generator support runs sequentially on this
        same thread after the interleaved members (correct, unoverlapped).
        """
        sched_point("engine.group_launcher")
        names = {t.name for t in members}
        active: List[Dict] = []
        t_group0 = timeit.default_timer()
        try:
            for t in members:
                for dep in plan.dependencies.get(t.name, ()):
                    if dep in running and dep not in names:
                        events[dep].wait()
            for t, tid in zip(members, tids):
                try:
                    a = plan.assignments[t.name]
                    devices = topology.block_devices(a.block)
                    didx = (
                        health.indices_of(devices) if health is not None else []
                    )
                    if faults is not None and faults.crashes(
                        t.name, interval_index
                    ):
                        raise RuntimeError(
                            f"injected transient trial crash for {t.name}"
                        )
                    if abort.is_set() or (didx and health.any_lost(didx)):
                        raise PreemptedError(
                            f"task {t.name} preempted before launch "
                            f"(block [{a.block.offset}:{a.block.end}])"
                        )
                    t.select_strategy(a.apportionment)
                    if on_task_start is not None:
                        on_task_start(t.name)
                    if _stall_then_check(t.name):
                        return  # whole group abandoned during the stall
                    _set_poison(t.name, t)
                    tech = t.selected_strategy.executor
                    n = batches[t.name]
                    pbt = max(
                        getattr(t.selected_strategy, "per_batch_time", 0.0),
                        1e-9,
                    )
                    can_interleave = getattr(
                        tech, "supports_coschedule", False
                    ) and hasattr(tech, "interval_dispatches")
                    logger.info(
                        "interval: co-launching %s on block [%d:%d] for %d "
                        "batches (%s)", t.name, a.block.offset, a.block.end,
                        n, "interleaved" if can_interleave else "sequential",
                    )
                    gen = (
                        tech.interval_dispatches(
                            t, devices, tid, override_batch_count=n,
                            shared=True, **_execute_kwargs(tech, n, window_cap)
                        )
                        if can_interleave
                        else None
                    )
                    active.append({
                        "task": t, "tech": tech, "gen": gen, "tid": tid,
                        "n": n, "pbt": pbt, "didx": didx, "devices": devices,
                        "block": a.block, "per_batch": None,
                        "interleaved": can_interleave,
                    })
                except BaseException as e:
                    _record_error(t.name, e)
                    if isinstance(e, PreemptedError):
                        logger.warning("%s", e)
                    else:
                        logger.exception(
                            "task %s failed during interval", t.name
                        )

            # Phase 1: interleave dispatches across the generator members.
            pending = [m for m in active if m["gen"] is not None]
            drained: List[Dict] = []
            while pending:
                progressed = False
                for m in list(pending):
                    try:
                        tag, _ = next(m["gen"])
                    except StopIteration:
                        pending.remove(m)
                        m["gen"] = None
                        continue
                    except BaseException as e:
                        _record_error(m["task"].name, e)
                        logger.exception(
                            "task %s failed during interval", m["task"].name
                        )
                        pending.remove(m)
                        m["gen"] = None
                        continue
                    if tag == "dispatched":
                        progressed = True
                    elif tag == "drain":
                        pending.remove(m)
                        drained.append(m)
                        progressed = True
                    # "waiting": fall through to the next member — the poll
                    # retries on this member's next visit
                if not progressed and pending:
                    # every member is staging: nothing to dispatch — give the
                    # staging threads the core instead of spinning
                    _time.sleep(0.001)

            # Phase 2: blocking finalizations (loss readback, checkpoint),
            # only after ALL members' device work is enqueued.
            for m in drained:
                if _abandoned(m["task"].name):
                    continue
                try:
                    for _ in m["gen"]:
                        pass
                except BaseException as e:
                    _record_error(m["task"].name, e)
                    logger.exception(
                        "task %s failed during interval", m["task"].name
                    )
                finally:
                    m["gen"] = None

            # Sequential fallback for members without generator support.
            for m in active:
                if m["interleaved"] or m["task"].name in errors:
                    continue
                try:
                    t_solo = timeit.default_timer()
                    m["tech"].execute(
                        m["task"], m["devices"], m["tid"],
                        override_batch_count=m["n"],
                        **_execute_kwargs(m["tech"], m["n"], window_cap),
                    )
                    m["per_batch"] = (
                        timeit.default_timer() - t_solo
                    ) / max(m["n"], 1)
                except BaseException as e:
                    _record_error(m["task"].name, e)
                    logger.exception(
                        "task %s failed during interval", m["task"].name
                    )

            # Attribute the group's wall clock to the interleaved members by
            # profiled work share: member i's attributed per-batch time is
            # wall * (n_i * pbt_i / sum_j n_j * pbt_j) / n_i — the realized
            # feedback the solver's next re-solve consumes. (Sequential
            # fallback members measured their own wall time above.)
            dt_group = timeit.default_timer() - t_group0
            ok = [m for m in drained if m["task"].name not in errors]
            denom = sum(m["n"] * m["pbt"] for m in ok)
            for m in ok:
                share = (
                    m["n"] * m["pbt"] / denom if denom > 0 else 1.0 / len(ok)
                )
                m["per_batch"] = dt_group * share / max(m["n"], 1)
                note = getattr(m["task"], "note_realized_per_batch", None)
                if note is not None:
                    note(m["per_batch"])

            # Per-member post-run bookkeeping, mirroring the solo launcher.
            for m in active:
                name = m["task"].name
                if name in errors or m["per_batch"] is None:
                    continue
                try:
                    if m["didx"] and health.any_lost(m["didx"]):
                        raise PreemptedError(
                            f"task {name} lost devices mid-run (block "
                            f"[{m['block'].offset}:{m['block'].end}])"
                        )
                    m["task"].reconfigure(m["n"])
                    if m["didx"]:
                        health.note_step(m["didx"], m["per_batch"])
                    if on_task_done is not None:
                        on_task_done(name, m["n"])
                except BaseException as e:
                    _record_error(name, e)
                    if isinstance(e, PreemptedError):
                        logger.warning("%s", e)
                    else:
                        logger.exception(
                            "task %s failed during interval", name
                        )
        except BaseException as e:
            for t in members:
                # keep_first: a member that already recorded its own failure
                # above keeps it; the group-level error only fills the gaps.
                _record_error(t.name, e, keep_first=True)
            logger.exception(
                "co-schedule group %s failed", sorted(names)
            )
        finally:
            for m in active:
                if m["gen"] is not None:
                    try:
                        m["gen"].close()
                    except BaseException:
                        logger.exception(
                            "closing dispatch generator for %s failed",
                            m["task"].name,
                        )
            for t in members:
                events[t.name].set()

    def fused_launcher(members: List, tids: List[int]):
        """One launcher for a fusion group: N members, ONE stacked program.

        Unlike the co-schedule launcher — which interleaves N independent
        programs on a shared block — the whole group here is a single
        compiled step (``parallel/fused.run_fused_interval``): params and
        optimizer state stacked along a leading ``model`` axis, every member
        advancing one batch per lockstep step. Per-member outcomes come back
        in the interval report:

        - healthy members commit like the solo launcher (cursor advance,
          realized fused-lockstep feedback EWMA'd into
          ``Strategy.fused_per_batch_time``, ``on_task_done``); a member
          whose forecast budget exceeded the lockstep count gets the
          shortfall rolled back (:func:`rollback_forecast`) so the next
          re-solve prices the truth;
        - a sentinel-faulted member surfaces exactly like a solo numeric
          fault (state discarded, error recorded, guardian owns recovery);
        - a DETACHED member (mid-interval unfuse) resumes SOLO on the same
          block for its remaining budget within this interval — the stack
          already checkpointed its state at the detach boundary, so the solo
          program restores bit-identically and no step is lost or repeated.
        """
        sched_point("engine.fused_launcher")
        names = {t.name for t in members}
        from saturn_tpu.parallel import fused as _fused

        try:
            for t in members:
                for dep in plan.dependencies.get(t.name, ()):
                    if dep in running and dep not in names:
                        events[dep].wait()
            a = plan.assignments[members[0].name]
            devices = topology.block_devices(a.block)
            didx = health.indices_of(devices) if health is not None else []
            for t in members:
                if faults is not None and faults.crashes(
                    t.name, interval_index
                ):
                    raise RuntimeError(
                        f"injected transient trial crash for {t.name}"
                    )
            if abort.is_set() or (didx and health.any_lost(didx)):
                raise PreemptedError(
                    f"fused group {sorted(names)} preempted before launch "
                    f"(block [{a.block.offset}:{a.block.end}])"
                )
            for t in members:
                t.select_strategy(a.apportionment)
                if on_task_start is not None:
                    on_task_start(t.name)
                _set_poison(t.name, t)
            if _stall_then_check(members[0].name):
                return  # whole group abandoned during the stall
            counts = [batches[t.name] for t in members]
            logger.info(
                "interval: fused-launching %s on block [%d:%d] "
                "(lockstep %d batches x %d members)",
                sorted(names), a.block.offset, a.block.end,
                min(counts), len(members),
            )
            report = _fused.run_fused_interval(
                members, devices, tids[0], batch_counts=counts,
            )
            if any(_abandoned(t.name) for t in members):
                logger.warning(
                    "fused group %s finished after watchdog abandonment; "
                    "discarding the attempt", sorted(names),
                )
                return
            if didx and health.any_lost(didx):
                raise PreemptedError(
                    f"fused group {sorted(names)} lost devices mid-run "
                    f"(block [{a.block.offset}:{a.block.end}])"
                )
            detached = {t.name: s for t, s in report.detached}
            if didx and report.per_step_s > 0:
                health.note_step(didx, report.per_step_s)
            for t in members:
                name = t.name
                mr = report.members.get(name)
                if mr is None:
                    continue
                try:
                    if mr.fault is not None:
                        raise mr.fault
                    budget = batches[name]
                    steps = mr.steps
                    if name in detached:
                        remaining = max(0, budget - steps)
                        if remaining > 0:
                            tech = t.selected_strategy.executor
                            logger.info(
                                "interval: resuming unfused %s solo for %d "
                                "remaining batches", name, remaining,
                            )
                            tech.execute(
                                t, devices, tids[0],
                                override_batch_count=remaining,
                                **_execute_kwargs(tech, remaining,
                                                  window_cap),
                            )
                            # the solo restore reset the cursor to the
                            # detach point; advance only the solo portion
                            t.reconfigure(remaining)
                        else:
                            t.reconfigure(steps)
                        done = budget
                    else:
                        t.reconfigure(steps)
                        if budget > steps:
                            # lockstep ran to the SHORTEST member's budget;
                            # give this member's shortfall back
                            rollback_forecast(t, budget - steps)
                        done = steps
                    strat = t.selected_strategy
                    if report.per_step_s > 0:
                        old = strat.fused_per_batch_time
                        strat.fused_per_batch_time = (
                            report.per_step_s if old is None
                            else 0.7 * report.per_step_s + 0.3 * old
                        )
                    if on_task_done is not None:
                        on_task_done(name, done)
                except BaseException as e:
                    _record_error(name, e)
                    if isinstance(e, PreemptedError):
                        logger.warning("%s", e)
                    else:
                        logger.exception(
                            "task %s failed during interval", name
                        )
        except BaseException as e:
            for t in members:
                # keep_first: a member that already recorded its own failure
                # above keeps it; the group-level error only fills the gaps.
                _record_error(t.name, e, keep_first=True)
            if isinstance(e, PreemptedError):
                logger.warning("%s", e)
            else:
                logger.exception("fused group %s failed", sorted(names))
        finally:
            for t in members:
                events[t.name].set()

    fused_groups = _fused_groups(run_tasks, plan)
    fused_names = {t.name for g in fused_groups for t in g}
    co_groups = _coschedule_groups(
        [t for t in run_tasks if t.name not in fused_names], plan
    )
    grouped = {t.name for g in co_groups for t in g} | fused_names
    tid_of = {t.name: i for i, t in enumerate(run_tasks)}

    def _expected_s(t) -> float:
        """Profiled window work for one task this interval (seconds)."""
        a = plan.assignments.get(t.name)
        strat = t.strategies.get(a.apportionment) if a is not None else None
        pbt = max(float(getattr(strat, "per_batch_time", 0.0) or 0.0), 0.0)
        return batches.get(t.name, 0) * pbt

    # Entered around the threads' start and join below; made here so that
    # every launcher thread can be handed it as its spans' parent.
    interval_span = metrics.span("interval", planned_s=interval,
                                 n_tasks=len(run_tasks))

    def under_interval(fn, *args):
        with metrics.under(interval_span):
            fn(*args)

    # (thread, member task names, watchdog deadline in seconds). A group
    # thread's deadline covers the SUM of its members' profiled work — the
    # members run interleaved on this one thread.
    watch: List[Tuple[threading.Thread, List[str], Optional[float]]] = []
    use_watchdog = guardian is not None and guardian.watchdog_enabled
    for i, t in enumerate(run_tasks):
        if t.name in grouped:
            continue
        th = threading.Thread(
            target=under_interval, args=(launcher, t, i), daemon=True,
            name=f"launch-{t.name}",
        )
        dl = guardian.window_deadline_s(_expected_s(t)) if use_watchdog else None
        watch.append((th, [t.name], dl))
    for g in co_groups:
        th = threading.Thread(
            target=under_interval,
            args=(group_launcher, g, [tid_of[t.name] for t in g]),
            daemon=True,
            name="colaunch-" + "+".join(t.name for t in g),
        )
        dl = (
            guardian.window_deadline_s(sum(_expected_s(t) for t in g))
            if use_watchdog else None
        )
        watch.append((th, [t.name for t in g], dl))
    for g in fused_groups:
        th = threading.Thread(
            target=under_interval,
            args=(fused_launcher, g, [tid_of[t.name] for t in g]),
            daemon=True,
            name="fuselaunch-" + "+".join(t.name for t in g),
        )
        # Deadline covers the members' summed profiled solo work — a loose
        # upper bound on the lockstep stack (the whole point of fusing is
        # beating it), so the watchdog only fires on a genuine wedge.
        dl = (
            guardian.window_deadline_s(sum(_expected_s(t) for t in g))
            if use_watchdog else None
        )
        watch.append((th, [t.name for t in g], dl))

    # The ``interval`` event is this span: same fields, plus its start. The
    # launcher threads are its children (``metrics.under``).
    with interval_span:
        t0 = timeit.default_timer()
        for th, _, _ in watch:
            th.start()
        if use_watchdog:
            _join_with_watchdog(watch, t0, hung, hung_lock, errors, events)
        else:
            for th, _, _ in watch:
                th.join()
        for tm in timers:
            tm.cancel()
        elapsed = timeit.default_timer() - t0
        interval_span.set(
            elapsed_s=elapsed,
            failed=sorted(
                n for n, e in errors.items()
                if not isinstance(e, PreemptedError)
            ),
            preempted=sorted(
                n for n, e in errors.items() if isinstance(e, PreemptedError)
            ),
        )
    # Interval boundary: drain the buffered metrics writer — emission is off
    # the step critical path, but an interval's telemetry must land before
    # the next interval starts (live tail_events followers, crash windows).
    metrics.flush()
    if failure_policy == "raise":
        real = {
            n: e for n, e in errors.items() if not isinstance(e, PreemptedError)
        }
        if guardian is not None:
            # Health faults belong to the guardian's recovery policy
            # (rollback + backoff), not the crash-the-batch path.
            real = {n: e for n, e in real.items() if not guardian.owns(e)}
        if real:
            name, err = next(iter(real.items()))
            raise RuntimeError(
                f"interval execution failed for task {name}"
            ) from err
    # estimate-error feedback (``executor.py:126-129``)
    if elapsed > interval:
        logger.info("interval overran: %.1fs vs planned %.1fs", elapsed, interval)
    else:
        logger.info("interval finished early: %.1fs of %.1fs", elapsed, interval)
    return errors


def _execute_multihost(
    run_tasks, batches, interval, plan, topology, failure_policy,
) -> Dict[str, BaseException]:
    """Multi-process interval: SEQUENTIAL, deterministic program order.

    Multi-controller JAX requires every pair of processes to enqueue their
    shared programs in the same order — the single-host thread gang cannot
    guarantee that, so cross-host intervals serialize tasks by planned
    (start, name). Each process executes only tasks whose block touches its
    local devices (a program over purely-remote devices has no local
    computation) but advances EVERY task's bookkeeping, keeping per-rank
    task state identical. Ordering edges are satisfied by construction: an
    overlap dependency always has an earlier planned start.
    """
    import jax

    # Co-schedule groups are ignored here on purpose: cross-host intervals
    # already serialize every task for deterministic program order, and
    # sequential execution of a group is trajectory-identical (just
    # unoverlapped). The single window-cap read per interval still applies.
    window_cap = _window_cap()
    my_proc = jax.process_index()
    ordered = sorted(
        run_tasks, key=lambda t: (plan.assignments[t.name].start, t.name)
    )
    with metrics.span("interval", planned_s=interval,
                      n_tasks=len(run_tasks)) as sp:
        errors = _multihost_interval(
            sp, ordered, batches, plan, topology, window_cap, my_proc,
        )
    metrics.flush()
    return errors


def _multihost_interval(sp, ordered, batches, plan, topology, window_cap,
                        my_proc) -> Dict[str, BaseException]:
    """The body of :func:`_execute_multihost`'s ``interval`` span."""
    from saturn_tpu.core import distributed

    errors: Dict[str, BaseException] = {}
    t0 = timeit.default_timer()
    for tid, task in enumerate(ordered):
        a = plan.assignments[task.name]
        task.select_strategy(a.apportionment)
        devices = topology.block_devices(a.block)
        local = any(
            getattr(d, "process_index", 0) == my_proc for d in devices
        )
        try:
            if local:
                n = batches[task.name]
                logger.info(
                    "interval[mh]: %s on block [%d:%d] for %d batches",
                    task.name, a.block.offset, a.block.end, n,
                )
                tech = task.selected_strategy.executor
                tech.execute(
                    task, devices, tid, override_batch_count=n,
                    **_execute_kwargs(tech, n, window_cap)
                )
            task.reconfigure(batches[task.name])
        except BaseException as e:
            # Fail FAST, before any barrier or further collective: healthy
            # ranks may be ahead in cross-process programs, and this rank
            # parking at a barrier while they wait in a collective is a
            # mutual hang. Raising here exits the process; the jax
            # coordination service then aborts the rest of the cluster
            # (multi-host supports failure_policy='raise' only).
            logger.exception("task %s failed during interval", task.name)
            sp.set(elapsed_s=timeit.default_timer() - t0, failed=[task.name])
            raise RuntimeError(
                f"interval execution failed for task {task.name}"
            ) from e
    # Interval-end durability point: join this rank's async checkpoint
    # writes, then barrier. Forfeits the single-host write/compute overlap,
    # but guarantees every rank sees identical shared-FS state before the
    # next interval's exists()/restore() decisions — the alternative
    # (collectives inside checkpoint reads) deadlocks for host-local tasks.
    from saturn_tpu.utils import checkpoint as _ckpt

    _ckpt.flush()
    distributed.sync("interval-end")
    sp.set(elapsed_s=timeit.default_timer() - t0, failed=[])
    return errors
