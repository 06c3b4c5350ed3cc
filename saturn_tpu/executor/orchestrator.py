"""Orchestrator: the interval loop with overlapped re-solving ("introspection").

Reference: ``saturn/orchestrator.py:21-75``. Structure preserved exactly:
initial blocking solve (``:55-56``), then per interval — forecast, drop
finished tasks, kick off an **async re-solve for the next interval that
overlaps the current interval's execution** (``:69-71``), execute, join the
solve, decode. The async solver runs in a worker thread instead of a Ray
remote reserving ¼ of the node's CPUs (``:21-23``).

The reference's first solve call had a positional-arg bug (gurobi=1000,
interval=500 — ``orchestrator.py:55`` vs ``:22``; SURVEY.md §3.2 says to
replicate the intent, not the bug): here both solves use the same, correct
arguments — solver time limit = interval/2 (``:55``).
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

from saturn_tpu import analysis
from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.executor import engine
from saturn_tpu.solver import anytime, milp
from saturn_tpu.utils import metrics, trace

logger = logging.getLogger("saturn_tpu")


def _gate_resolved_plan(candidate, previous, topo, tasks, interval,
                        journal, interval_index):
    """Static-verification gate on a re-solved plan (compare-and-swap side).

    A candidate that fails :func:`saturn_tpu.analysis.verify_or_raise` is
    QUARANTINED — never adopted — and the orchestrator falls back to the
    previous interval's plan slid down by ``interval`` (exactly the keep
    path of ``milp.resolve``), which passed the same gate last interval.
    Only when no covering fallback exists (first plan, or new tasks the old
    plan can't place) does the failure propagate.

    Deterministic: multihost ranks gate the identical broadcast payload and
    reach the identical adopt/quarantine decision.
    """
    try:
        analysis.verify_or_raise(candidate, topology=topo, tasks=tasks,
                                 source="re-solve")
        return candidate
    except analysis.PlanVerificationError as e:
        codes = sorted({d.code for d in e.report.errors})
        logger.error("re-solve plan quarantined (%s): %s", codes, e)
        metrics.event("plan_quarantine", source="re-solve", codes=codes)
        if journal is not None:
            journal.append("plan_quarantine", interval=interval_index + 1,
                           source="re-solve", codes=codes)
        cur = {t.name for t in tasks}
        if previous is None or (cur - set(previous.assignments)):
            raise  # no covering fallback — refuse loudly, don't launch it
        slid = milp.Plan(
            assignments={
                n: milp.Assignment(a.apportionment, a.block,
                                   max(0.0, a.start - interval), a.runtime)
                for n, a in previous.assignments.items() if n in cur
            },
            makespan=max(0.0, previous.makespan - interval),
            coschedule=[
                kept for grp in previous.coschedule
                if len(kept := [n for n in grp if n in cur]) >= 2
            ],
        )
        slid.compute_dependencies()
        return slid


def orchestrate(
    task_list: List,
    log: bool = False,
    interval: float = 1000.0,
    topology: Optional[SliceTopology] = None,
    threshold: float = 0.0,
    solver_time_limit: Optional[float] = None,
    failure_policy: str = "raise",
    max_task_retries: int = 1,
    metrics_path: Optional[str] = None,
    trace_dir: Optional[str] = None,
    fault_injector=None,
    health_monitor=None,
    recovery_policy: str = "pause-resolve-resume",
    replan_degrade_factor: float = 2.0,
    resume_dir: Optional[str] = None,
    health_guardian=None,
    crash_barrier=None,
) -> dict:
    """Run every task to completion, minimizing batch makespan.

    ``interval``: seconds of execution per scheduling round (reference default
    1000, ``orchestrator.py:32``). ``threshold``: makespan improvement needed
    to adopt a re-solved plan (``milp.py:376-379``). ``failure_policy``:
    ``"raise"`` (reference crash-the-batch semantics), ``"drop"`` (evict the
    failed task, keep the rest running), or ``"retry"`` (keep the failed task
    in the batch for up to ``max_task_retries`` more attempts — it resumes
    from its last checkpoint at the next interval — then evict like
    ``"drop"``). ``metrics_path`` appends JSONL events (``utils/metrics.py``),
    among them one ``metrics.span`` event per phase: ``orchestrate`` >
    ``solver.resolve`` / ``forecast`` / ``interval`` > ``task_interval`` >
    ``launch.*`` / ``readback`` / ``step_flops`` / ``ckpt.*``, and one
    ``compile`` event per XLA compile with the span it fell into
    (``docs/architecture.md``, "Metrics stream & spans"). ``trace_dir`` wraps the run — the
    final checkpoint flush included — in a jax.profiler trace that holds the
    device's ops under the same spans (``saturn.<name>`` on the host plane),
    with the Python tracer off.

    Elasticity (``saturn_tpu.resilience``): passing ``health_monitor`` (a
    ``FleetHealthMonitor``) — or a ``fault_injector`` / setting
    ``SATURN_TPU_FAULTS`` — turns the fixed-topology loop elastic. Each
    interval starts with a health poll; on a shrink/grow
    ``TopologyChange`` the ``ElasticReplanner`` rebuilds topology + plan
    over the surviving mesh under ``recovery_policy``
    (``resilience.RECOVERY_POLICIES``). Mid-interval device loss
    aborts-and-requeues the affected tasks (``PreemptedError`` — requeued
    WITHOUT counting against ``max_task_retries``); migrated tasks resume
    from their checkpoints on the new mesh. Single-host only.

    Durability (``saturn_tpu.durability``): ``resume_dir`` points the run at
    a write-ahead journal directory. Every interval's realized iterations,
    plan commits, completions/failures and checkpoint publications are
    group-committed there; re-running ``orchestrate(resume_dir=...)`` after
    a crash replays the journal (torn trailing records are quarantined and
    rolled back to the last durable cut), drops journaled-completed tasks,
    subtracts durably realized batches from each survivor's budget, and
    resumes — no durably completed iteration re-runs. Single-host only.

    Training health (``saturn_tpu.health``): a ``TrainingGuardian`` is
    active by default on single-host runs — the sentinel screens every
    interval's losses on-device, the engine watchdog deadlines every
    launcher, and a health fault rolls the task back to its last published
    checkpoint and retries under exponential backoff (quarantining repeat
    bad batches, detaching repeat offenders from co-schedule groups,
    evicting past the per-cause budget). Pass ``health_guardian=False`` to
    disable, or your own ``TrainingGuardian`` to customize policy. Health
    transitions are journaled when ``resume_dir`` is set, so kill-replay
    restores quarantine state. ``crash_barrier`` (a
    ``resilience.CrashInjector``) is test-only: it threads kill points into
    the journal and the health recovery path.

    Returns ``{"completed": [names], "failed": {name: error string}}``.
    """
    if log:
        logging.basicConfig(level=logging.INFO)
    from saturn_tpu.core import distributed

    if distributed.is_multihost() and not distributed.is_coordinator():
        # One writer per metrics file: every rank appending the same JSONL
        # on shared storage would duplicate each event N-fold (and NFS
        # O_APPEND interleaving is not line-atomic).
        metrics_path = None
    # The sink, the profiler region and the root span enclose the whole call:
    # the journal's replay before the loop, and after it the ``finally`` that
    # joins the checkpoint writer threads (whose ``ckpt.write`` spans need a
    # sink to land in) and closes the journal.
    with metrics.scoped(metrics_path), trace.profile_trace(trace_dir), \
            metrics.span("orchestrate", n_tasks=len(task_list)):
        return _orchestrate(
            task_list, interval, topology, threshold, solver_time_limit,
            failure_policy, max_task_retries, fault_injector, health_monitor,
            recovery_policy, replan_degrade_factor, resume_dir,
            health_guardian, crash_barrier,
        )


def _orchestrate(
    task_list, interval, topology, threshold, solver_time_limit,
    failure_policy, max_task_retries, fault_injector, health_monitor,
    recovery_policy, replan_degrade_factor, resume_dir, health_guardian,
    crash_barrier,
) -> dict:
    """:func:`orchestrate` inside its sink, trace and root span."""
    if failure_policy not in ("raise", "drop", "retry"):
        raise ValueError(
            f"failure_policy must be 'raise', 'drop' or 'retry', got {failure_policy!r}"
        )
    from saturn_tpu.core import distributed

    if distributed.is_multihost() and failure_policy != "raise":
        # drop/retry mutate the task set from a per-rank error view; until
        # errors are all-gathered, divergent task lists would interleave
        # collective programs differently per process (multi-controller
        # deadlock). A failed rank aborts the cluster through the jax
        # coordination service instead.
        raise ValueError(
            "multi-host orchestration supports failure_policy='raise' only"
        )
    topo = topology if topology is not None else SliceTopology()

    if fault_injector is None:
        from saturn_tpu.resilience.faults import FaultInjector

        fault_injector = FaultInjector.from_env()
    if fault_injector is not None and health_monitor is None:
        from saturn_tpu.resilience.health import FleetHealthMonitor

        health_monitor = FleetHealthMonitor.for_topology(topo)
    replanner = None
    if health_monitor is not None:
        if distributed.is_multihost():
            # Elastic recovery mutates topology/plan from one process's
            # health view; until changes are broadcast like plans are, a
            # divergent topology means divergent collective programs.
            raise ValueError(
                "elastic resilience (health_monitor/fault_injector) is "
                "single-host only"
            )
        from saturn_tpu.resilience.replan import ElasticReplanner

        replanner = ElasticReplanner(
            policy=recovery_policy, degrade_factor=replan_degrade_factor
        )
    names = [t.name for t in task_list]
    if len(set(names)) != len(names):
        dupes = sorted({n for n in names if names.count(n) > 1})
        raise ValueError(
            f"duplicate task names {dupes}: every subsystem (plan, engine, "
            "checkpoints) keys on task.name — give tasks unique names"
        )
    for t in task_list:
        if not t.feasible_strategies():
            raise ValueError(
                f"task {t.name} has no profiled strategies — run saturn_tpu.search first"
            )
    tlimit = solver_time_limit if solver_time_limit is not None else interval / 2

    task_list = list(task_list)
    all_completed: List[str] = []
    all_failed: dict = {}
    retries: dict = {}  # task name -> failed attempts so far

    journal = None
    ckpt_hook = None
    recovered_state = None
    if crash_barrier is not None and resume_dir is None:
        raise ValueError("crash_barrier requires resume_dir (it instruments "
                         "the durability journal)")
    if resume_dir is not None:
        if distributed.is_multihost():
            raise ValueError(
                "resume_dir (crash-safe durability) is single-host only — "
                "multi-controller journal consensus is future work"
            )
        from saturn_tpu.durability import journal as jmod
        from saturn_tpu.durability import recovery as rmod
        from saturn_tpu.utils import checkpoint as _ckpt

        journal = jmod.Journal(resume_dir, barrier=crash_barrier)  # recovers torn tails on open
        state = recovered_state = rmod.replay_batch_state(resume_dir)
        if state.plan:
            # Journal-replay audit: the orchestrator always re-solves on
            # resume, but a committed plan the static verifier rejects
            # means the pre-crash process launched (or was about to launch)
            # a corrupt schedule — quarantine it on the record so the
            # incident is durable and debuggable.
            try:
                replayed_report = analysis.verify_plan(
                    milp.Plan.from_json(state.plan), subject="journal-replay"
                )
            except Exception as e:
                replayed_report = None
                logger.warning("replayed plan_commit undecodable: %s", e)
            if replayed_report is not None and not replayed_report.ok:
                codes = sorted({d.code for d in replayed_report.errors})
                logger.warning(
                    "journal's committed plan fails static verification "
                    "(%s) — quarantined; resuming from a fresh solve", codes,
                )
                journal.log("plan_quarantine", source="journal-replay",
                            codes=codes)
        if state.checkpoints:
            rmod.reconcile_checkpoints(state.checkpoints)
        task_list = _fold_batch_recovery(
            task_list, state, all_completed, all_failed
        )
        journal.log(
            "recovery", replayed_seq=state.last_seq,
            replayed_records=state.n_records,
            completed=len(all_completed), remaining=len(task_list),
        )

        def ckpt_hook(task_name, path):
            journal.append("ckpt_published", task=task_name, path=path)

        _ckpt.add_publish_hook(ckpt_hook)

    # Training-health guardian: on by default single-host. ``False``
    # disables; a caller-supplied guardian is adopted as-is (its journal is
    # wired up if it has none and this run is durable).
    guardian = None
    if health_guardian is not False and not distributed.is_multihost():
        from saturn_tpu.health import TrainingGuardian

        guardian = (
            health_guardian if health_guardian is not None
            else TrainingGuardian(journal=journal)
        )
        if guardian.journal is None and journal is not None:
            guardian.journal = journal
        if recovered_state is not None:
            # Kill-replay: re-apply journaled quarantine skip-lists and
            # co-schedule detachments to the rebuilt tasks.
            guardian.restore(
                getattr(recovered_state, "quarantined", {}) or {},
                getattr(recovered_state, "detached", ()) or (),
                task_list,
            )

    try:
        return _orchestrate_loop(
            task_list, topo, interval, threshold, tlimit, failure_policy,
            max_task_retries,
            all_completed, all_failed, retries,
            health_monitor, fault_injector, replanner, journal,
            guardian,
        )
    finally:
        import sys

        from saturn_tpu.resilience.crash import SimulatedKill
        from saturn_tpu.utils import checkpoint as ckpt

        if ckpt_hook is not None:
            ckpt.remove_publish_hook(ckpt_hook)
        # A simulated SIGKILL runs no handlers: no checkpoint flush, no
        # journal flush/close — buffered records die with the "process",
        # exactly like the service loop's kill path. Recovery is the next
        # incarnation's problem (that is the point).
        if not isinstance(sys.exc_info()[1], SimulatedKill):
            try:
                # join outstanding async checkpoint writes on EVERY other
                # exit path — a caller catching a failure must still see
                # landed checkpoints
                ckpt.flush()
            except Exception:
                if sys.exc_info()[1] is None:
                    raise  # clean exit: surface the write failure
                logger.exception(
                    "async checkpoint flush failed during error unwind"
                )
            if journal is not None:
                # Buffered records describe work that really happened
                # (task_progress only fires post-success), so committing
                # them on an error unwind is correct; a hard crash skips
                # this and loses only re-runnable work.
                try:
                    journal.close()
                except Exception:
                    logger.exception("journal close failed during unwind")


def _fold_batch_recovery(task_list, state, all_completed, all_failed) -> List:
    """Apply replayed journal state to a fresh task list: journaled-terminal
    tasks never re-run, and durably realized batches come off each
    survivor's budget (strategy runtimes re-derived from per-batch
    profiles). The journal is authoritative — it only records iterations
    that actually executed."""
    out = []
    for t in task_list:
        if t.name in state.completed:
            all_completed.append(t.name)
            logger.info("resume: %s already completed durably — skipping",
                        t.name)
            continue
        if t.name in state.failed:
            all_failed[t.name] = state.failed[t.name]
            logger.info("resume: %s failed durably — not retrying", t.name)
            continue
        realized = state.progress.get(t.name, 0)
        if realized > 0:
            t.total_batches = max(0, t.total_batches - realized)
            for s in t.strategies.values():
                if s.feasible:
                    s.runtime = s.per_batch_time * t.total_batches
            logger.info(
                "resume: %s has %d durably realized batch(es) — %d remain",
                t.name, realized, t.total_batches,
            )
            if t.total_batches <= 0:
                all_completed.append(t.name)
                continue
        out.append(t)
    return out


def _persist_realized(task) -> None:
    """Write the task's freshly measured per-batch time back to the
    persistent profile cache (``utils/profile_cache.py``).

    This is what upgrades interpolated trial-sweep entries to measured ones
    *across processes*: the in-process upgrade happens in
    ``Task.apply_realized_feedback`` (flag cleared, EWMA folded in), and this
    write makes the next driver's ``search()`` start from realized numbers
    instead of solo-trial or cost-model estimates. Only the self-measured
    strategy is persisted — sibling ratio corrections are derived, not
    evidence."""
    strat = getattr(task, "last_feedback_strategy", None)
    key = getattr(strat, "cache_key", None) if strat is not None else None
    if not key or not strat.feasible:
        return
    from saturn_tpu.utils import profile_cache as pcache

    cache = pcache.default_cache()
    if cache is None:
        return
    try:
        wrote = cache.note_realized(
            key, strat.per_batch_time, strat.params,
            technique=getattr(strat.executor, "name", "unknown"),
            size=strat.apportionment,
        )
        if wrote:
            metrics.event(
                "profile_cache", op="realized_writeback", task=task.name,
                size=strat.apportionment, per_batch_s=strat.per_batch_time,
            )
    except Exception:
        logger.debug("profile cache write-back failed for %s", task.name,
                     exc_info=True)


def fold_realized_feedback(run_tasks) -> dict:
    """Fold each executed task's realized per-batch time into its strategy
    (EWMA via ``Task.apply_realized_feedback``) and persist the measured
    number to the profile cache. Returns ``{name: (old, new)}`` for the tasks
    that produced an update. Call only while no solver thread is reading
    strategy state. Shared by the interval loop and the online job service."""
    updates = {}
    for t in run_tasks:
        apply_fb = getattr(t, "apply_realized_feedback", None)
        upd = apply_fb() if apply_fb is not None else None
        if upd is not None:
            updates[t.name] = upd
            _persist_realized(t)
    return updates


def _fusion_proposals(tasks) -> Optional[List[List[str]]]:
    """Candidate fusion groups for a solve call (same-fingerprint task
    names, ``parallel/fused.fusion_candidates``). Proposing is free: only
    groups whose members all carry a measured ``fused_per_batch_time`` can
    win the pricing (``milp.fusion_priced_groups`` refuses guesswork), so
    an unprofiled sweep degrades to exactly the pre-fusion plan. Fail open
    on any trouble — fusion is an optimization, never a launch blocker."""
    try:
        from saturn_tpu.parallel import fused as _fused

        return _fused.fusion_candidates(tasks) or None
    except Exception:
        logger.exception("fusion candidate proposal failed (fail-open)")
        return None


def _memlens_fusion_gate(topo):
    """Adapt memlens' stacked-residency pass to the solver's
    ``fusion_fits(member_tasks, size, n_members)`` contract: an explicit
    False (the ×N stacked params would blow past the OOM margin) vetoes
    that size before any compile; None (analyzer unavailable, capacity
    unknown, untraceable config) never prunes — the zero-compile
    feasibility-prior contract."""
    def fits(member_tasks, size, n_members):
        try:
            from saturn_tpu.analysis.memlens import passes as ml_passes

            rep = member_tasks[0]
            strat = rep.feasible_strategies().get(size)
            if strat is None or strat.executor is None:
                return None
            blocks = topo.blocks(size)
            if not blocks:
                return None
            return ml_passes.fused_stack_fits(
                strat.executor, rep, topo.block_devices(blocks[0]),
                n_members, config=strat.params or None,
            )
        except Exception:
            return None

    return fits


def _handle_topology_change(
    task_list, base_topo, health, replanner, change, plan, tlimit,
    all_failed,
):
    """Pre-interval elastic hook: rebuild topology + plan over the monitor's
    surviving device set, evict the unschedulable, release migrated tasks'
    live device state so their next interval restores from checkpoint on
    the new mesh (cross-mesh migration, ``utils/checkpoint.py``)."""
    import timeit as _timeit

    t_detect = _timeit.default_timer()
    metrics.event("topology_change", **change.to_fields())
    logger.warning(
        "topology change (%s): lost=%s gained=%s stragglers=%s — replanning",
        change.kind, change.lost, change.gained, change.stragglers,
    )
    result = replanner.replan(
        task_list, base_topo, health.alive_indices(), change,
        previous_plan=plan, time_limit=tlimit,
    )
    evicted = set(result.evicted)
    for name in sorted(evicted):
        all_failed[name] = f"evicted on topology change ({change.kind})"
        metrics.event("task_failed", task=name,
                      error=f"evicted on topology change ({change.kind})")
    by_name = {t.name: t for t in task_list}
    for name, d in sorted(result.migrations.items()):
        if not d["moved"] or name in evicted:
            continue
        t = by_name.get(name)
        if t is not None:
            release = getattr(t, "release_live_state", None)
            if release is not None:
                release()  # next interval restores from ckpt on the new mesh
        metrics.event("migration", task=name, moved_from=d["from"],
                      moved_to=d["to"])
    task_list = [t for t in task_list if t.name not in evicted]
    metrics.event(
        "recovery", policy=replanner.policy,
        replan_latency_s=_timeit.default_timer() - t_detect,
        capacity=result.topology.capacity, n_tasks=len(task_list),
    )
    # Mandatory adoption gate (migration path): the replanner's plan targets
    # a topology the running plan never saw — verify it against the NEW
    # slice before any task is migrated onto it. There is no covering
    # fallback plan on a changed topology, so a failure propagates.
    analysis.verify_or_raise(result.plan, topology=result.topology,
                             tasks=task_list, source="migration-replan")
    return task_list, result.topology, result.plan


def _resolve_under(above, *args, **kwargs):
    """``anytime_resolve`` on the solver pool's thread, its ``solver.resolve``
    span a child of the span that was open where it was submitted."""
    with metrics.under(above):
        return anytime.anytime_resolve(*args, **kwargs)


def _orchestrate_loop(
    task_list, topo, interval, threshold, tlimit, failure_policy,
    max_task_retries,
    all_completed, all_failed, retries,
    health=None, faults=None, replanner=None, journal=None,
    guardian=None,
) -> dict:
    from saturn_tpu.core import distributed
    from saturn_tpu.resilience.faults import PreemptedError

    multihost = distributed.is_multihost()
    if not task_list:
        # Nothing left to run — e.g. a resumed batch whose journal already
        # records every task terminal (restart after a crash-after-finish).
        logger.info("orchestration complete (%d completed, %d failed)",
                    len(all_completed), len(all_failed))
        return {"completed": all_completed, "failed": all_failed}
    if multihost:
        # Profile sync BEFORE the first forecast: per-process wall-clock
        # profiling yields slightly different per-batch times, and
        # forecast budgets derived from divergent numbers mean divergent
        # collective program counts (multi-controller deadlock). The
        # coordinator's trial numbers win here; per-interval syncs below
        # use each task's executing rank.
        distributed.sync_task_state(task_list)
    # Multi-host: ONLY the coordinator solves (a time-limited HiGHS run
    # is not deterministic across processes); every rank executes the
    # same broadcast plan. Single-host: unchanged.
    if not multihost or distributed.is_coordinator():
        # Initial blocking solve through the anytime tier ladder: a
        # small batch degenerates to the exact MILP (single-partition
        # tier 1); a big queue lands inside tlimit via the cheaper
        # tiers instead of blowing the first interval.
        plan = anytime.anytime_resolve(
            task_list, topo, None, interval, deadline=tlimit,
            source="orchestrator-initial",
            fusion=_fusion_proposals(task_list),
            fusion_fits=_memlens_fusion_gate(topo),
        )
    else:
        plan = None
    if multihost:
        plan = milp.Plan.from_json(
            distributed.broadcast_json(plan.to_json() if plan else None)
        )
    # Mandatory adoption gate (fresh-solve path): a malformed initial
    # plan fails HERE, with structured diagnostics, not at gang launch.
    analysis.verify_or_raise(plan, topology=topo, tasks=task_list,
                             source="fresh-solve")
    logger.info("initial plan: makespan %.1fs, %d tasks", plan.makespan, len(task_list))
    metrics.event("solve", makespan_s=plan.makespan,
                  n_tasks=len(task_list), plan=plan.to_json())
    if journal is not None:
        journal.append("plan_commit", interval=0,
                       makespan=plan.makespan, plan=plan.to_json())

    on_done = None
    if journal is not None:
        def on_done(name, n):  # buffered; durable at interval end
            if n > 0:
                journal.append("task_progress", task=name,
                               batches=int(n))

    base_topo = topo  # health-monitor indices refer to the pre-fault fleet
    interval_index = 0
    # Tasks parked by the guardian's exponential backoff: out of the
    # forecast/re-solve set entirely until their resume interval.
    parked: List = []
    with ThreadPoolExecutor(max_workers=1, thread_name_prefix="solver") as pool:
        while task_list or parked:
            if parked:
                back = [
                    t for t in parked
                    if guardian is None
                    or not guardian.benched(t.name, interval_index)
                ]
                if back:
                    names_back = {t.name for t in back}
                    parked = [
                        t for t in parked if t.name not in names_back
                    ]
                    task_list.extend(back)
                    logger.info(
                        "guardian: backoff expired for %s — re-admitted",
                        sorted(names_back),
                    )
            if not task_list:
                # Everyone is benched: burn an idle interval so the
                # backoff clock advances.
                interval_index += 1
                continue
            if health is not None:
                # Pre-interval health poll (elastic hook point): apply
                # scheduled interval-start faults, then consume at most
                # one aggregated TopologyChange into a replan.
                if faults is not None:
                    faults.apply_due(interval_index, health)
                change = health.poll()
                if change is not None and change.kind in ("shrink", "grow"):
                    if change.kind == "grow" and journal is not None:
                        journal.log(
                            "grow_event", interval=interval_index,
                            gained=list(change.gained),
                            cause=change.cause,
                            n_parked=len(parked),
                            capacity=base_topo.capacity,
                        )
                    if change.kind == "grow" and parked:
                        # Elastic scale-up: fresh capacity runs parked
                        # work NOW — short-circuit remaining backoff
                        # (streak ledgers untouched) and fold the parked
                        # tasks into the replan set so the grow re-solve
                        # covers live ∪ parked.
                        if guardian is not None:
                            guardian.unbench_all(cause="grow")
                        names_back = sorted(t.name for t in parked)
                        task_list.extend(parked)
                        parked = []
                        if journal is not None:
                            # log, not append: durable alongside the
                            # grow_event so a crash cannot drop the
                            # drain attribution record.
                            journal.log(
                                "backlog_drain",
                                interval=interval_index,
                                jobs=names_back, trigger="grow",
                            )
                        metrics.event(
                            "backlog_drain", interval=interval_index,
                            jobs=names_back, trigger="grow",
                        )
                        logger.info(
                            "grow: re-admitted parked %s ahead of "
                            "backoff", names_back,
                        )
                    task_list, topo, plan = _handle_topology_change(
                        task_list, base_topo, health, replanner, change,
                        plan, tlimit, all_failed,
                    )
                    if not task_list:
                        break
                elif change is not None:  # degrade: advisory, no replan
                    metrics.event("topology_change", **change.to_fields())
                    logger.warning(
                        "degraded fleet: stragglers %s (policy %s keeps "
                        "running)", change.stragglers, replanner.policy,
                    )
            with metrics.span("forecast", n_tasks=len(task_list)):
                run_tasks, batches, completed = engine.forecast(
                    task_list, interval, plan)
            remaining = [t for t in task_list if t not in completed]

            future = None
            if remaining and (not multihost or distributed.is_coordinator()):
                # overlap next-interval solve with this interval's execution
                # (``orchestrator.py:69-71``)
                future = pool.submit(
                    _resolve_under, metrics.current_span(),
                    remaining, topo, plan,
                    interval, threshold, deadline=tlimit,
                    coschedule_exclude=(
                        guardian.detached_names() if guardian is not None
                        else None
                    ),
                    source="orchestrator",
                    fusion=_fusion_proposals(remaining),
                    fusion_exclude=(
                        guardian.detached_names() if guardian is not None
                        else None
                    ),
                    fusion_fits=_memlens_fusion_gate(topo),
                )

            # Snapshot the EXECUTED plan's assignments before the
            # re-solve broadcast replaces `plan`: feedback source ranks
            # must name the rank that actually ran each task, not where
            # the next plan happens to move it.
            executed_assignments = {
                t.name: plan.assignments.get(t.name) for t in run_tasks
            }
            errors: dict = {}
            if run_tasks:
                errors = engine.execute(
                    run_tasks, batches, interval, plan, topo,
                    failure_policy="raise" if failure_policy == "raise" else "drop",
                    health=health, faults=faults,
                    interval_index=interval_index,
                    on_task_done=on_done,
                    guardian=guardian,
                )
                if guardian is not None:
                    # Consecutive-fault streaks reset on a clean interval
                    # (quarantine/detach state persists — corrections,
                    # not penalties).
                    for t in run_tasks:
                        if t.name not in errors:
                            guardian.note_success(t.name)
                if journal is not None:
                    journal.barrier("mid-interval",
                                    interval=interval_index)
            elif remaining:
                # nothing scheduled inside this interval (all starts beyond
                # it): the slide in resolve() brings work forward next round.
                logger.info("idle interval: no task starts within %.1fs", interval)

            if multihost and remaining:
                # Every rank must reach this broadcast; the coordinator
                # contributes its joined re-solve. A coordinator-side
                # solve failure must still be broadcast — as an error
                # sentinel every rank raises on — or the other ranks
                # block inside broadcast_json until the distributed
                # failure detector fires (opaque cluster hang; same
                # fail-fast rationale as engine._execute_multihost).
                new_plan = None
                if future is not None:
                    try:
                        new_plan = future.result().to_json()
                    except Exception as e:
                        new_plan = {
                            "__solve_error__": f"{type(e).__name__}: {e}"
                        }
                future = None
                payload = distributed.broadcast_json(new_plan)
                if isinstance(payload, dict) and "__solve_error__" in payload:
                    raise RuntimeError(
                        "re-solve failed on coordinator: "
                        + payload["__solve_error__"]
                    )
                plan = _gate_resolved_plan(
                    milp.Plan.from_json(payload), plan, topo, remaining,
                    interval, None, interval_index,
                )
                logger.info("re-solve: makespan %.1fs", plan.makespan)
                metrics.event("solve", makespan_s=plan.makespan,
                              n_tasks=len(remaining),
                              plan=plan.to_json())
            elif future is not None:
                # Join the overlapped solve BEFORE the failure handling
                # below mutates Task/Strategy state the solver thread
                # reads (retry rollback rewrites strategy runtimes).
                plan = _gate_resolved_plan(
                    future.result(), plan, topo, remaining, interval,
                    journal, interval_index,
                )
                future = None
                # Evictions happen after the solve was submitted: the
                # plan may still cover dropped tasks; their slots simply
                # idle for one interval and vanish at the next re-solve.
                logger.info("re-solve: makespan %.1fs", plan.makespan)
                metrics.event("solve", makespan_s=plan.makespan,
                              n_tasks=len(remaining),
                              plan=plan.to_json())
                if journal is not None:
                    journal.append("plan_commit",
                                   interval=interval_index + 1,
                                   makespan=plan.makespan,
                                   plan=plan.to_json())

            # Estimate feedback: fold each task's realized per-batch time
            # into its executed strategy (EWMA) now that no solver thread
            # is reading strategy state; the NEXT re-solve and forecast
            # consume the corrected numbers. The reference only logged
            # this error (``executor.py:126-129``).
            local_updates = fold_realized_feedback(run_tasks)
            all_updates = local_updates
            if multihost and run_tasks:
                # All ranks must forecast from identical numbers. Each
                # task's numbers come from the rank that actually ran it
                # (the lowest process of its EXECUTED block) —
                # broadcasting the coordinator's view would throw away
                # realized-feedback corrections for tasks on other
                # hosts' blocks forever. The merged update map rides the
                # same broadcast so the coordinator (sole metrics
                # writer) records corrections made on other hosts.
                src = {}
                for t in run_tasks:
                    a = executed_assignments.get(t.name)
                    if a is not None:
                        devs = topo.block_devices(a.block)
                        src[t.name] = min(
                            getattr(d, "process_index", 0) for d in devs
                        )
                all_updates = distributed.sync_task_state(
                    run_tasks, src, local_updates
                )
            for name, (old, new) in sorted(all_updates.items()):
                metrics.event(
                    "estimate_update", task=name,
                    profiled_s=round(old, 6), updated_s=round(new, 6),
                )
                if abs(new - old) > 0.25 * max(old, 1e-9):
                    logger.info(
                        "estimate correction for %s: %.3fs -> %.3fs "
                        "per batch", name, old, new,
                    )

            preempted = {
                n: e for n, e in errors.items()
                if isinstance(e, PreemptedError)
            }
            if preempted:
                # Abort-and-requeue: preemption is the fleet's fault, not
                # the task's — roll back forecast's accounting and requeue
                # WITHOUT counting against max_task_retries; the next
                # loop-top health poll replans onto the surviving mesh
                # and the task resumes from its checkpoint there.
                errors = {
                    n: e for n, e in errors.items() if n not in preempted
                }
                by_name = {t.name: t for t in run_tasks}
                for name, err in sorted(preempted.items()):
                    t = by_name[name]
                    release = getattr(t, "release_live_state", None)
                    if release is not None:
                        release()  # device state died with the chips
                    engine.rollback_forecast(t, batches.get(name, 0))
                    metrics.event("task_preempted", task=name,
                                  error=repr(err))
                    logger.warning(
                        "task %s preempted — requeued for replan: %r",
                        name, err,
                    )
                    if t not in remaining:
                        remaining.append(t)  # was forecast-completed
                completed = [
                    t for t in completed if t.name not in preempted
                ]

            health_errs = (
                {n: e for n, e in errors.items() if guardian.owns(e)}
                if guardian is not None and errors else {}
            )
            if health_errs:
                # Guardian path: rollback to the last published
                # checkpoint + backoff/quarantine/detach/evict — a ledger
                # separate from both preemption and max_task_retries.
                errors = {
                    n: e for n, e in errors.items()
                    if n not in health_errs
                }
                by_name = {t.name: t for t in run_tasks}
                group_of = plan.coschedule_group_of()
                for name, err in sorted(health_errs.items()):
                    t = by_name[name]
                    release = getattr(t, "release_live_state", None)
                    if release is not None:
                        release()  # poisoned/hung device state is dead
                    engine.rollback_forecast(t, batches.get(name, 0))
                    decision = guardian.on_fault(
                        t, err, interval_index,
                        in_group=name in group_of,
                    )
                    if journal is not None:
                        # Kill point: quarantine/detach records are
                        # already durable (guardian journals them with
                        # an immediate commit) — a kill here must replay
                        # them on restart.
                        journal.barrier("post-rollback", task=name,
                                        interval=interval_index)
                    if decision.action == "retry":
                        parked.append(t)
                        logger.warning(
                            "task %s health fault (%s, attempt %d) — "
                            "rolled back, parked for %d interval(s)",
                            name, decision.cause, decision.attempt,
                            decision.cooldown,
                        )
                    else:
                        all_failed[name] = repr(err)
                        if journal is not None:
                            journal.append("task_failed", task=name,
                                           error=repr(err))
                        metrics.event("task_failed", task=name,
                                      error=repr(err))
                        logger.error(
                            "evicting task %s after exhausted health "
                            "retry budget: %r", name, err,
                        )
                        release_c = getattr(t, "release_compiled", None)
                        if release_c is not None:
                            release_c()
                remaining = [
                    t for t in remaining if t.name not in health_errs
                ]
                completed = [
                    t for t in completed if t.name not in health_errs
                ]

            if errors:  # "drop": evict failed tasks; "retry": give them
                # max_task_retries more intervals first
                by_name = {t.name: t for t in run_tasks}
                retried: List = []
                for name, err in errors.items():
                    t = by_name[name]
                    release = getattr(t, "release_live_state", None)
                    if release is not None:
                        release()  # free HBM before the block is reused
                    retries[name] = retries.get(name, 0) + 1
                    if (
                        failure_policy == "retry"
                        and retries[name] <= max_task_retries
                    ):
                        # Roll back forecast's optimistic accounting: the
                        # batches it pre-deducted never ran (the checkpoint
                        # is the ground truth the retry resumes from).
                        engine.rollback_forecast(t, batches.get(name, 0))
                        retried.append(t)
                        metrics.event("task_retry", task=name,
                                      attempt=retries[name], error=repr(err))
                        logger.warning(
                            "task %s failed (attempt %d/%d) — retrying "
                            "next interval from its last checkpoint: %r",
                            name, retries[name], max_task_retries + 1, err,
                        )
                    else:
                        all_failed[name] = repr(err)
                        if journal is not None:
                            journal.append("task_failed", task=name,
                                           error=repr(err))
                        metrics.event("task_failed", task=name, error=repr(err))
                        logger.warning("evicting failed task %s: %r", name, err)
                        # permanently dropped: also free its compiled
                        # programs (a retried task keeps them — recompiling
                        # an identical program is the cost the cache avoids)
                        release_c = getattr(t, "release_compiled", None)
                        if release_c is not None:
                            release_c()
                keep = {t.name for t in retried}
                remaining = [
                    t for t in remaining
                    if t.name not in errors or t.name in keep
                ]
                for t in retried:
                    if t not in remaining:
                        remaining.append(t)  # was forecast-completed
                completed = [t for t in completed if t.name not in errors]

            for t in completed:
                all_completed.append(t.name)
                if journal is not None:
                    journal.append("task_completed", task=t.name)
                metrics.event("task_completed", task=t.name)
                release = getattr(t, "release_live_state", None)
                if release is not None:
                    release()  # free HBM held by finished tasks
                release_c = getattr(t, "release_compiled", None)
                if release_c is not None:
                    release_c()  # and their compiled programs
            task_list = remaining
            if journal is not None:
                # Interval-end group commit: one fsync covers this
                # interval's progress, plan and completion records.
                with metrics.span("journal.commit", interval=interval_index):
                    journal.append("interval_commit",
                                   interval=interval_index)
                    journal.commit()
            # Interval boundary for the buffered metrics writer too:
            # telemetry rides the buffer during the hot loop and lands
            # here, with the journal commit.
            metrics.flush()
            interval_index += 1
    logger.info("orchestration complete (%d completed, %d failed)",
                len(all_completed), len(all_failed))
    return {"completed": all_completed, "failed": all_failed}
