"""Engine-level recovery policy for health faults, plus the hung-dispatch
watchdog's error type and deadline rule.

The guardian owns the *policy* half of training health: the sentinel (and
the engine watchdog) detect, the orchestrator/service rolls back, and this
class decides what happens next — retry with exponential backoff, quarantine
the offending batch range, detach the task from its co-schedule group, or
evict. Its budgets are deliberately separate ledgers from both the
preemption path (never charged — losing chips is the fleet's fault) and
``max_task_retries`` (ordinary crashes): a job that NaNs twice and then
trains clean should neither burn its crash budget nor be whitewashed by a
preemption requeue.

Policy, per (task, cause) with CONSECUTIVE counting (a clean interval
resets the streak via :meth:`TrainingGuardian.note_success`):

1. every fault: roll back to the last published checkpoint (caller runs
   ``rollback_forecast``), then park the task for ``backoff_base * 2^(k-1)``
   intervals (capped);
2. a repeated data-cause fault (``quarantine_after``-th consecutive)
   additionally quarantines the faulting window's dataset indices — the
   cursor rolled back, so a deterministic bad batch re-faults at the same
   indices and the skip-list is exactly the fix;
3. a grouped task at ``detach_after`` faults is detached from its
   co-schedule group (the re-solve excludes it from the co-location term)
   so healthy partners keep interleaving without it;
4. past ``retry_budget`` (``hung_budget`` for hung dispatches) the task is
   evicted through the caller's failure path.

Every transition is journaled (``health_fault`` / ``health_backoff``
buffered; ``health_quarantine`` / ``health_detach`` group-commit
immediately — rare, load-bearing for kill-replay) and mirrored to metrics
with stable ``SAT-H*`` event codes (see ``docs/architecture.md`` runbook).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Dict, FrozenSet, Iterable, List, Optional, Tuple

from saturn_tpu.analysis import concurrency as tsan
from saturn_tpu.health.sentinel import NumericFaultError
from saturn_tpu.utils import metrics

logger = logging.getLogger("saturn_tpu")

#: Stable operator-facing event codes (``metrics`` events + runbook).
HEALTH_EVENT_CODES = {
    "numeric_fault": "SAT-H001",
    "hung_dispatch": "SAT-H002",
    "backoff": "SAT-H003",
    "quarantine": "SAT-H010",
    "unquarantine": "SAT-H011",
    "detach": "SAT-H020",
    "evict": "SAT-H030",
}


class HungDispatchError(RuntimeError):
    """A task's interval dispatch exceeded its watchdog deadline.

    Raised *on the task's behalf* by the engine's join-side watchdog (the
    launcher thread itself is wedged — that is the point); the attempt is
    abandoned, the last published checkpoint stays ground truth, and the
    guardian escalates timeout -> rollback -> evict.
    """

    def __init__(self, job: str, deadline_s: float, elapsed_s: float):
        self.job = job
        self.deadline_s = float(deadline_s)
        self.elapsed_s = float(elapsed_s)
        super().__init__(
            f"hung dispatch: job {job} exceeded its watchdog deadline "
            f"({elapsed_s:.1f}s elapsed > {deadline_s:.1f}s allowed)"
        )


CAUSE_HUNG = "hung_dispatch"


@dataclass(frozen=True)
class GuardianConfig:
    """Recovery policy.

    ``watchdog_floor_s`` is generous by default because the FIRST interval
    of a task pays XLA compilation inside its window — the deadline is
    ``floor + factor x profiled window time``, so the profiled term only
    dominates once windows are long enough for compile noise not to matter.
    """

    retry_budget: int = 3        # consecutive numeric faults before evict
    hung_budget: int = 2         # consecutive hung dispatches before evict
    quarantine_after: int = 2    # consecutive data faults before quarantine
    detach_after: int = 2        # consecutive faults before group detach
    backoff_base: int = 1        # cooldown intervals after the 1st fault
    backoff_cap: int = 8         # cooldown ceiling (intervals)
    watchdog: bool = True
    watchdog_factor: float = 8.0   # k in  k x profiled window time
    watchdog_floor_s: float = 60.0


@dataclass(frozen=True)
class FaultDecision:
    """What the guardian decided for one fault."""

    action: str                       # "retry" | "evict"
    cause: str
    attempt: int                      # consecutive fault count for this cause
    cooldown: int = 0                 # backoff, in intervals (retry only)
    quarantined: Tuple[int, ...] = () # dataset indices quarantined just now
    detached: bool = False            # detached from its group just now


class TrainingGuardian:
    """Per-run health policy state.

    Policy *decisions* are made from the single loop thread (orchestrator
    loop, service loop) after the engine's interval barrier; the streak /
    bench / detach ledgers are nevertheless guarded by ``_mu`` because
    read paths (``benched``, ``detached_names``) are reachable from other
    threads (status endpoints, engine launcher callbacks) and a torn
    read-modify-write of a streak counter silently mis-counts a fault.
    The lock is leaf-level: nothing is called while holding it, so it can
    never participate in a lock-order cycle."""

    def __init__(self, config: Optional[GuardianConfig] = None, journal=None):
        self.config = config if config is not None else GuardianConfig()
        self.journal = journal
        self._mu = tsan.lock("guardian.lock")
        # (task, cause) -> consecutive faults; cleared by note_success.
        self._streak: Dict[Tuple[str, str], int] = {}
        # task -> consecutive faults of ANY cause (drives group detach).
        self._total: Dict[str, int] = {}
        self._detached: set = set()
        # task -> first interval index it may run again (backoff parking).
        self._benched: Dict[str, int] = {}

    # ------------------------------------------------------- classification
    @staticmethod
    def owns(err: BaseException) -> bool:
        """Is this a health fault the guardian manages (vs an ordinary task
        failure charged to ``max_task_retries``)?"""
        return isinstance(err, (NumericFaultError, HungDispatchError))

    @staticmethod
    def cause_of(err: BaseException) -> str:
        if isinstance(err, NumericFaultError):
            return err.cause
        return CAUSE_HUNG

    @property
    def watchdog_enabled(self) -> bool:
        return self.config.watchdog

    # ------------------------------------------------------------ watchdog
    def window_deadline_s(self, expected_s: float) -> float:
        """Deadline for an interval expected to take ``expected_s`` of
        profiled window time: ``floor + factor x expected``."""
        return self.config.watchdog_floor_s + self.config.watchdog_factor * max(
            float(expected_s), 0.0
        )

    # -------------------------------------------------------------- policy
    def on_fault(
        self, task: Any, err: BaseException, interval_index: int,
        in_group: bool = False,
    ) -> FaultDecision:
        """Classify one health fault and decide retry/evict. The caller has
        already rolled the task back (release_live_state +
        ``rollback_forecast``); this only mutates policy state, the task's
        quarantine skip-list, and the journal."""
        cause = self.cause_of(err)
        key = (task.name, cause)
        with self._mu:
            streak = self._streak[key] = self._streak.get(key, 0) + 1
            total = self._total[task.name] = self._total.get(task.name, 0) + 1
        code = HEALTH_EVENT_CODES.get(
            "hung_dispatch" if cause == CAUSE_HUNG else "numeric_fault"
        )
        metrics.event(
            "health", code=code, task=task.name, cause=cause,
            attempt=streak, interval=interval_index,
        )
        self._journal(
            "health_fault", task=task.name, cause=cause, attempt=streak,
            interval=interval_index, error=repr(err),
        )

        quarantined: Tuple[int, ...] = ()
        if (
            isinstance(err, NumericFaultError)
            and err.batch_indices
            and streak >= self.config.quarantine_after
        ):
            quarantined = self.quarantine(task, err.batch_indices)

        detached = False
        if (
            in_group
            and task.name not in self._detached
            and total >= self.config.detach_after
        ):
            self.detach(task.name)
            detached = True

        budget = (
            self.config.hung_budget if cause == CAUSE_HUNG
            else self.config.retry_budget
        )
        if streak > budget:
            metrics.event(
                "health", code=HEALTH_EVENT_CODES["evict"], task=task.name,
                cause=cause, attempt=streak,
            )
            logger.error(
                "guardian: evicting %s after %d consecutive %s fault(s)",
                task.name, streak, cause,
            )
            return FaultDecision(
                "evict", cause=cause, attempt=streak,
                quarantined=quarantined, detached=detached,
            )

        cooldown = min(
            self.config.backoff_cap,
            max(1, self.config.backoff_base) * (2 ** (streak - 1)),
        )
        resume_at = interval_index + 1 + cooldown
        with self._mu:
            self._benched[task.name] = resume_at
        metrics.event(
            "health", code=HEALTH_EVENT_CODES["backoff"], task=task.name,
            cause=cause, attempt=streak, cooldown_intervals=cooldown,
        )
        self._journal(
            "health_backoff", task=task.name, cause=cause, attempt=streak,
            cooldown_intervals=cooldown,
            resume_interval=resume_at,
        )
        logger.warning(
            "guardian: %s fault #%d on %s — rolled back, retrying after "
            "%d-interval backoff%s%s",
            cause, streak, task.name, cooldown,
            f", quarantined batches {list(quarantined)}" if quarantined else "",
            ", detached from co-schedule group" if detached else "",
        )
        return FaultDecision(
            "retry", cause=cause, attempt=streak, cooldown=cooldown,
            quarantined=quarantined, detached=detached,
        )

    def note_success(self, name: str) -> None:
        """A clean interval resets the consecutive-fault ledgers (quarantine
        and detach state persist — they are corrections, not penalties)."""
        with self._mu:
            self._total.pop(name, None)
            for key in [k for k in self._streak if k[0] == name]:
                del self._streak[key]

    # ---------------------------------------------------------- quarantine
    def quarantine(self, task: Any, indices: Iterable[int]) -> Tuple[int, ...]:
        """Add dataset indices to the task's skip-list; journaled with an
        immediate group commit — a kill during the subsequent rollback must
        replay the quarantine or the restart deterministically re-faults."""
        idx = tuple(sorted({int(i) for i in indices}))
        if not idx:
            return ()
        try:
            task.quarantine_batches(idx)
        except ValueError as e:
            # The task refused (skip-listing these would empty the dataset).
            # Don't crash the recovery path: keep retrying under the budget
            # and let eviction handle a job whose every batch faults.
            logger.warning("guardian: quarantine refused for %s: %s",
                           task.name, e)
            return ()
        metrics.event(
            "health", code=HEALTH_EVENT_CODES["quarantine"], task=task.name,
            batches=list(idx),
        )
        self._journal(
            "health_quarantine", task=task.name, indices=list(idx),
            durable=True,
        )
        return idx

    def detach(self, name: str) -> None:
        """Exclude the task from co-schedule candidate generation at every
        future (re-)solve."""
        with self._mu:
            self._detached.add(name)
        metrics.event(
            "health", code=HEALTH_EVENT_CODES["detach"], task=name,
        )
        self._journal("health_detach", task=name, durable=True)

    def detached_names(self) -> FrozenSet[str]:
        with self._mu:
            return frozenset(self._detached)

    # -------------------------------------------------------------- parking
    def benched(self, name: str, interval_index: int) -> bool:
        """Is the task still inside its backoff window? Clears the bench
        entry once the resume interval is reached."""
        with self._mu:
            resume = self._benched.get(name)
            if resume is None:
                return False
            if interval_index >= resume:
                del self._benched[name]
                return False
            return True

    def resume_interval(self, name: str) -> Optional[int]:
        with self._mu:
            return self._benched.get(name)

    def unbench_all(self, cause: str = "grow") -> Tuple[str, ...]:
        """Short-circuit every remaining backoff window (grow event: fresh
        capacity should run parked work *now*, not ``ceil(backoff)``
        intervals later). The consecutive-fault streak ledgers are
        deliberately untouched — the next fault of a flaky task still sees
        its full history and backs off harder, exactly as if the bench had
        expired naturally."""
        with self._mu:
            released = tuple(sorted(self._benched))
            self._benched.clear()
        for name in released:
            metrics.event(
                "health", code=HEALTH_EVENT_CODES["backoff"], task=name,
                cause=cause, unbenched=True,
            )
        if released:
            self._journal(
                "health_unbench", tasks=list(released), cause=cause,
            )
        return released

    # ------------------------------------------------------------- recovery
    def restore(
        self,
        quarantined: Dict[str, List[int]],
        detached: Iterable[str],
        tasks: Iterable[Any] = (),
    ) -> None:
        """Re-apply journaled health state after a crash: quarantine
        skip-lists onto the rebuilt task objects, detach set onto the
        guardian. Budgets/backoff deliberately reset — an incarnation
        boundary is a clean slate for transient-fault counting."""
        by_name = {t.name: t for t in tasks}
        for name, idx in (quarantined or {}).items():
            t = by_name.get(name)
            if t is not None and idx:
                t.quarantine_batches(idx)
                logger.info(
                    "recovery: re-applied quarantine of %d batch(es) to %s",
                    len(idx), name,
                )
        with self._mu:
            self._detached.update(detached or ())

    # -------------------------------------------------------------- journal
    def _journal(self, kind: str, durable: bool = False, **data) -> None:
        jnl = self.journal
        if jnl is None:
            return
        if durable:
            jnl.log(kind, **data)
        else:
            jnl.append(kind, **data)
