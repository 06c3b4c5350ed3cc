"""Admission control: profile arrivals, gate on fit, weight the objective.

Profiling rides the existing trial-runner stack end to end — persistent
profile cache first, cost-model (Amdahl) pruning for uncached grids — so a
*warm* arrival (same model/data/optimizer fingerprint seen before, any
priority) admits in O(cache lookup) with **zero** trial executions, while a
cold arrival pays the sweep exactly once across the fleet's lifetime.
Requeued jobs (preemption round-trips) skip profiling entirely: their
strategies are already populated in-process.

Fit gating: a job with no feasible strategy that fits the *current* mesh is
REJECTED on a full-capacity mesh (it will never fit) but DEFERRED when the
mesh is degraded below its base capacity (a grow event may re-admit it).

Weights: each admitted job gets a solver-objective weight

    w = 2^priority * (1 + est_runtime / max(deadline_slack, est_runtime))

— exponential in priority so integer priority classes strictly dominate,
with a deadline-urgency boost capped at 2x (a job whose estimated runtime
already consumes its slack is maximally urgent). ``solver.milp`` folds the
normalized weights into the objective as a weighted-start-time tiebreak.

Tenancy (when the service wires a ``TenantLedger``): before any profiling
spend, the arrival's tenant is gated on its quota — over ``max_live_jobs``
DEFERs (the tenant's own completions free the slot), an exhausted
``chip_seconds`` budget REJECTs — and an admitted job's weight is scaled
by the tenant's weighted-fair-share multiplier, so an over-share tenant's
new work yields the solver's attention to under-share tenants without
overriding priority classes or deadlines.
"""

from __future__ import annotations

import logging
import time
import timeit
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.service.queue import JobRecord, JobState, SubmissionQueue
from saturn_tpu.utils import metrics

logger = logging.getLogger("saturn_tpu")

ADMIT = "admit"
REJECT = "reject"
DEFER = "defer"

# revisit_on hints carried by DEFER decisions: what event can change the
# verdict, so operators (and the grow coordinator) know what a grow or
# defrag wave would drain.
REVISIT_INTERVAL = "interval"  # the tenant's own completions free the slot
REVISIT_GROW = "grow"          # a grow event restores the missing capacity
REVISIT_DEFRAG = "defrag"      # capacity exists; pinned HBM must compact


@dataclass
class AdmissionDecision:
    action: str                  # ADMIT | REJECT | DEFER
    reason: str = ""
    trials_run: int = 0          # trials the profiling sweep executed
    weight: float = 0.0          # solver objective weight (ADMIT only)
    latency_s: float = 0.0       # wall-clock admission latency
    # This decision rests on shardflow cold-start priors: the job's
    # strategies were synthesized from the static sharding/communication
    # analysis (``analysis/shardflow/prior.py``), not from trials. Realized
    # feedback supersedes them; SAT-X005 audits the estimate afterwards.
    static_prior: bool = False
    # DEFER only: which event should re-open this verdict (see REVISIT_*).
    revisit_on: str = ""


def _min_feasible_runtime(task) -> float:
    feas = task.feasible_strategies()
    return min(s.runtime for s in feas.values()) if feas else 0.0


def compute_weight(priority: float, deadline_slack_s: Optional[float],
                   est_runtime_s: float) -> float:
    """Priority/deadline weight for the solver objective (see module doc)."""
    w = 2.0 ** float(priority)
    if deadline_slack_s is not None:
        est = max(est_runtime_s, 1e-9)
        w *= 1.0 + est / max(deadline_slack_s, est)
    return w


class AdmissionController:
    """Profiles and gates arrivals for :class:`~saturn_tpu.service.server.
    SaturnService`. Single-threaded: only the server loop calls it."""

    def __init__(
        self,
        topology: SliceTopology,
        queue: SubmissionQueue,
        technique_names: Optional[List[str]] = None,
        profile_cache: Any = None,
        prune: bool = True,
        parallel_trials: Optional[int] = None,
        static_priors: bool = False,
    ):
        self.base_capacity = topology.capacity
        self.technique_names = technique_names
        self.profile_cache = profile_cache
        self.prune = prune
        self.parallel_trials = parallel_trials
        #: Opt-in shardflow cold-start path: a never-profiled arrival gets
        #: ``static_prior=True`` strategies from the jaxpr-level sharding /
        #: communication analysis instead of paying the trial sweep up
        #: front. ADMIT/DEFER become sharding-aware with zero chip time;
        #: the first realized interval supersedes the prior and SAT-X005
        #: audits it (``_audit_priors``).
        self.static_priors = static_priors
        self.queue = queue
        #: Optional write-ahead journal (set by ``SaturnService`` when
        #: durability is on): every admission outcome becomes a buffered
        #: ``job_admission`` record, durable at the next group commit.
        self.journal = None
        #: Optional TenantLedger (set by ``SaturnService`` when tenancy is
        #: on): quota gates + fair-share weight scaling, see module doc.
        self.tenancy = None
        #: Optional occupancy gate (set by the grow coordinator): called
        #: ``gate(task, topology) -> verdict-dict | None`` after the size
        #: fit passes. A ``{"fits": False, ...}`` verdict DEFERs with
        #: ``revisit_on="defrag"`` — the schedule has room but other tasks'
        #: device-resident live state pins too much HBM; a defrag wave can
        #: free it. ``None`` = no verdict (fail open).
        self.occupancy_gate: Optional[Callable] = None
        #: DEFER pool: job_id -> {task, tenant, reason, revisit_on,
        #: deferred_at, count}. Entries land on every DEFER and leave on
        #: the job's next ADMIT/REJECT; the grow coordinator reads it to
        #: know what a grow event or defrag wave would drain, and the
        #: ``analysis grow``/``tenancy`` views report backlog age from the
        #: journaled ``job_deferred`` records.
        self.deferred: Dict[str, dict] = {}
        #: tenant -> jobs ADMITted in the *current* drain pass. The queue
        #: only counts a job as admitted once the post-solve SCHEDULED mark
        #: lands, so without this a burst draining in one pass would sail
        #: past ``max_live_jobs`` together. The server resets it via
        #: :meth:`begin_pass` before each drain.
        self._pass_admitted: dict = {}

    def begin_pass(self) -> None:
        """Start a new drain pass (resets the in-pass admission tally)."""
        # sanctioned-unlocked: drain-pass scratch owned by the scheduler
        # thread (see admit); cleared here before each drain.
        self._pass_admitted.clear()

    def admit(self, rec: JobRecord, topology: SliceTopology) -> AdmissionDecision:
        """Profile (if needed) and decide one arrival.

        Transitions the record QUEUED -> PROFILING here; the *caller* applies
        the decision (SCHEDULED on admit after the re-solve, QUEUED on defer,
        FAILED on reject) — admission decides, the server owns the plan.
        """
        t0 = timeit.default_timer()
        self.queue.mark(rec, JobState.PROFILING)
        task = rec.task

        # Tenant quota gate: before a single trial or compile is spent on
        # this arrival. Both verdicts are cheap ledger lookups.
        if self.tenancy is not None:
            dec = self._tenant_gate(rec, t0)
            if dec is not None:
                self._note(rec, dec)
                return dec

        # Memlens cold-start memory gate: before any trial or compile, the
        # static liveness analysis checks every fitting (technique, size,
        # config) grid point against per-device HBM capacity. A verdict
        # only exists when capacity is known AND every point traced and
        # predicted OOM — anything unknown falls through to the sweep, and
        # the compile-time check stays the authoritative backstop.
        mem = self._memlens_verdict(task, topology)
        if mem is not None and not mem["fits"]:
            degraded = topology.capacity < self.base_capacity
            dec = AdmissionDecision(
                DEFER if degraded else REJECT,
                reason=(
                    f"memlens: predicted per-device HBM peak "
                    f"{mem['min_peak_bytes']} B exceeds capacity "
                    f"{mem['capacity_bytes']} B at every fitting size "
                    f"({mem['checked']} grid points, zero trials)"
                ),
                latency_s=timeit.default_timer() - t0,
                revisit_on=REVISIT_GROW if degraded else "",
            )
            self._note(rec, dec)
            return dec

        trials = 0
        used_prior = False
        if self.static_priors and not task.feasible_strategies():
            # Shardflow cold-start path: synthesize static-prior strategies
            # from the jaxpr-level analysis — zero trials, zero compiles.
            used_prior = self._synthesize_priors(rec, task, topology)
        if not task.feasible_strategies():
            # Cold (or never-seen) arrival: run the sweep. Warm fingerprints
            # resolve entirely from the profile cache — zero trials.
            from saturn_tpu.trial_runner import evaluator

            try:
                stats = evaluator.search(
                    [task],
                    technique_names=self.technique_names,
                    topology=topology,
                    profile_cache=self.profile_cache,
                    prune=self.prune,
                    parallel_trials=self.parallel_trials,
                )
            except Exception as e:
                dec = AdmissionDecision(
                    REJECT, reason=f"profiling failed: {e!r}",
                    latency_s=timeit.default_timer() - t0,
                )
                self._note(rec, dec)
                return dec
            trials = int((stats or {}).get("trials_run", 0))
        rec.trials_run += trials
        if self.static_priors:
            # SAT-X005: any strategy whose prior has since been superseded
            # by real evidence gets its static estimate audited now, while
            # the job is back in front of the controller.
            self._audit_priors(rec, task)

        fits = any(
            g <= topology.capacity for g in task.feasible_strategies()
        )
        if not fits and rec.requeues > 0:
            # A preempted job re-entering through the queue was already
            # running: instead of stranding it in DEFER until the mesh
            # grows back, synthesize a fitting strategy from its measured
            # anchors — the same Amdahl extrapolation the replanner applies
            # to jobs that were live when the topology shrank.
            from saturn_tpu.resilience.replan import ElasticReplanner

            added = ElasticReplanner()._synthesize(task, topology.capacity)
            if added:
                logger.info(
                    "admission: synthesized size(s) %s for requeued %s on "
                    "the %d-chip mesh", added, rec.job_id, topology.capacity,
                )
            fits = any(
                g <= topology.capacity for g in task.feasible_strategies()
            )
        if not fits:
            degraded = topology.capacity < self.base_capacity
            dec = AdmissionDecision(
                DEFER if degraded else REJECT,
                reason=(
                    "no feasible strategy fits the degraded mesh "
                    f"({topology.capacity}/{self.base_capacity} chips)"
                    if degraded else
                    f"no feasible strategy fits the mesh "
                    f"({topology.capacity} chips)"
                ),
                trials_run=trials,
                latency_s=timeit.default_timer() - t0,
                static_prior=used_prior,
                revisit_on=REVISIT_GROW if degraded else "",
            )
            self._note(rec, dec)
            return dec

        # Occupancy gate (grow coordinator): the gang fits the schedule,
        # but does its HBM footprint fit around other tasks' pinned live
        # state? A negative verdict is DEFER, never REJECT — a defrag wave
        # (or a completion releasing its state) re-opens it.
        if self.occupancy_gate is not None:
            try:
                occ = self.occupancy_gate(task, topology)
            except Exception as e:
                logger.debug("admission: occupancy gate skipped: %r", e)
                occ = None
            if occ is not None and not occ.get("fits", True):
                dec = AdmissionDecision(
                    DEFER,
                    reason=(
                        "occupancy: pinned live state leaves "
                        f"{occ.get('free_bytes', 0)} B free on every "
                        f"fitting block, need {occ.get('need_bytes', 0)} B "
                        "— a defrag wave can compact it"
                    ),
                    trials_run=trials,
                    latency_s=timeit.default_timer() - t0,
                    static_prior=used_prior,
                    revisit_on=REVISIT_DEFRAG,
                )
                self._note(rec, dec)
                return dec

        slack = None
        if rec.deadline_at is not None:
            import time as _time

            slack = rec.deadline_at - _time.monotonic()
        weight = compute_weight(
            rec.request.priority, slack, _min_feasible_runtime(task)
        )
        if self.tenancy is not None:
            # Weighted fair share: scale (never override) the priority/
            # deadline weight by how far the tenant sits from its slice.
            weight *= self.tenancy.fair_share_multiplier(
                rec.tenant, self.queue.live_by_tenant()
            )
        rec.weight = weight
        # Scheduling-only hints: the replanner's eviction policies order by
        # task.hints["priority"]; profile_cache.task_signature excludes both
        # keys so they never perturb warm cache hits.
        hints = getattr(task, "hints", None)
        if isinstance(hints, dict):
            hints["priority"] = float(rec.request.priority)
            if rec.request.deadline_s is not None:
                hints["deadline"] = float(rec.request.deadline_s)
        dec = AdmissionDecision(
            ADMIT, reason="static prior" if used_prior else "ok",
            trials_run=trials, weight=weight,
            latency_s=timeit.default_timer() - t0,
            static_prior=used_prior,
        )
        if self.tenancy is not None:
            self.tenancy.note_admit(rec.tenant)
            # sanctioned-unlocked: _pass_admitted is drain-pass scratch,
            # touched only by the single scheduler thread that calls
            # begin_pass()/admit() back-to-back — no concurrent access.
            self._pass_admitted[rec.tenant] = (
                self._pass_admitted.get(rec.tenant, 0) + 1
            )
        self._note(rec, dec)
        return dec

    # -------------------------------------------------------------- tenancy
    def _tenant_gate(self, rec: JobRecord, t0: float):
        """Quota verdict for the arrival's tenant, or None to proceed.

        Chip-seconds exhaustion is terminal (REJECT: the budget never
        refills by waiting); a full ``max_live_jobs`` window DEFERs — the
        tenant's own completions free slots, and the requeue re-admits
        warm. The gate counts *admitted* (SCHEDULED/RUNNING) jobs, not
        queued arrivals: counting a burst's own queued siblings would
        defer the whole burst forever.
        """
        tenant = rec.tenant
        quota = self.tenancy.quota(tenant)
        if self.tenancy.budget_exhausted(tenant):
            return AdmissionDecision(
                REJECT,
                reason=(
                    f"tenant {tenant!r} chip-seconds budget exhausted "
                    f"({self.tenancy.charged(tenant):.1f}s burned of "
                    f"{quota.chip_seconds:.1f}s)"
                ),
                latency_s=timeit.default_timer() - t0,
            )
        if quota.max_live_jobs is not None:
            admitted = (self.queue.admitted_tenant(tenant)
                        + self._pass_admitted.get(tenant, 0))
            if admitted >= quota.max_live_jobs:
                return AdmissionDecision(
                    DEFER,
                    reason=(
                        f"tenant {tenant!r} has {admitted} admitted job(s), "
                        f"at its max_live_jobs quota {quota.max_live_jobs}"
                    ),
                    latency_s=timeit.default_timer() - t0,
                    revisit_on=REVISIT_INTERVAL,
                )
        return None

    # -------------------------------------------------------------- memlens
    def _memlens_verdict(self, task, topology: SliceTopology):
        """Zero-trial memory verdict (or None). Restricted to this
        controller's technique roster; fails open on any error."""
        try:
            from saturn_tpu.analysis.memlens import passes as ml_passes
            from saturn_tpu.parallel import BUILTIN_TECHNIQUES

            names = self.technique_names or sorted(BUILTIN_TECHNIQUES)
            techniques = {
                n: (BUILTIN_TECHNIQUES[n]()
                    if isinstance(BUILTIN_TECHNIQUES[n], type)
                    else BUILTIN_TECHNIQUES[n])
                for n in names if n in BUILTIN_TECHNIQUES
            }
            return ml_passes.coldstart_verdict(
                task, topology, techniques=techniques)
        except Exception as e:
            logger.debug("admission: memlens verdict skipped: %r", e)
            return None

    # ------------------------------------------------------------ shardflow
    def _synthesize_priors(self, rec: JobRecord, task,
                           topology: SliceTopology) -> bool:
        """Fill the task's grid with static-prior strategies; never raises
        (an untraceable task just falls through to the trial sweep)."""
        try:
            from saturn_tpu.analysis.shardflow import prior as sf_prior

            with metrics.span("prior.shardflow", task=rec.name) as sp:
                added = sf_prior.synthesize_strategies(
                    task, topology, technique_names=self.technique_names,
                )
                sp.set(n_points=len(added or ()))
        except Exception as e:
            logger.warning(
                "admission: shardflow prior failed for %s (%r); falling "
                "back to the trial sweep", rec.job_id, e,
            )
            return False
        if added:
            logger.info(
                "admission: %s admitted on shardflow static priors at "
                "sizes %s (no trials)", rec.job_id, added,
            )
        return bool(added)

    def _audit_priors(self, rec: JobRecord, task) -> None:
        """Emit SAT-X005 for superseded priors (warn-only, never gates)."""
        try:
            from saturn_tpu.analysis.shardflow import prior as sf_prior

            diags = sf_prior.audit_task(task)
            # Same audit stream, second consumer: measured step times on
            # formerly-overlapped priors move the per-op-class overlap
            # factors, so the next cold-start/admission/solver pass prices
            # overlap from evidence instead of the static seeds. Warn-only
            # path — a calibration failure must never gate admission.
            sf_prior.calibrate_overlap_factors([task])
        except Exception:
            return
        for d in diags:
            logger.warning("admission: %s %s", rec.job_id, d.message)
            metrics.event(
                "shardflow_audit", job=rec.job_id, task=rec.name,
                **d.to_json(),
            )

    def _note(self, rec: JobRecord, dec: AdmissionDecision) -> None:
        if self.journal is not None:
            # sanctioned-unlocked: journal buffering is internally locked;
            # admission runs only on the scheduler thread (see begin_pass)
            self.journal.append(
                "job_admission", job=rec.job_id, task=rec.name,
                decision=dec.action, reason=dec.reason,
                trials_run=dec.trials_run, weight=round(dec.weight, 6),
                static_prior=dec.static_prior, tenant=rec.tenant,
            )
        self._note_deferred(rec, dec)
        metrics.event(
            "job_admitted", job=rec.job_id, task=rec.name,
            decision=dec.action, reason=dec.reason,
            trials_run=dec.trials_run, warm=dec.trials_run == 0,
            weight=round(dec.weight, 6), latency_s=round(dec.latency_s, 6),
            static_prior=dec.static_prior,
        )
        logger.info(
            "admission: %s %s (%s; %d trials, weight %.3f, %.3fs)",
            rec.job_id, dec.action, dec.reason or "ok", dec.trials_run,
            dec.weight, dec.latency_s,
        )

    def _note_deferred(self, rec: JobRecord, dec: AdmissionDecision) -> None:
        """Maintain the DEFER pool + journal ``job_deferred`` visibility
        records. A record lands only on the *first* defer of a job or when
        its reason class (revisit_on) changes — re-defers on the same
        grounds would otherwise flood the journal every interval."""
        if dec.action != DEFER:
            self.deferred.pop(rec.job_id, None)
            return
        prev = self.deferred.get(rec.job_id)
        entry = {
            "task": rec.name,
            "tenant": rec.tenant,
            "reason": dec.reason,
            "revisit_on": dec.revisit_on,
            "deferred_at": prev["deferred_at"] if prev else time.time(),
            "count": (prev["count"] + 1) if prev else 1,
        }
        self.deferred[rec.job_id] = entry
        changed = prev is None or prev["revisit_on"] != dec.revisit_on
        if changed and self.journal is not None:
            # sanctioned-unlocked: journal buffering is internally locked;
            # admission runs only on the scheduler thread (see begin_pass)
            self.journal.append(
                "job_deferred", job=rec.job_id, task=rec.name,
                tenant=rec.tenant, reason=dec.reason,
                revisit_on=dec.revisit_on,
                at=round(entry["deferred_at"], 6),
            )
