"""Chaos campaign harness: seeded mixed-fault sweeps with kill-replay.

PR 4's crash harness kills the controller at journal barriers; PR 8's
sentinel/guardian stack detects and recovers from *training* faults. This
module composes both into one campaign: a seeded schedule draws at least one
event from every health-fault class (NaN loss, loss spike, persistent batch
poisoning, dispatch stall), optionally arms a simulated SIGKILL at the
``post-rollback`` barrier (the window right after a faulted task's
quarantine/detach records went durable), and restarts the batch orchestrator
against the same journal directory until the batch completes — exactly the
operator's restart loop.

What a campaign proves (asserted by ``tests/test_chaos.py``):

- **zero lost jobs** — every task reaches ``completed`` across restarts;
- **quarantine survives the kill** — the skip-list replayed from the
  journal keeps a restarted run off the poisoned batches;
- **bit-identical recovery** — a faulted task's final checkpoint equals a
  fault-free run over the same surviving batch sequence, byte for byte
  (faults are injected at the observation level, never into train state).

Same seed, same campaign, every run — chaos testing without flakes.
"""

from __future__ import annotations

import logging
import os
import random
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from saturn_tpu.resilience.crash import CrashInjector, SimulatedKill
from saturn_tpu.resilience.faults import FaultEvent, FaultInjector, FaultKind

logger = logging.getLogger("saturn_tpu")

#: The guardian's detection targets — every campaign draws at least one
#: event per class listed in its spec.
HEALTH_FAULT_CLASSES = (
    FaultKind.NUMERIC_NAN,
    FaultKind.LOSS_SPIKE,
    FaultKind.BATCH_POISON,
    FaultKind.DISPATCH_STALL,
)


@dataclass(frozen=True)
class CampaignSpec:
    """One seeded campaign's shape.

    ``poison_range`` bounds the dataset indices batch poisoning may pick —
    keep it within the first interval's window so the fault is guaranteed
    to be observed (and small enough that quarantine never empties the
    dataset). ``stall_s`` is the injected dispatch wedge; pair it with a
    guardian whose watchdog deadline is below it so the watchdog, not
    patience, ends the stall. ``max_intervals_hit`` defaults to 1 — every
    fault lands in interval 0, so the first rollback is to the INITIAL
    state and a faulted run's final checkpoint is exactly comparable to a
    fault-free run with the quarantine pre-applied (a later-interval fault
    rolls back to a checkpoint whose pre-quarantine prefix a pre-applied
    reference never trains).
    """

    seed: int
    fault_classes: Tuple[str, ...] = HEALTH_FAULT_CLASSES
    kill_during_rollback: bool = False
    max_intervals_hit: int = 1     # faults land in intervals [0, hit)
    poison_range: int = 8
    poison_batches: int = 1
    stall_s: float = 0.3
    max_restarts: int = 8


@dataclass
class CampaignResult:
    """What one campaign run did, for the test/benchmark asserts."""

    seed: int
    completed: List[str] = field(default_factory=list)
    failed: Dict[str, str] = field(default_factory=dict)
    restarts: int = 0
    kills: int = 0
    schedule: List[FaultEvent] = field(default_factory=list)
    quarantined: Dict[str, List[int]] = field(default_factory=dict)
    detached: List[str] = field(default_factory=list)


def campaign_schedule(
    task_names: List[str], spec: CampaignSpec
) -> List[FaultEvent]:
    """Draw one fault event per class in ``spec.fault_classes``, targets and
    timing derived from the seed. Deterministic: same (names, spec) → same
    schedule."""
    if not task_names:
        raise ValueError("campaign needs at least one task")
    rng = random.Random(spec.seed)
    hit = max(1, spec.max_intervals_hit)
    events: List[FaultEvent] = []
    for kind in spec.fault_classes:
        target = rng.choice(list(task_names))
        at = rng.randrange(hit)
        if kind in (FaultKind.NUMERIC_NAN, FaultKind.LOSS_SPIKE):
            events.append(
                FaultEvent(at, kind, task=target, step=rng.randrange(4))
            )
        elif kind == FaultKind.BATCH_POISON:
            n = min(spec.poison_batches, spec.poison_range)
            idx = tuple(sorted(rng.sample(range(spec.poison_range), n)))
            events.append(FaultEvent(at, kind, task=target, batches=idx))
        elif kind == FaultKind.DISPATCH_STALL:
            events.append(
                FaultEvent(at, kind, task=target, stall_s=spec.stall_s)
            )
        else:
            raise ValueError(
                f"{kind!r} is not a health-fault class "
                f"(use one of {HEALTH_FAULT_CLASSES})"
            )
    return events


def run_campaign(
    tasks_factory: Callable[[], List[Any]],
    spec: CampaignSpec,
    workdir: str,
    guardian_config: Any = None,
    **orchestrate_kwargs,
) -> CampaignResult:
    """Run one seeded campaign to completion, restarting through kills.

    ``tasks_factory`` must return a FRESH task list per call — each
    incarnation rebuilds its tasks like a restarted process would, and the
    journal replay subtracts durably realized batches from their budgets.
    Keyword arguments are forwarded to ``orchestrate`` (``resume_dir`` and
    ``fault_injector`` are owned by the harness).

    The fault injector is re-created per incarnation, so consumed-once
    transients (NaN, spike, stall) scheduled for an interval index a restart
    revisits fire again — more chaos, same invariants: quarantined batch
    poisoning is restored from the journal and stays skipped, and every job
    still finishes. ``guardian_config`` (a ``GuardianConfig``) builds a
    FRESH guardian per incarnation — a restarted process carries no policy
    state, only what the journal replays.
    """
    from saturn_tpu.durability import recovery as rmod
    from saturn_tpu.executor.orchestrator import orchestrate

    tasks = tasks_factory()
    schedule = campaign_schedule([t.name for t in tasks], spec)
    result = CampaignResult(seed=spec.seed, schedule=list(schedule))

    barrier = None
    if spec.kill_during_rollback:
        barrier = CrashInjector("post-rollback", hit=1).barrier

    while True:
        injector = FaultInjector(schedule=list(schedule))
        guardian = None
        if guardian_config is not None:
            from saturn_tpu.health import TrainingGuardian

            guardian = TrainingGuardian(config=guardian_config)
        try:
            out = orchestrate(
                tasks,
                resume_dir=workdir,
                fault_injector=injector,
                crash_barrier=barrier,
                health_guardian=guardian,
                **orchestrate_kwargs,
            )
        except SimulatedKill:
            result.kills += 1
            result.restarts += 1
            if result.restarts > spec.max_restarts:
                raise RuntimeError(
                    f"campaign seed {spec.seed} exceeded "
                    f"{spec.max_restarts} restarts — runaway kill loop"
                )
            barrier = None  # the injector fired once; the process is "new"
            tasks = tasks_factory()
            logger.warning(
                "chaos campaign (seed %d): killed at post-rollback — "
                "restart %d", spec.seed, result.restarts,
            )
            continue
        break

    result.completed = list(out["completed"])
    result.failed = dict(out["failed"])
    state = rmod.replay_batch_state(workdir)
    result.quarantined = dict(state.quarantined)
    result.detached = list(state.detached)
    return result


def compare_checkpoints(
    dir_a: str, dir_b: str, names: Optional[List[str]] = None
) -> List[str]:
    """Byte-for-byte comparison of final published checkpoints.

    Compares ``{name}.npz`` checkpoints under both directories (all common
    stems when ``names`` is None; per-rank shard files and quarantine
    sidecars are not themselves checkpoints and are skipped) array-by-array
    on the raw buffer — the bit-identity the campaign promises, strict
    enough to catch a single flipped mantissa bit and NaN-safe (``==`` is
    not). Reads through ``checkpoint.load_arrays`` so sharded-manifest and
    legacy single-file checkpoints compare interchangeably. Returns a list
    of human-readable mismatch descriptions; empty means identical.
    """
    from saturn_tpu.utils import checkpoint as ckpt
    from saturn_tpu.utils.checkpoint import _SHARD_RE

    if names is None:
        stems = sorted(
            os.path.splitext(f)[0]
            for f in os.listdir(dir_a)
            if f.endswith(".npz") and ".corrupt" not in f
            and not _SHARD_RE.search(f)
        )
    else:
        stems = list(names)
    mismatches: List[str] = []
    for stem in stems:
        pa = os.path.join(dir_a, f"{stem}.npz")
        pb = os.path.join(dir_b, f"{stem}.npz")
        if not os.path.exists(pb):
            mismatches.append(f"{stem}: missing from {dir_b}")
            continue
        a = ckpt.load_arrays(pa)
        b = ckpt.load_arrays(pb)
        ka, kb = set(a), set(b)
        if ka != kb:
            mismatches.append(
                f"{stem}: key sets differ ({sorted(ka ^ kb)})"
            )
            continue
        for k in sorted(ka):
            va, vb = a[k], b[k]
            if va.shape != vb.shape or va.dtype != vb.dtype:
                mismatches.append(
                    f"{stem}[{k}]: shape/dtype {va.shape}/{va.dtype} "
                    f"vs {vb.shape}/{vb.dtype}"
                )
            elif va.tobytes() != vb.tobytes():
                mismatches.append(f"{stem}[{k}]: bytes differ")
    return mismatches
