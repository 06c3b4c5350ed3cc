"""Strategy: the (technique, sub-mesh size, params, runtime) tuple the solver picks.

TPU-native analog of the reference's ``saturn/core/representations/Strategy.py:50-76``.
Differences from the reference (intentional, idiomatic-TPU):

- The allocation unit is a **contiguous ICI sub-mesh size** (power-of-two number of
  chips of the pod slice), not a flat GPU count. The solver later picks *which*
  aligned block of that size the job runs on (buddy-style allocation preserves ICI
  contiguity on the torus).
- ``Techniques`` lists the techniques the built-in library actually ships. The
  reference declared ``MEGATRON = 4`` but never implemented it
  (``Strategy.py:34``); here tensor parallelism is a real executor.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


class Techniques(enum.Enum):
    """Built-in parallelism techniques (reference: ``Strategy.py:25-34``)."""

    DP = 1          # batch-sharded pjit over a 1-D `data` mesh axis
    FSDP = 2        # GSPMD fully-sharded params (ZeRO-3 style)
    PIPELINE = 3    # stage-sharded layers, microbatched (GPipe-style)
    OFFLOAD = 4     # host-memory param/activation offload ("spilling")
    TENSOR = 5      # Megatron-style tensor parallelism over a `model` axis
    RING = 6        # sequence/context parallelism with ring attention
    ULYSSES = 7     # sequence parallelism with all-to-all head resharding
    EXPERT = 8      # expert parallelism for mixture-of-experts models
    # Aliases matching the reference's member names (``Strategy.py:31-34``)
    # so users switching from it can keep their spelling.
    SPILLED = 4     # reference's name for offload
    MEGATRON = 5    # reference's name for tensor parallelism


@dataclass
class Strategy:
    """One profiled execution option for a task.

    Reference: ``Strategy.py:50-73`` — (executor, gpu_apportionment, params,
    runtime). Here ``apportionment`` is the number of chips in the contiguous
    sub-mesh; ``params`` are the technique's autotuned knobs returned by
    ``BaseTechnique.search``; ``runtime`` is the estimated *remaining* runtime in
    seconds for the task under this strategy (decremented by the forecast loop as
    batches complete — reference ``executor.py:165-172``).
    """

    executor: Any                      # BaseTechnique instance (or None = dummy)
    apportionment: int                 # number of chips (power of two)
    params: Optional[Dict[str, Any]]   # autotuned knobs; None = infeasible
    runtime: float                     # est. remaining runtime, seconds
    per_batch_time: float = field(default=0.0)  # seconds per batch (profiled)
    # Cost-model estimate, not a measured trial: the trial runner profiles
    # only anchor sizes and fills the rest from an Amdahl-style fit
    # (``trial_runner/evaluator.py``). Cleared the first time a realized
    # interval measurement lands on this strategy (``Task.apply_realized_feedback``).
    interpolated: bool = field(default=False)
    # Synthesized by the shardflow cold-start prior
    # (``analysis/shardflow/prior.py``): runtime comes from the static
    # roofline + communication-ledger model, not from any trial. Like
    # ``interpolated``, cleared the moment real evidence lands — a trial
    # profile replaces the strategy wholesale, and
    # ``Task.apply_realized_feedback`` clears the flag on the first realized
    # interval. Journaled as ``static_prior`` in admission/solver events so
    # plans built on untested estimates are auditable (SAT-X005).
    static_prior: bool = field(default=False)
    # Persistent profile-cache fingerprint for this (task, technique, size)
    # grid point (``utils/profile_cache.py``) — lets the orchestrator write
    # realized measurements back to the cache.
    cache_key: Optional[str] = field(default=None)
    # Fraction of a steady-state batch spent on HOST work (staging, pinned
    # host transfers) rather than device compute, in [0, 1]. Measured by the
    # trial runner (``SPMDTechnique._measure``); the solver's co-location
    # term uses it to predict which job pairs can fill each other's bubbles
    # when their windows interleave on a shared block. 0.0 (the default, and
    # what pre-existing cache entries report) predicts no overlap win, so a
    # strategy without a measurement is never co-scheduled.
    host_fraction: float = field(default=0.0)
    # Seconds per LOCKSTEP step of a fused stack this task belongs to —
    # every member of the stack advances one batch per lockstep step — as
    # measured by the trial runner's fused-group profile
    # (``trial_runner/evaluator.profile_fused_group``). None means the fused
    # program was never profiled at this (task, size) point, and the solver
    # must not fuse on guesswork: fusion is priced strictly on measured cost
    # (``solver/milp.solve``), exactly like every other grid point. Updated
    # by realized fused-interval feedback (EWMA, the
    # ``apply_realized_feedback`` pattern) via the engine's fused launcher.
    fused_per_batch_time: Optional[float] = field(default=None)
    # Analytic schedule-bubble fraction of a steady-state step, in [0, 1):
    # device-idle time (pipeline warmup/cooldown) a co-scheduled partner's
    # device windows could fill. Recomputed from ``params`` by every install
    # path (``BaseTechnique.config_bubble_fraction``) rather than measured —
    # GPipe pays (S-1)/(M+S-1), 1F1B only (S-1)/(M+2(S-1)), and the solver's
    # co-location term adds it to ``host_fraction`` so a 1F1B job is priced
    # as the worse gap-filler partner it is.
    bubble_fraction: float = field(default=0.0)

    def __post_init__(self) -> None:
        if self.apportionment < 1:
            raise ValueError("apportionment must be a positive chip count")

    @property
    def feasible(self) -> bool:
        """Reference treats params=None as an un-runnable strategy
        (``PerformanceEvaluator.py:96-99,110``)."""
        return self.params is not None and self.executor is not None

    @property
    def technique(self) -> Optional[Techniques]:
        """Which built-in technique family this strategy uses (None for
        user-defined plugins) — plan introspection, e.g. metrics/logs."""
        return getattr(self.executor, "technique", None)
