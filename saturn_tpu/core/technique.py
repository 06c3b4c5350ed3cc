"""BaseTechnique: the two-method plugin contract every parallelism executor obeys.

Reference: ``saturn/core/executors/Technique.py:24-45``. The entire extension
surface of the system is this ABC: a technique must be able to (a) *autotune and
profile* itself on a given sub-mesh (``search``) and (b) *run a bounded number of
batches* on a given sub-mesh, resuming from and writing checkpoints
(``execute``). Everything else (solver, orchestrator, trial runner) only ever
talks to these two methods.

TPU-native deltas from the reference contract:

- ``devices`` is a list of ``jax.Device`` forming a contiguous ICI sub-mesh,
  not a list of integer GPU ids (reference passed ``[0..g-1]``,
  ``executor.py:82-83``).
- ``search`` must exclude XLA compile time from the reported per-batch time
  (the reference timed batch 2-of-2 to skip warmup, ``FSDP.py:140-149``; under
  jit we compile once, sync, then time steady-state steps).
- Techniques should use XLA compile-time memory analysis
  (``compiled.memory_analysis()``) to reject configurations that won't fit in
  HBM instead of try/except OOM probing (reference ``Spilled.py:68-87``).
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional, Sequence, Tuple


class InfeasibleConfig(ValueError):
    """This (config, sub-mesh) pair cannot exist for this task — a batch the
    data axis does not divide, a stage count the block does not divide.

    A verdict, not a fault: ``SPMDTechnique.search`` skips the config without
    counting it among the errors that ``search()`` reports. Any other
    exception from a config is counted there."""


class BaseTechnique(abc.ABC):
    """Abstract parallelism technique ("UDP" in the reference's terms)."""

    #: Optional friendly name used when registering into the library.
    name: str = "base"

    #: Which built-in technique family this is (``Techniques`` enum member),
    #: None for user-defined plugins. Consumed by ``library.retrieve`` (enum
    #: lookup) and ``Strategy.technique`` (plan introspection) — the reference
    #: declared its enum but nothing ever read it (``Strategy.py:25-34``).
    technique = None  # type: ignore[assignment]  # Optional[Techniques]

    #: Declares that this technique's per-chip memory footprint is
    #: non-increasing in sub-mesh size (smaller block => per-chip memory the
    #: same or strictly higher). True for every sharding-based technique:
    #: replicated state is constant per chip while sharded state shrinks as
    #: the block grows. The trial runner uses it to propagate XLA memory
    #: infeasibility monotonically — a memory rejection at size ``g`` skips
    #: the trials at every smaller size instead of compiling them to fail.
    #: Techniques additionally expose the rejection reason via
    #: ``search_report`` (see ``SPMDTechnique``); without a report claiming
    #: the rejection was memory-bound, nothing is propagated (a batch
    #: divisibility failure at a LARGE size says nothing about small ones).
    memory_monotone: bool = False

    @abc.abstractmethod
    def execute(
        self,
        task: Any,
        devices: Sequence[Any],
        tid: int,
        override_batch_count: Optional[int] = None,
    ) -> None:
        """Train ``task`` on ``devices`` for ``override_batch_count`` batches.

        Must resume from the task's checkpoint if one exists and write a full
        train-state checkpoint (params AND optimizer state — fixing the
        reference's dropped-optimizer wart, ``FSDP.py:220``) when the batch
        budget is exhausted. Reference contract: ``Technique.py:31-34``.
        """

    @abc.abstractmethod
    def search(
        self,
        task: Any,
        devices: Sequence[Any],
        tid: int,
    ) -> Tuple[Optional[Dict[str, Any]], Optional[float]]:
        """Autotune internal knobs on ``devices``; return ``(params, per_batch_time)``.

        ``params`` is the technique's chosen configuration (e.g. remat on/off,
        microbatch count); ``(None, None)`` means the technique cannot run this
        task on this sub-mesh. Reference contract: ``Technique.py:42-45``.
        """
