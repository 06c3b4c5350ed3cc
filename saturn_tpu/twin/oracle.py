"""Static cost/memory oracle: priors replace execution, no chips involved.

The twin cannot run trials, so where the real service gets per-batch times
from profiling sweeps (and shardflow/memlens fill cold-start gaps), the
twin gets *everything* from a seeded analytic model in the same shape those
analyzers emit:

- **cost**: per-family Amdahl + communication roofline,
  ``pbt(g) = serial + parallel/g + comm * log2(g)``, with a DCN penalty on
  the comm term once a block must span slices — the shardflow-style scaling
  curve, deterministic from ``(seed, family)``.
- **memory**: ``peak(g) = 3 * model_bytes / g + activation_bytes``
  (params+grads+optimizer sharded, activations replicated); a size whose
  projected peak overflows the virtual chips' HBM gets **no strategy** —
  the memlens-style residency gate, applied before admission ever sees the
  task.

Strategies carry ``static_prior=True`` — exactly the flag shardflow-admitted
jobs carry in production — so twin plans are auditable as prior-built, and
realized (simulated) feedback clears the flag through the same
``apply_realized_feedback`` path the orchestrator uses for real tasks.

Nothing here imports jax: :class:`VirtualTechnique` is a dispatch-surface
stub that must never execute (the VirtualEngine advances the clock
instead), and ``technique_names=["twin-virtual"]`` keeps the admission
controller's built-in roster empty so no sweep is attempted.
"""

from __future__ import annotations

import math
import random
import zlib
from typing import Dict, List, Optional, Sequence

from saturn_tpu.core.strategy import Strategy

#: Matches ``core.task.Task.EWMA_ALPHA`` — realized feedback folds the same.
EWMA_ALPHA = 0.7


class VirtualTechnique:
    """Executor stub: satisfies ``Strategy.feasible`` (executor is not None)
    and identity probes; raises if anything tries to actually run it."""

    name = "twin-virtual"
    technique = None

    def execute(self, *a, **k):
        raise RuntimeError(
            "VirtualTechnique.execute called — the twin must route all "
            "execution through VirtualEngine, never a real dispatch"
        )

    def search(self, *a, **k):
        raise RuntimeError("VirtualTechnique has no profiling sweep")


class TwinTask:
    """Duck-typed Task: everything admission/solver/replanner/engine-forecast
    touch, nothing that needs a runtime. Mirrors the real Task's realized-
    feedback surface (``note_realized_per_batch`` + no-arg
    ``apply_realized_feedback``) so ``orchestrator.fold_realized_feedback``
    works on it unmodified."""

    EWMA_ALPHA = EWMA_ALPHA

    def __init__(self, name: str, total_batches: int, family: int = 0,
                 hints: Optional[dict] = None):
        self.name = name
        self.total_batches = int(total_batches)
        self.current_batch = 0
        self.epoch_length = 1000
        self.family = family
        self.hints = dict(hints or {})
        self.chip_range = None
        self.strategies: Dict[int, Strategy] = {}
        self.selected_strategy: Optional[Strategy] = None
        self._pending_realized = None

    def feasible_strategies(self) -> Dict[int, Strategy]:
        return {g: s for g, s in self.strategies.items() if s.feasible}

    def select_strategy(self, g: int) -> None:
        self.selected_strategy = self.strategies[g]

    def reconfigure(self, n: int) -> None:
        self.current_batch = (self.current_batch + n) % self.epoch_length

    # ------------------------------------------------- realized feedback
    def note_realized_per_batch(self, per_batch_s: float) -> None:
        if self.selected_strategy is not None and per_batch_s > 0.0:
            self._pending_realized = (self.selected_strategy, per_batch_s)

    def apply_realized_feedback(self):
        pending = self._pending_realized
        self._pending_realized = None
        if pending is None:
            return None
        strat, realized = pending
        if not strat.feasible:
            return None
        old = strat.per_batch_time
        strat.per_batch_time = (
            self.EWMA_ALPHA * realized + (1.0 - self.EWMA_ALPHA) * old
            if old > 0.0 else realized
        )
        strat.runtime = strat.per_batch_time * self.total_batches
        # Simulated evidence landed: the prior did its cold-start job.
        strat.static_prior = False
        strat.interpolated = False
        return (old, strat.per_batch_time)


def family_of(name: str, n_families: int) -> int:
    """Stable task-name → family hash (CRC32, not ``hash()`` — the latter is
    salted per process and would break cross-run determinism)."""
    return zlib.crc32(name.encode("utf-8")) % max(1, n_families)


class StaticOracle:
    """Seeded per-family cost/memory model + task factory.

    ``flat_per_batch_s`` switches to trace-replay mode: every strategy gets
    that constant per-batch time with ``static_prior=False`` — mirroring
    a live run's pre-profiled tasks, so a replayed trace is costed the way
    the real run was.
    """

    def __init__(self, fleet, seed: int = 0, n_families: int = 16,
                 flat_per_batch_s: Optional[float] = None,
                 dcn_penalty: float = 4.0):
        self.fleet = fleet
        self.seed = seed
        self.n_families = max(1, n_families)
        self.flat_per_batch_s = flat_per_batch_s
        self.dcn_penalty = dcn_penalty
        self.technique = VirtualTechnique()
        self._profiles: Dict[int, dict] = {}

    # ------------------------------------------------------------ the model
    def profile(self, family: int) -> dict:
        prof = self._profiles.get(family)
        if prof is None:
            rng = random.Random((self.seed << 20) ^ (family * 2654435761 % (1 << 31)))
            prof = {
                "serial_s": rng.uniform(0.02, 0.10),
                "parallel_s": rng.uniform(0.5, 4.0),
                "comm_s": rng.uniform(0.002, 0.012),
                "model_bytes": int(rng.uniform(0.5, 8.0) * (1 << 30)),
                "activation_bytes": int(rng.uniform(0.1, 1.0) * (1 << 30)),
            }
            self._profiles[family] = prof
        return prof

    def per_batch_time(self, family: int, g: int) -> float:
        if self.flat_per_batch_s is not None:
            return self.flat_per_batch_s
        p = self.profile(family)
        comm = p["comm_s"] * math.log2(g) if g > 1 else 0.0
        if g > self.fleet.chips:
            comm *= self.dcn_penalty  # block spans slices: DCN, not ICI
        return p["serial_s"] + p["parallel_s"] / g + comm

    def peak_bytes(self, family: int, g: int) -> int:
        p = self.profile(family)
        return 3 * p["model_bytes"] // g + p["activation_bytes"]

    def fits(self, family: int, g: int) -> bool:
        if self.flat_per_batch_s is not None:
            return True  # trace mode: the real run already admitted these
        hbm = min(d.hbm_bytes for d in self.fleet.devices)
        return self.peak_bytes(family, g) <= hbm

    # --------------------------------------------------------- task factory
    def candidate_sizes(self, capacity: int) -> List[int]:
        out, g = [], 1
        while g <= capacity:
            out.append(g)
            g *= 2
        return out

    def strategize(self, task: TwinTask,
                   sizes: Optional[Sequence[int]] = None) -> TwinTask:
        """Fill ``task.strategies`` with prior-built strategies at every
        HBM-feasible size (the memory gate: an OOM-projected size simply
        does not exist as an option)."""
        capacity = self.fleet.topology().capacity
        for g in (sizes or self.candidate_sizes(capacity)):
            g = int(g)
            if g < 1 or g > capacity or not self.fits(task.family, g):
                continue
            pbt = self.per_batch_time(task.family, g)
            prior = self.flat_per_batch_s is None
            task.strategies[g] = Strategy(
                self.technique, g, {}, pbt * task.total_batches, pbt,
                static_prior=prior, interpolated=prior,
            )
        return task

    def make_task(self, name: str, total_batches: int,
                  family: Optional[int] = None,
                  sizes: Optional[Sequence[int]] = None) -> TwinTask:
        if family is None:
            family = family_of(name, self.n_families)
        return self.strategize(
            TwinTask(name, total_batches, family=family), sizes=sizes
        )

    def task_provider(self):
        """``task_provider(payload) -> task`` closure in the gateway /
        crash-recovery rebuild contract (``service.server.task_provider``):
        the payload is the journaled submission spec."""

        def provide(payload: dict) -> TwinTask:
            spec = payload.get("spec") or {}
            return self.make_task(
                payload["task"],
                total_batches=int(payload.get("remaining_batches") or 1),
                family=spec.get("family"),
                sizes=spec.get("sizes"),
            )

        return provide
