"""Numeric-health sentinel: on-device loss screening for fused intervals.

The async step pipeline (``parallel/spmd_base.py``) already carries every
step's loss on-device — the old finalization read back only the LAST scalar
and let a NaN at step 3 of a 64-step interval silently poison the published
checkpoint. The sentinel folds the interval's full per-step loss vector
through one jitted ``lax.scan`` **on the device** (``jnp.isfinite`` plus an
EWMA spike score), producing a fixed-shape 6-float report; the single host
readback the interval already paid now transfers that report instead of the
bare scalar. Detection therefore costs one tiny fused program per interval
and ZERO additional host syncs on the hot path — and the report's last slot
is the interval's final loss, bit-identical to what the bare readback
returned, so enabling the sentinel never perturbs the loss trajectory.

Fault taxonomy (the ``cause`` on :class:`NumericFaultError`):

- ``nonfinite`` — any step's loss is NaN/Inf (always checked);
- ``loss_spike`` — a finite loss exceeded ``spike_factor x`` the running
  EWMA after ``warmup_steps`` folded steps (off by default:
  ``spike_factor <= 0`` disables the score — divergence thresholds are
  workload policy, non-finiteness is not).

The EWMA carry ``[ewma, steps]`` is persisted host-side between intervals
on ``task._sentinel_carry`` and only advanced when the interval was
healthy: a faulted interval's carry is discarded with the rest of its
state, so the retry folds from exactly the pre-fault statistics.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

#: ``cause`` values (also the fold's on-device kind codes 1 / 2).
CAUSE_NONFINITE = "nonfinite"
CAUSE_SPIKE = "loss_spike"
_KIND_TO_CAUSE = {1: CAUSE_NONFINITE, 2: CAUSE_SPIKE}

#: Report vector layout (shape ``(6,)`` float32).
REP_EWMA = 0          # post-interval EWMA (healthy steps only)
REP_STEPS = 1         # total healthy steps folded, across intervals
REP_BAD_COUNT = 2     # bad steps in THIS interval
REP_FIRST_BAD = 3     # interval-relative offset of the first bad step (-1)
REP_FIRST_KIND = 4    # kind code of the first bad step (0 = none)
REP_LAST_LOSS = 5     # the interval's final loss (the old bare readback)


class NumericFaultError(RuntimeError):
    """A window's carried loss failed the sentinel's numeric screen.

    Raised from the technique's interval finalization BEFORE the
    end-of-interval checkpoint write and live-state republish — a faulted
    interval never becomes durable state, so the last published checkpoint
    stays the rollback target. Structured fields drive the guardian's
    per-cause policy and the quarantine skip-list.
    """

    def __init__(
        self,
        job: str,
        window: int,
        cause: str,
        step: Optional[int] = None,
        loss: Optional[float] = None,
        batch_indices: Tuple[int, ...] = (),
        bad_count: int = 0,
    ):
        self.job = job
        self.window = window
        self.cause = cause
        self.step = step
        self.loss = loss
        self.batch_indices = tuple(int(i) for i in batch_indices)
        self.bad_count = int(bad_count)
        super().__init__(
            f"numeric fault in job {job}: {cause} at window {window} "
            f"(interval step {step}, loss {loss!r}, "
            f"{self.bad_count} bad step(s), "
            f"dataset batches {list(self.batch_indices)})"
        )


@dataclass(frozen=True)
class SentinelConfig:
    """Sentinel policy (resolved once per interval).

    ``spike_factor <= 0`` disables the EWMA spike score; non-finiteness is
    always screened while ``enabled``.
    """

    enabled: bool = True
    spike_factor: float = 0.0
    ewma_alpha: float = 0.3
    warmup_steps: int = 8


_override: Optional[SentinelConfig] = None


def set_config(cfg: Optional[SentinelConfig]) -> None:
    """Process-wide override (tests / campaigns); ``None`` restores the
    default policy."""
    global _override
    _override = cfg


def get_config() -> SentinelConfig:
    return _override if _override is not None else SentinelConfig()


def carry_init() -> np.ndarray:
    """Fresh EWMA carry ``[ewma, steps]``."""
    return np.zeros(2, dtype=np.float32)


@functools.lru_cache(maxsize=32)
def _fold_fn(spike_factor: float, alpha: float, warmup: int) -> Callable:
    """The jitted per-config fold. Cached per policy tuple; jax's own shape
    cache handles the per-``n`` retraces (one per distinct interval batch
    budget — the same cardinality the fused window programs already have)."""
    import jax
    import jax.numpy as jnp

    def saturn_sentinel_fold(carry, losses):  # the name a profile shows
        losses = losses.astype(jnp.float32)

        def step(c, x):
            ewma, steps, bad, first_off, first_kind, idx = c
            finite = jnp.isfinite(x)
            if spike_factor > 0.0:
                spike = (
                    finite
                    & (steps >= float(warmup))
                    & (ewma > 0.0)
                    & (x > spike_factor * ewma)
                )
            else:
                spike = jnp.zeros((), dtype=bool)
            kind = jnp.where(
                ~finite, jnp.float32(1.0),
                jnp.where(spike, jnp.float32(2.0), jnp.float32(0.0)),
            )
            is_bad = kind > 0.0
            is_first = jnp.logical_and(bad == 0.0, is_bad)
            first_off = jnp.where(is_first, idx, first_off)
            first_kind = jnp.where(is_first, kind, first_kind)
            bad = bad + jnp.where(is_bad, 1.0, 0.0)
            # Only healthy steps advance the running statistics: a bad step
            # must not drag the EWMA toward the value that tripped it.
            healthy = jnp.logical_not(is_bad)
            ewma = jnp.where(
                healthy,
                jnp.where(steps > 0.0, alpha * x + (1.0 - alpha) * ewma, x),
                ewma,
            )
            steps = steps + jnp.where(healthy, 1.0, 0.0)
            return (ewma, steps, bad, first_off, first_kind, idx + 1.0), None

        init = (
            carry[0], carry[1],
            jnp.float32(0.0), jnp.float32(-1.0), jnp.float32(0.0),
            jnp.float32(0.0),
        )
        (ewma, steps, bad, first_off, first_kind, _), _ = jax.lax.scan(
            step, init, losses
        )
        return jnp.stack(
            [ewma, steps, bad, first_off, first_kind, losses[-1]]
        )

    return jax.jit(saturn_sentinel_fold)


def fold(carry: Any, losses: Any, cfg: SentinelConfig):
    """Run the on-device fold; returns the (6,) report as a device array.
    ``carry`` is the (2,) host/device carry, ``losses`` the interval's
    flattened per-step loss vector."""
    return _fold_fn(
        float(cfg.spike_factor), float(cfg.ewma_alpha), int(cfg.warmup_steps)
    )(carry, losses)


def inspect(report: np.ndarray) -> Optional[Tuple[str, int, int]]:
    """Host-side report decode: ``(cause, first_bad_offset, bad_count)`` on
    a fault, ``None`` when the interval is numerically healthy."""
    bad = int(report[REP_BAD_COUNT])
    if bad <= 0:
        return None
    cause = _KIND_TO_CAUSE.get(int(report[REP_FIRST_KIND]), CAUSE_NONFINITE)
    return cause, int(report[REP_FIRST_BAD]), bad


def poison_overrides(
    plan: Dict[str, Any],
    n: int,
    dataset_index_of: Callable[[int], int],
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Decode a fault injector's numeric plan into ``(positions, values)``
    to overwrite in the interval's OBSERVED loss vector.

    ``plan["steps"]`` keys interval-relative step offsets; ``plan["batches"]``
    keys dataset indices (persistent poisoning — the fault follows the batch
    through rollbacks and cursor moves, which is what makes the quarantine
    path deterministic). Injection happens at the observation level only:
    the train state itself is never corrupted, so the post-rollback retry's
    trajectory is genuinely the fault-free one.
    """
    if not plan:
        return None
    steps = plan.get("steps") or {}
    batches = plan.get("batches") or {}
    pos, vals = [], []
    for j in range(int(n)):
        v = steps.get(j)
        if v is None and batches:
            v = batches.get(dataset_index_of(j))
        if v is not None:
            pos.append(j)
            vals.append(v)
    if not pos:
        return None
    return (
        np.asarray(pos, dtype=np.int32),
        np.asarray(vals, dtype=np.float32),
    )
