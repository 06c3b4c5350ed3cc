"""Trace loading + fidelity comparison: journals from real runs feed the twin.

``load_trace`` folds a durability journal (written by a real
``SaturnService`` run with ``durability_dir`` set)
into an arrival trace plus the run's *reference distributions*: admission
verdict mix and, when a metrics file rode along, ``solver_tier`` shares.
Multi-incarnation journals are handled by
``durability.journal.replay_reconciled`` — the stable ``(seq,
incarnation)`` merge — so a service that crashed and restarted mid-run
still replays as one valid trace.

``fidelity_compare`` is the calibrated-instrument check: the twin replays
the trace and its tier shares / verdict mix / makespan must agree with
journaled reality within the documented band (see ``DEFAULT_BAND`` — the
values asserted by ``tests/test_twin.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from saturn_tpu.durability import journal as jmod
from saturn_tpu.utils.metrics import read_events

#: The documented fidelity band (docs/architecture.md, round 22):
#: - per-tier solver share absolute delta <= 0.25 (tier choice is a race
#:   against real CPU time on both sides; shares, not sequences, must agree)
#: - admission verdict share absolute delta <= 0.10 (the decision logic is
#:   the identical code; only arrival interleaving differs)
#: - makespan ratio within [0.3, 3.0] (the twin quantizes work to interval
#:   boundaries; the real run pays wire + scheduling wall time)
DEFAULT_BAND = {
    "tier_share_delta": 0.25,
    "verdict_share_delta": 0.10,
    "makespan_ratio": (0.3, 3.0),
}


@dataclass(frozen=True)
class TraceJob:
    """One replayable submission from a journaled run."""

    job_id: str
    name: str
    at_s: float                      # arrival offset from the trace start
    priority: float = 0.0
    deadline_s: Optional[float] = None
    total_batches: int = 1
    spec: Optional[dict] = None
    dedup_key: Optional[str] = None


@dataclass
class TwinTrace:
    """A journal folded into twin-consumable form."""

    jobs: List[TraceJob] = field(default_factory=list)
    admission_mix: Dict[str, int] = field(default_factory=dict)
    incarnations: int = 1
    span_s: float = 0.0              # first..last submission offset

    @property
    def verdict_shares(self) -> Dict[str, float]:
        total = sum(self.admission_mix.values())
        if total == 0:
            return {}
        return {k: v / total for k, v in sorted(self.admission_mix.items())}


def load_trace(durability_dir: str) -> TwinTrace:
    """Fold a journal directory into a :class:`TwinTrace`.

    Arrival offsets come from each ``job_submitted`` record's commit
    timestamp relative to the first one — the journaled submit is fsync'd
    before the client's ACK, so it is an honest arrival-order clock.
    """
    trace = TwinTrace()
    first_ts: Optional[float] = None
    last_ts: float = 0.0
    segments_opened = 0
    for rec in jmod.replay_reconciled(durability_dir):
        kind, d = rec.get("kind"), rec.get("data", {})
        if kind == "segment_open":
            segments_opened += 1
            continue
        if kind == "recovery":
            trace.incarnations += 1
            continue
        if kind == "job_submitted":
            ts = float(rec.get("ts", 0.0))
            if first_ts is None:
                first_ts = ts
            last_ts = ts
            trace.jobs.append(TraceJob(
                job_id=d.get("job", ""),
                name=d["task"],
                at_s=ts - first_ts,
                priority=float(d.get("priority") or 0.0),
                deadline_s=d.get("deadline_s"),
                total_batches=int(d.get("total_batches") or 1),
                spec=d.get("spec"),
                dedup_key=d.get("dedup_key"),
            ))
        elif kind == "job_admission":
            dec = d.get("decision", "unknown")
            trace.admission_mix[dec] = trace.admission_mix.get(dec, 0) + 1
    if first_ts is not None:
        trace.span_s = last_ts - first_ts
    return trace


def tier_shares(metrics_path: str) -> Dict[str, float]:
    """Per-tier share of ``solver_tier`` events in a metrics file (keys are
    tier numbers as strings — JSON-stable)."""
    counts: Dict[str, int] = {}
    for e in read_events(metrics_path, kind="solver_tier"):
        t = str(e.get("tier"))
        counts[t] = counts.get(t, 0) + 1
    total = sum(counts.values())
    if total == 0:
        return {}
    return {t: n / total for t, n in sorted(counts.items())}


def _share_deltas(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {
        k: round(abs(a.get(k, 0.0) - b.get(k, 0.0)), 6)
        for k in sorted(set(a) | set(b))
    }


def fidelity_compare(twin: dict, real: dict,
                     band: Optional[dict] = None) -> dict:
    """Compare a twin campaign against journaled reality.

    Both sides are dicts with ``tier_shares`` (str tier -> share),
    ``verdict_shares`` (decision -> share) and ``makespan_s``. Returns the
    per-key deltas, the band they were checked against, and ``within_band``.
    Empty distributions on *both* sides compare equal (delta 0); one-sided
    emptiness shows up as the full share delta, as it should.
    """
    band = dict(DEFAULT_BAND, **(band or {}))
    tier_deltas = _share_deltas(
        twin.get("tier_shares", {}), real.get("tier_shares", {})
    )
    verdict_deltas = _share_deltas(
        twin.get("verdict_shares", {}), real.get("verdict_shares", {})
    )
    tm, rm = twin.get("makespan_s", 0.0), real.get("makespan_s", 0.0)
    ratio = (tm / rm) if rm > 0 else (1.0 if tm == 0 else float("inf"))
    lo, hi = band["makespan_ratio"]
    ok = (
        all(dv <= band["tier_share_delta"] for dv in tier_deltas.values())
        and all(dv <= band["verdict_share_delta"]
                for dv in verdict_deltas.values())
        and lo <= ratio <= hi
    )
    return {
        "tier_share_deltas": tier_deltas,
        "verdict_share_deltas": verdict_deltas,
        "makespan_ratio": round(ratio, 4),
        "band": {
            "tier_share_delta": band["tier_share_delta"],
            "verdict_share_delta": band["verdict_share_delta"],
            "makespan_ratio": list(band["makespan_ratio"]),
        },
        "within_band": ok,
    }
