"""Shardflow pass 3: the cold-start solver prior, and its SAT-X005 audit.

Before the trial runner has spent any chip time on a (task, technique,
size) grid point, the only cost signal available used to be the dummy
``DUMMY_RUNTIME`` sentinel — ADMIT/DEFER and the first plan were blind to
sharding. This module turns the shardflow communication ledger into a
**static per-batch-time prior** (Piper's programmable-cost-model framing,
arxiv 2606.11169):

    t_step  =  flops / (chips x peak x MFU)  +  wire_bytes / bandwidth

— roofline compute plus un-overlapped communication (pessimistic on
purpose: a prior that flatters communication-heavy layouts would admit
jobs the mesh cannot actually serve).

Strategies synthesized here are marked ``static_prior=True`` and are
superseded the moment real evidence lands: a trial profile overwrites
them wholesale, and ``Task.apply_realized_feedback`` clears the flag on
the first realized interval. :func:`audit_task` then closes the loop —
SAT-X005 flags any grid point whose static estimate disagreed with the
eventually-measured runtime by more than ``AUDIT_TOLERANCE``, which is
how a drifting cost model gets caught instead of silently steering
admission.

The hardware constants are not measurements — the prior's job is
*relative ordering* across techniques and sizes, and SAT-X005 polices its
absolute error. The MFU target and the overlap factors are deployment knobs
(``SATURN_TPU_PRIOR_MFU``, ``SATURN_TPU_PRIOR_OVERLAP_<OP>``).
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, List, Optional, Sequence

from saturn_tpu.analysis.diagnostics import Diagnostic, make

from saturn_tpu.analysis.shardflow.interp import CommLedger, interpret

log = logging.getLogger("saturn_tpu")

#: |static - profiled| / profiled above which SAT-X005 fires.
AUDIT_TOLERANCE = 0.35

#: The prior's roofline constants (see the module docstring).
PEAK_FLOPS = 100e12     # bf16-class chip
ICI_BYTES_S = 4.5e10    # per-link ICI
DCN_BYTES_S = 2.5e9     # per-host DCN

_ENV_MFU = "SATURN_TPU_PRIOR_MFU"
_ENV_OVERLAP_PREFIX = "SATURN_TPU_PRIOR_OVERLAP_"

#: Per-op-class fraction of wire time the overlapped lowering hides under
#: compute (``{"overlap": True}`` grid points: double-buffered ppermute
#: hops in ring/pipeline, collective-matmul / ZeRO-3 prefetch gathers).
#: Static seeds, deliberately conservative; :func:`calibrate_overlap_factors`
#: moves them from the SAT-X005 audit stream and
#: ``SATURN_TPU_PRIOR_OVERLAP_<OP>`` pins them per deployment. Serial grid
#: points keep the fully-pessimistic un-overlapped pricing.
DEFAULT_OVERLAP_FACTORS: Dict[str, float] = {
    "ppermute": 0.7,        # neighbor hop rides under the chunk's compute
    "all_gather": 0.6,      # layer-ahead prefetch / chunked partial products
    "reduce_scatter": 0.3,  # grad scatter partially hides behind backward
    "all_reduce": 0.0,      # grad psum gates the optimizer: critical path
    "all_to_all": 0.0,      # MoE dispatch has no overlapped lowering yet
}

# Calibrated deltas layered over the defaults (process-local; the factor
# set is stamped into the profile-cache fingerprint, so recalibration
# invalidates stale entries instead of silently repricing them).
_calibrated_factors: Dict[str, float] = {}


def _envf(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def overlap_factors() -> Dict[str, float]:
    """Active per-op-class overlap factor set: defaults, then calibration,
    then env pins — each clamped to [0, 1]."""
    out = dict(DEFAULT_OVERLAP_FACTORS)
    out.update(_calibrated_factors)
    for op in out:
        out[op] = min(
            max(_envf(_ENV_OVERLAP_PREFIX + op.upper(), out[op]), 0.0), 1.0
        )
    return out


def overlap_factor_signature() -> str:
    """Stable signature of the active factor set for cache fingerprints."""
    f = overlap_factors()
    return ",".join(f"{k}={f[k]:.4f}" for k in sorted(f))


def hardware_model() -> Dict[str, float]:
    """Roofline constants for the prior (per chip / per link)."""
    return {
        "peak_flops": PEAK_FLOPS,
        "ici_bytes_s": ICI_BYTES_S,
        "dcn_bytes_s": DCN_BYTES_S,
        "mfu": _envf(_ENV_MFU, 0.45),             # the repo's MFU target
    }


def estimate_step_seconds(
    ledger: CommLedger, size: int,
    crossing: Optional[frozenset] = None,
    hw: Optional[Dict[str, float]] = None,
    overlap: bool = False,
    factors: Optional[Dict[str, float]] = None,
) -> float:
    """Static per-batch seconds from one ledger: roofline compute + wire
    time, DCN-priced for axes in ``crossing``. Serial (default) prices every
    collective un-overlapped; ``overlap=True`` discounts each op class by
    the active :func:`overlap_factors` — the pricing for ``overlap`` grid
    points, never for the serial lowering."""
    hw = hw or hardware_model()
    compute = ledger.flops / max(size, 1) / (hw["peak_flops"] * hw["mfu"])
    f = (factors if factors is not None else overlap_factors()) if overlap \
        else {}
    comm = 0.0
    cross = crossing or frozenset()
    for rec in ledger.records:
        bw = hw["dcn_bytes_s"] if set(rec.axes) & cross else hw["ici_bytes_s"]
        comm += (rec.wire_bytes * rec.count / bw) * (
            1.0 - f.get(rec.op, 0.0)
        )
    return max(compute + comm, 1e-9)


def comm_seconds_by_op(
    ledger: CommLedger, crossing: Optional[frozenset] = None,
    hw: Optional[Dict[str, float]] = None,
) -> Dict[str, float]:
    """Un-overlapped wire seconds per op class — the breakdown the
    calibrator needs to attribute a measured overlap win to op classes."""
    hw = hw or hardware_model()
    cross = crossing or frozenset()
    out: Dict[str, float] = {}
    for rec in ledger.records:
        bw = hw["dcn_bytes_s"] if set(rec.axes) & cross else hw["ici_bytes_s"]
        out[rec.op] = out.get(rec.op, 0.0) + rec.wire_bytes * rec.count / bw
    return out


def _resolve_techniques(technique_names: Optional[List[str]]) -> Dict[str, Any]:
    from saturn_tpu import library as lib

    if not lib.registered_names():
        lib.register_default_library()
    names = (technique_names if technique_names is not None
             else lib.registered_names())
    out: Dict[str, Any] = {}
    for n in names:
        cls = lib.retrieve(n)
        tech = cls() if isinstance(cls, type) else cls
        out[getattr(tech, "name", str(n))] = tech
    return out


def synthesize_strategies(
    task: Any,
    topology: Any,
    technique_names: Optional[List[str]] = None,
    sizes: Optional[Sequence[int]] = None,
    max_configs: int = 3,
    slice_size: Optional[int] = None,
) -> List[int]:
    """Fill ``task.strategies`` with ``static_prior=True`` entries for every
    never-profiled size a technique can trace — zero trials, zero compiles.

    For each (technique, size) the prior picks the candidate config with
    the best static estimate (up to ``max_configs`` traced per point), and
    across techniques the fastest estimate wins the grid point — the same
    per-size argmin the trial runner's ``install`` applies to measured
    trials. Returns the sizes synthesized. Existing feasible strategies
    (measured, cached or already-synthesized) are never overwritten.
    """
    from saturn_tpu.analysis.shardflow.passes import crossing_axes
    from saturn_tpu.core.strategy import Strategy
    from saturn_tpu.utils import profile_cache as pcache

    try:
        techs = _resolve_techniques(technique_names)
    except Exception as e:
        log.warning("shardflow prior: technique resolution failed: %r", e)
        return []
    task_sig = pcache.task_signature(task)
    topo_sig = pcache.topology_signature(topology)
    ss = slice_size if slice_size is not None else getattr(
        topology, "slice_size", None)

    chip_range = getattr(task, "chip_range", None)
    grid_sizes = [
        g for g in (sizes if sizes is not None else topology.valid_sizes())
        if chip_range is None or g in chip_range
    ]
    added: List[int] = []
    for g in grid_sizes:
        if g in task.feasible_strategies():
            continue
        try:
            devices = topology.block_devices(topology.blocks(g)[0])
        except Exception:
            continue
        best: Optional[Strategy] = None
        best_t = float("inf")
        for name, tech in sorted(techs.items()):
            if not hasattr(tech, "trace_step"):
                continue
            try:
                grid = tech.candidate_configs(task, g)
            except Exception:
                continue
            for config in grid[:max_configs]:
                try:
                    traced = tech.trace_step(task, devices, config)
                    ledger = interpret(traced)
                except Exception as e:
                    log.debug(
                        "shardflow prior: %s@%d %r untraceable: %r",
                        name, g, config, e,
                    )
                    continue
                cross = crossing_axes(traced["mesh_axes"], ss)
                overlapped = bool(config.get("overlap", False))
                t = estimate_step_seconds(
                    ledger, g, crossing=cross, overlap=overlapped
                )
                if t < best_t:
                    best_t = t
                    # Analytic schedule bubble (pipeline GPipe/1F1B
                    # warmup-cooldown): like the runtime prior itself it
                    # needs no trial, so cold-started strategies price
                    # co-location the same way measured ones do.
                    bubble = 0.0
                    bf = getattr(tech, "config_bubble_fraction", None)
                    if callable(bf):
                        try:
                            bubble = min(max(float(bf(config)), 0.0), 1.0)
                        except Exception:
                            bubble = 0.0
                    best = Strategy(
                        executor=tech,
                        apportionment=g,
                        params=dict(config),
                        runtime=t * max(task.total_batches, 0),
                        per_batch_time=t,
                        static_prior=True,
                        cache_key=pcache.fingerprint(
                            task_sig, name, g, topo_sig
                        ),
                        bubble_fraction=bubble,
                    )
                    best._static_overlap = overlapped
                    best._static_compute_s = estimate_step_seconds(
                        ledger, g, crossing=cross,
                        factors={}, overlap=False,
                    ) - sum(comm_seconds_by_op(ledger, crossing=cross).values())
                    best._static_comm_by_op = comm_seconds_by_op(
                        ledger, crossing=cross
                    )
        if best is not None:
            best._static_prior_estimate = best_t
            task.strategies[g] = best
            added.append(g)
    if added:
        log.info(
            "shardflow prior: synthesized %d static strategy(s) for %s "
            "at sizes %s", len(added), getattr(task, "name", "?"), added,
        )
    return added


# ------------------------------------------------------------ SAT-X005 audit
def audit_point(
    static_s: float, profiled_s: float, technique: str, size: int,
    tolerance: float = AUDIT_TOLERANCE,
) -> Optional[Diagnostic]:
    """SAT-X005 for one grid point, when a profile exists."""
    if profiled_s <= 0.0 or static_s <= 0.0:
        return None
    err = abs(static_s - profiled_s) / profiled_s
    if err <= tolerance:
        return None
    return make(
        "SAT-X005", "warning",
        f"static estimate disagrees with the profiled runtime by "
        f"{100 * err:.0f}% (> {100 * tolerance:.0f}%) for {technique}@"
        f"{size}: static {static_s:.6f}s vs profiled {profiled_s:.6f}s — "
        "the cost prior is miscalibrated for this workload",
        counterexample={
            "technique": technique, "size": size,
            "static_s": round(static_s, 9),
            "profiled_s": round(profiled_s, 9),
            "relative_error": round(err, 4),
        },
        category="shardflow",
    )


def audit_task(task: Any,
               tolerance: float = AUDIT_TOLERANCE) -> List[Diagnostic]:
    """SAT-X005 over every strategy whose static prior has since been
    superseded by real evidence (trial profile or realized feedback)."""
    diags: List[Diagnostic] = []
    for g, strat in getattr(task, "strategies", {}).items():
        static_s = getattr(strat, "_static_prior_estimate", None)
        if static_s is None or getattr(strat, "static_prior", False):
            continue  # never had a prior, or the prior is still live
        tech = getattr(strat.executor, "name", str(strat.executor))
        d = audit_point(float(static_s), float(strat.per_batch_time),
                        tech, g, tolerance=tolerance)
        if d is not None:
            diags.append(d)
    return diags


# ------------------------------------------------ overlap factor calibration
def calibrate_overlap_factors(
    tasks: Sequence[Any], blend: float = 0.25,
) -> Dict[str, float]:
    """Move :func:`overlap_factors` from static seeds toward measured truth.

    Consumes the same stream SAT-X005 audits: strategies synthesized with an
    ``overlap`` config whose static prior has since been superseded by a
    realized measurement (``static_prior`` flipped off in place, so the
    stashed ``_static_*`` decomposition survives). For each such point the
    measured step time implies how much wire time the overlapped lowering
    actually hid::

        hidden = (compute_s + comm_total - measured) / comm_total

    clamped to [0, 1]. One scalar cannot separate op classes, so the update
    is attributed to each class by its share of the static wire time and
    EWMA-blended (weight ``blend`` x share) into the process-local
    calibrated set. The blended factors flow through
    :func:`overlap_factors` into every later :func:`estimate_step_seconds`
    call — cold-start priors, admission, and the anytime solver all re-price
    — and through :func:`overlap_factor_signature` into the profile-cache
    fingerprint, so entries priced under the old factor set miss.

    Returns the active factor set after calibration. Env pins still win.
    """
    n_points = 0
    for task in tasks:
        for strat in getattr(task, "strategies", {}).values():
            if not getattr(strat, "_static_overlap", False):
                continue
            if getattr(strat, "static_prior", False):
                continue  # prior still live: no measurement yet
            comm_by_op = getattr(strat, "_static_comm_by_op", None) or {}
            compute_s = getattr(strat, "_static_compute_s", None)
            measured = float(getattr(strat, "per_batch_time", 0.0) or 0.0)
            comm_total = sum(comm_by_op.values())
            if compute_s is None or comm_total <= 0.0 or measured <= 0.0:
                continue
            hidden = min(
                max((compute_s + comm_total - measured) / comm_total, 0.0),
                1.0,
            )
            active = overlap_factors()
            for op, s in comm_by_op.items():
                w = min(max(blend, 0.0), 1.0) * (s / comm_total)
                base = active.get(op, 0.0)
                _calibrated_factors[op] = min(
                    max((1.0 - w) * base + w * hidden, 0.0), 1.0
                )
            n_points += 1
    if n_points:
        log.info(
            "shardflow prior: calibrated overlap factors from %d measured "
            "point(s): %s", n_points, overlap_factor_signature(),
        )
    return overlap_factors()


def reset_overlap_calibration() -> None:
    """Drop calibrated deltas (tests; factor set reverts to defaults+env)."""
    _calibrated_factors.clear()
