"""Memlens pass 2: SAT-M diagnostics and zero-compile feasibility verdicts.

Diagnostics over one :class:`MemoryProfile` (:func:`analyze_traced`):

- SAT-M001 (error): predicted per-device HBM peak exceeds capacity by
  the OOM margin — deterministic infeasibility before any compile;
- SAT-M002 (warning): the peak is dominated by a single oversized
  temporary;
- SAT-M003 (error): a non-donated input's shape/dtype matches an output
  — XLA could alias it, the buffer is paid twice;
- SAT-M004 (warning): predicted peak lands above the allocator headroom
  margin but under capacity — fragmentation risk;
- SAT-M005 (warning, :func:`audit_point`): static peak vs the compiled
  ``memory_analysis()`` figure drift beyond the calibration ratio;
- SAT-M000: technique untraceable / source unreadable.

A ``# sanctioned-memlens: <reason>`` comment at a finding's file:line
provenance (or the contiguous comment block above it) downgrades it to
``info`` — visible, never gating, never silent. eqn#-style provenance
cannot be sanctioned.

Feasibility verdicts for the three consumers:

- :func:`grid_point_infeasible` — the trial runner's pre-lowering prune
  (conservative: every candidate config must trace AND predict OOM);
- :func:`coldstart_verdict` — the admission controller's zero-trial
  memory gate over all fitting sizes and techniques;
- :func:`task_fits_mesh` / :func:`migration_fits` — the elastic
  replanner's destination checks for degraded meshes and migrations.

All verdicts fail open: unknown capacity, untraceable steps, or any
internal error means "no verdict", never a false prune/reject. The
compile-time ``_fits_memory`` check stays the authoritative backstop.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from saturn_tpu.analysis.diagnostics import AnalysisReport, make

from saturn_tpu.analysis.memlens import liveness
from saturn_tpu.analysis.memlens.liveness import MemoryProfile

log = logging.getLogger("saturn_tpu")

SANCTION_MARKER = "sanctioned-memlens:"

#: env override for per-device HBM capacity in bytes — lets CPU hosts
#: (tests, benches, cold-start planners) reason about a real chip
ENV_CAPACITY = "SATURN_TPU_HBM_BYTES"

#: a point is *infeasible* only when predicted peak > OOM_MARGIN x
#: capacity: static over-prediction within the margin never prunes a
#: point the compiler might still fit
OOM_MARGIN = 1.15

#: the same allocator headroom spmd_base._fits_compiled enforces;
#: predictions between it and capacity get the SAT-M004 warning
HEADROOM_MARGIN = 0.92

#: SAT-M002 fires when one temporary is more than this fraction of the
#: transient peak and at least DOMINANT_FLOOR bytes
DOMINANT_FRACTION = 0.5
DOMINANT_FLOOR = 1 << 24

#: SAT-M005 fires when static and compiled peaks differ by more than
#: this ratio in either direction
DRIFT_RATIO = 2.5


# ----------------------------------------------------------------- sanctions
def _sanction_in_lines(lines: Sequence[str], line: int) -> Optional[str]:
    """Marker on the finding line or the contiguous comment block above
    it (the saturn-tsan/shardflow lookup with the memlens marker)."""
    if 1 <= line <= len(lines):
        text = lines[line - 1]
        if SANCTION_MARKER in text:
            return text.split(SANCTION_MARKER, 1)[1].strip() or "audited"
    ln = line - 1
    while 1 <= ln <= len(lines):
        text = lines[ln - 1]
        if not text.strip().startswith("#"):
            break
        if SANCTION_MARKER in text:
            return text.split(SANCTION_MARKER, 1)[1].strip() or "audited"
        ln -= 1
    return None


def _sanction_at(provenance: str) -> Optional[str]:
    """Resolve ``file:line`` provenance against its source file's
    sanction markers; eqn#-style provenance can never be sanctioned."""
    path, _, line_s = (provenance or "").rpartition(":")
    try:
        line = int(line_s)
    except ValueError:
        return None
    if not path or not os.path.isfile(path):
        return None
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError:
        return None
    return _sanction_in_lines(lines, line)


# ------------------------------------------------------------------ capacity
def hbm_capacity_bytes(devices: Optional[Sequence[Any]] = None) -> int:
    """Per-device HBM capacity: the env override first (so CPU hosts can
    model a target chip), then the live device's memory stats; 0 when
    neither knows — all capacity-gated checks then stand down."""
    env = os.environ.get(ENV_CAPACITY)
    if env:
        try:
            return max(int(float(env)), 0)
        except ValueError:
            log.warning("memlens: bad %s=%r ignored", ENV_CAPACITY, env)
    if devices:
        try:
            from saturn_tpu.utils.timing import device_hbm_bytes
            return max(int(device_hbm_bytes(devices[0])), 0)
        except Exception:
            return 0
    return 0


# --------------------------------------------------------------- diagnostics
def analyze_traced(
    traced: Dict[str, Any],
    report: Optional[AnalysisReport] = None,
    capacity_bytes: Optional[int] = None,
    window: int = 1,
) -> Tuple[AnalysisReport, MemoryProfile]:
    """SAT-M001/M002/M003/M004 over one ``trace_step`` result."""
    subject = f"memlens:{traced.get('technique')}@{traced.get('size')}"
    if report is None:
        report = AnalysisReport(subject=subject)
    profile = liveness.analyze(traced, window=window)
    cap = hbm_capacity_bytes() if capacity_bytes is None else int(
        capacity_bytes)
    ctx = {
        "technique": profile.technique,
        "size": profile.size,
        "window": profile.window,
        "peak_bytes": profile.peak_bytes,
        "persistent_bytes": profile.persistent_bytes,
        "transient_peak_bytes": profile.transient_peak_bytes,
    }

    for md in profile.missed_donations:
        report.add(make(
            "SAT-M003", "error",
            f"missed donation: input #{md['invar']} "
            f"({md['dtype']}{md['shape']}, {md['bytes']} bytes) matches an "
            f"output shape/dtype but is not donated — XLA cannot alias it, "
            f"so that buffer is resident twice",
            counterexample={**md, **ctx}, category="memlens",
        ))

    if (profile.largest_temp_bytes >= DOMINANT_FLOOR
            and profile.transient_peak_bytes > 0
            and profile.largest_temp_bytes
            >= DOMINANT_FRACTION * profile.transient_peak_bytes):
        sanction = _sanction_at(profile.largest_temp_where)
        report.add(make(
            "SAT-M002", "info" if sanction else "warning",
            f"peak dominated by one temporary: {profile.largest_temp_bytes} "
            f"bytes is >= {DOMINANT_FRACTION:.0%} of the transient peak "
            f"({profile.transient_peak_bytes} bytes) — a remat or reshard "
            f"of this one value moves the whole peak"
            + (f" [sanctioned: {sanction}]" if sanction else ""),
            counterexample=ctx,
            location=profile.largest_temp_where or None, category="memlens",
        ))

    if cap > 0:
        if profile.peak_bytes > OOM_MARGIN * cap:
            sanction = _sanction_at(profile.largest_temp_where)
            report.add(make(
                "SAT-M001", "info" if sanction else "error",
                f"predicted OOM: static per-device HBM peak "
                f"{profile.peak_bytes} bytes exceeds capacity {cap} bytes "
                f"(margin x{OOM_MARGIN:g}) — deterministically infeasible "
                f"before any compile"
                + (f" [sanctioned: {sanction}]" if sanction else ""),
                counterexample={**ctx, "capacity_bytes": cap},
                location=profile.largest_temp_where or None,
                category="memlens",
            ))
        elif profile.peak_bytes > HEADROOM_MARGIN * cap:
            report.add(make(
                "SAT-M004", "warning",
                f"headroom below margin: predicted peak "
                f"{profile.peak_bytes} bytes is within "
                f"{(1 - HEADROOM_MARGIN):.0%} of capacity {cap} bytes — "
                f"allocator fragmentation can tip this point over",
                counterexample={**ctx, "capacity_bytes": cap},
                category="memlens",
            ))
    return report, profile


def audit_point(
    predicted_bytes: int,
    compiled_bytes: int,
    technique: str,
    size: int,
    k: int = 1,
    ratio: float = DRIFT_RATIO,
):
    """SAT-M005: static-vs-compiled drift audit for one grid point.

    Returns the diagnostic when the two peaks disagree by more than
    ``ratio`` in either direction, else ``None``. Fed for free from
    every compile-time ``_fits_memory`` check."""
    p, c = float(predicted_bytes), float(compiled_bytes)
    if p <= 0 or c <= 0:
        return None
    r = max(p, c) / max(min(p, c), 1.0)
    if r <= ratio:
        return None
    return make(
        "SAT-M005", "warning",
        f"static/compiled drift: memlens predicts {int(p)} bytes but "
        f"memory_analysis() reports {int(c)} bytes for {technique}@{size} "
        f"K={k} ({r:.1f}x apart, ratio gate {ratio:g}) — the liveness "
        f"model is miscalibrated for this workload",
        counterexample={
            "predicted_bytes": int(p), "compiled_bytes": int(c),
            "technique": technique, "size": int(size), "k": int(k),
            "ratio": round(r, 2),
        },
        category="memlens",
    )


# ----------------------------------------------------------------- verdicts
_PRED_CACHE: Dict[Any, Optional[MemoryProfile]] = {}


def pipeline_stash_bytes(
    schedule: str, n_stages: int, n_microbatches: int,
    stage_input_bytes: int,
) -> int:
    """Analytic activation-stash residency of the staged pipeline programs
    (``ops/pipeline.staged_pipeline_loss_and_grads``).

    The schedule's scan carries a depth-``D`` ring of stage-INPUT
    microbatch activations, ``D = min(M, C+1)`` with ``C`` the backward
    launch offset: ``2(S-1)`` for 1F1B — so ``D <= 2S-1``, BOUNDED in the
    microbatch count — and ``M + 2(S-1)`` for the GPipe ordering, where
    every in-flight microbatch stays resident (``D = M``). Backward
    recomputes the stage forward from the stashed input (torchgpipe-style
    checkpointing), so this ring is the dominant schedule-dependent
    liveness term; the generic scan-carry rule in
    :mod:`~saturn_tpu.analysis.memlens.liveness` must reproduce it, and
    the SAT-M regression test (``tests/test_memlens.py``) holds the two
    to each other — a liveness change that stops seeing the stash, or a
    schedule change that silently grows it, breaks the band.
    """
    from saturn_tpu.ops.pipeline import stash_depth

    depth = stash_depth(int(n_stages), int(n_microbatches), str(schedule))
    return int(depth) * int(stage_input_bytes)


def predict_profile(
    tech: Any, task: Any, devices: Sequence[Any],
    config: Optional[Dict[str, Any]] = None, window: int = 1,
) -> Optional[MemoryProfile]:
    """Trace + analyze one grid point; ``None`` when untraceable.

    Memoized per in-process task object — admission and sweeps re-ask
    for the same points many times."""
    key = (
        id(task), getattr(task, "name", ""), getattr(tech, "name", str(tech)),
        len(devices),
        tuple(sorted((k, str(v)) for k, v in (config or {}).items())),
        int(window),
    )
    if key in _PRED_CACHE:
        return _PRED_CACHE[key]
    try:
        traced = tech.trace_step(task, list(devices), dict(config or {}))
        prof: Optional[MemoryProfile] = liveness.analyze(
            traced, window=window)
    except Exception as e:
        log.debug("memlens: %s@%d untraceable: %r",
                  getattr(tech, "name", tech), len(devices), e)
        prof = None
    if len(_PRED_CACHE) > 512:
        _PRED_CACHE.clear()
    _PRED_CACHE[key] = prof
    return prof


def grid_point_infeasible(
    tech: Any, task: Any, devices: Sequence[Any], capacity_bytes: int,
    max_configs: int = 3,
) -> bool:
    """True only when this (technique, task, size) point is statically
    certain not to fit: every candidate config traced AND every predicted
    peak clears the OOM margin. Any unknown keeps the point alive for the
    compile-time backstop."""
    if capacity_bytes <= 0 or not hasattr(tech, "trace_step"):
        return False
    try:
        grid = tech.candidate_configs(task, len(devices))
    except Exception:
        return False
    if not grid or len(grid) > max_configs:
        return False
    for config in grid:
        prof = predict_profile(tech, task, devices, config)
        if prof is None or prof.peak_bytes <= OOM_MARGIN * capacity_bytes:
            return False
    return True


def fused_stack_fits(
    tech: Any,
    task: Any,
    devices: Sequence[Any],
    n_members: int,
    capacity_bytes: Optional[int] = None,
    config: Optional[Dict[str, Any]] = None,
    max_configs: int = 3,
) -> Optional[bool]:
    """Zero-compile residency prior for an N-member fused stack.

    The stacked program shards its leading ``model`` axis across the block's
    devices (``parallel/fused.py``), so each device is resident for
    ``ceil(N / n_dev)`` members' FULL solo state — stacking multiplies the
    single-device peak rather than resharding it. This charges that product
    against the OOM margin and answers the solver's ``fusion_fits`` contract
    (``solver/milp.fusion_priced_groups``):

    - ``False``: the cheapest traceable config's stacked peak statically
      clears the OOM margin — certain not to fit, vetoes the size.
    - ``True``: the stacked peak fits under the margin.
    - ``None``: no safe verdict (capacity unknown, nothing traceable) —
      never prunes; the compile-time backstop decides.

    ``n_dev`` honors the fused program's divisibility walk: the model axis
    only spans a device count that divides N evenly, falling back by powers
    of two (worst case one device carries the whole vmapped stack).
    """
    cap = (hbm_capacity_bytes(devices) if capacity_bytes is None
           else int(capacity_bytes))
    if cap <= 0 or int(n_members) < 2 or not hasattr(tech, "trace_step"):
        return None
    n_dev = max(len(devices), 1)
    while n_dev > 1 and int(n_members) % n_dev != 0:
        n_dev //= 2
    members_per_dev = -(-int(n_members) // n_dev)
    grid: List[Dict[str, Any]]
    if config is not None:
        grid = [dict(config)]
    else:
        try:
            grid = list(tech.candidate_configs(task, 1))
        except Exception:
            return None
        grid = grid[:max_configs]
    peaks: List[int] = []
    for cfg in grid:
        prof = predict_profile(tech, task, list(devices)[:1], cfg)
        if prof is not None:
            peaks.append(int(prof.peak_bytes))
    if not peaks:
        return None
    return bool(members_per_dev * min(peaks) <= OOM_MARGIN * cap)


def coldstart_verdict(
    task: Any, topology: Any,
    techniques: Optional[Dict[str, Any]] = None,
    capacity_bytes: Optional[int] = None,
    max_configs: int = 3,
) -> Optional[Dict[str, Any]]:
    """Admission's zero-trial memory gate over every fitting grid point.

    Returns ``None`` when there is no safe verdict (capacity unknown,
    nothing traceable, or an untraceable point that might still fit);
    otherwise ``{"fits", "min_peak_bytes", "capacity_bytes", "checked"}``
    where ``fits`` is False only when *every* fitting point traced and
    predicted OOM."""
    cap = (hbm_capacity_bytes(getattr(topology, "devices", None))
           if capacity_bytes is None else int(capacity_bytes))
    if cap <= 0:
        return None
    if techniques is None:
        from saturn_tpu.parallel import BUILTIN_TECHNIQUES
        techniques = {
            n: (c() if isinstance(c, type) else c)
            for n, c in BUILTIN_TECHNIQUES.items()
        }
    chip_range = getattr(task, "chip_range", None)
    try:
        sizes = [g for g in topology.valid_sizes()
                 if g <= topology.capacity
                 and (not chip_range or g in chip_range)]
    except Exception:
        return None
    min_peak: Optional[int] = None
    checked = 0
    untraceable = 0
    for g in sorted(sizes, reverse=True):
        try:
            devices = topology.block_devices(topology.blocks(g)[0])
        except Exception:
            untraceable += 1
            continue
        for name in sorted(techniques):
            tech = techniques[name]
            if not hasattr(tech, "trace_step"):
                continue
            try:
                grid = tech.candidate_configs(task, g)
            except Exception:
                untraceable += 1
                continue
            for config in grid[:max_configs]:
                prof = predict_profile(tech, task, devices, config)
                if prof is None:
                    untraceable += 1
                    continue
                checked += 1
                peak = prof.peak_bytes
                min_peak = peak if min_peak is None else min(min_peak, peak)
                if peak <= OOM_MARGIN * cap:
                    return {"fits": True, "min_peak_bytes": int(peak),
                            "capacity_bytes": cap, "checked": checked}
            if len(grid) > max_configs:
                untraceable += 1  # unchecked configs might fit
    if checked == 0 or untraceable > 0:
        return None  # an unknown point might fit: no REJECT on a guess
    return {"fits": False, "min_peak_bytes": int(min_peak or 0),
            "capacity_bytes": cap, "checked": checked}


def task_fits_mesh(task: Any, topology: Any, capacity_bytes: int) -> bool:
    """Replanner keep/evict helper: False only when *every* fitting
    feasible strategy of an already-admitted task is predicted OOM on
    this (possibly degraded) mesh. Fails open on any unknown."""
    if capacity_bytes <= 0:
        return True
    try:
        feas = task.feasible_strategies()
    except Exception:
        return True
    fitting = {g: s for g, s in feas.items() if g <= topology.capacity}
    if not fitting:
        return True  # pure size-fit is the caller's _runnable check
    saw = False
    for g, strat in sorted(fitting.items(), reverse=True):
        tech = getattr(strat, "executor", None)
        if tech is None or not hasattr(tech, "trace_step"):
            return True
        try:
            devices = topology.block_devices(topology.blocks(g)[0])
        except Exception:
            return True
        prof = predict_profile(tech, task, devices,
                               getattr(strat, "params", None) or {})
        if prof is None:
            return True
        saw = True
        if prof.peak_bytes <= OOM_MARGIN * capacity_bytes:
            return True
    return not saw


def migration_fits(
    task: Any, topology: Any, apportionment: int, capacity_bytes: int,
) -> Optional[Dict[str, Any]]:
    """Destination-fit check for one planned migration: the restored
    checkpoint shards (persistent state) plus the steady-state peak must
    fit the destination block. ``None`` = no verdict (fail open)."""
    if capacity_bytes <= 0:
        return None
    try:
        strat = task.feasible_strategies().get(apportionment)
    except Exception:
        return None
    if strat is None or not hasattr(
            getattr(strat, "executor", None), "trace_step"):
        return None
    try:
        devices = topology.block_devices(topology.blocks(apportionment)[0])
    except Exception:
        return None
    prof = predict_profile(strat.executor, task, devices,
                           getattr(strat, "params", None) or {})
    if prof is None:
        return None
    return {
        "fits": prof.peak_bytes <= OOM_MARGIN * capacity_bytes,
        "peak_bytes": int(prof.peak_bytes),
        "restored_shard_bytes": int(prof.persistent_bytes),
        "capacity_bytes": int(capacity_bytes),
    }


# ------------------------------------------------------------ in-tree audit
def audit_intree(
    size: int = 4,
    devices: Optional[Sequence[Any]] = None,
    capacity_bytes: Optional[int] = None,
    window: int = 1,
) -> Tuple[AnalysisReport, Dict[str, MemoryProfile]]:
    """The CLI/gate entry point: SAT-M over every registered in-tree
    technique's traced step at a probe size. Shares shardflow's probe
    tasks; techniques the probes cannot exercise are SAT-M000 warnings,
    not failures."""
    import tempfile

    import jax

    from saturn_tpu.analysis.shardflow.passes import _probe_tasks
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    report = AnalysisReport(subject="memlens")
    devs = list(devices) if devices is not None else list(jax.devices())
    probe = min(size, len(devs))
    profiles: Dict[str, MemoryProfile] = {}
    with tempfile.TemporaryDirectory() as tmpdir:
        tasks = _probe_tasks(tmpdir)
        for name, cls in sorted(BUILTIN_TECHNIQUES.items()):
            tech = cls() if isinstance(cls, type) else cls
            if not hasattr(tech, "trace_step"):
                continue  # non-SPMD executor (pipeline): out of scope
            task = tasks["moe" if name == "ep" else "dense"]
            try:
                grid = tech.candidate_configs(task, probe)
                if not grid:
                    continue
                traced = tech.trace_step(task, devs[:probe], grid[0])
                _, profile = analyze_traced(
                    traced, report=report, capacity_bytes=capacity_bytes,
                    window=window,
                )
            except Exception as e:
                report.add(make(
                    "SAT-M000", "warning",
                    f"technique {name!r} could not be traced at size "
                    f"{probe}: {type(e).__name__}: {e}",
                    category="memlens",
                ))
                continue
            profiles[name] = profile
    return report, profiles
