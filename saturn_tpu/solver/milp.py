"""The SPASE MILP: jointly select strategy, allocate a sub-mesh, and schedule.

Reference: ``saturn/solver/milp.py:23-445``. Same decision structure —
one strategy per task (``bss``, ``milp.py:96-111``), one placement per task
(``bna`` node choice, ``:117-137``), start times (``sta``, ``:139-149``),
pairwise ordering (``boa``, ``:263-270``), makespan objective (``:90,321``) —
re-shaped for a TPU pod slice:

- Placement ranges over **contiguous, size-aligned blocks** of the device ring
  (buddy allocation; see ``core/mesh.py``) instead of (node × GPU-subset).
  The reference's "a job never spans nodes" constraint (``milp.py:134-137``)
  becomes "a job occupies exactly one contiguous block" — which also
  guarantees its collectives ride ICI.
- Strategy and placement merge into one joint binary ``x[t][(size, block)]``
  per task: exactly-one per task covers both ``bss`` and ``bna``.
- Big-M is the total runtime bound, not 1e10 (``milp.py:163`` used 1e10 and
  leaned on Gurobi's IntFeasTol; HiGHS is happier with tight Ms).
- Solved with HiGHS via ``saturn_tpu.solver.lp`` (no Gurobi/PuLP in-image).

The introspection compare-and-swap (``milp.py:354-444``) lives in
``resolve()``: re-solve each interval, adopt the new plan only if it beats the
old one by more than interval+threshold, else slide the old plan down.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from saturn_tpu.core.mesh import Block, SliceTopology
from saturn_tpu.solver.lp import Expr, Model

log = logging.getLogger("saturn_tpu")


@dataclass
class Assignment:
    """One task's slot in the plan."""

    apportionment: int      # sub-mesh size (chips)
    block: Block            # which aligned block of the ring
    start: float            # start time, seconds from interval origin
    runtime: float          # estimated remaining runtime under this strategy


@dataclass
class Plan:
    """Decoded schedule (reference ``convert_into_comprehensible``,
    ``milp.py:448-513``)."""

    assignments: Dict[str, Assignment]          # task name -> slot
    makespan: float
    dependencies: Dict[str, List[str]] = field(default_factory=dict)
    # Co-schedule groups: lists of task names whose windows the engine may
    # INTERLEAVE on a shared device block instead of serializing them — the
    # explicit co-location edge ``_check_disjoint`` honors. Produced by the
    # MILP's co-location term when the measured host fractions predict that
    # one job's host phases can hide under the other's device windows; empty
    # everywhere else (warm/greedy/native plans are conservatively serial).
    coschedule: List[List[str]] = field(default_factory=list)
    # Fusion groups: lists of task names the engine trains as ONE stacked
    # SPMD program (``parallel/fused.py``) — N identical-architecture sweep
    # members advancing in lockstep under a single compiled step. Members of
    # a group hold IDENTICAL assignments (same block, same start, runtime =
    # the fused lockstep runtime) by construction; like co-schedule groups
    # their mutual overlap is the point, not a race. Produced only by the
    # fusion pricing pre-pass in :func:`solve` when every member carries a
    # measured ``fused_per_batch_time`` and the fused runtime beats both the
    # serial and the co-scheduled alternative.
    fused: List[List[str]] = field(default_factory=list)

    def coschedule_group_of(self) -> Dict[str, int]:
        """task name -> index of its co-schedule group (absent = solo)."""
        out: Dict[str, int] = {}
        for gi, grp in enumerate(self.coschedule):
            for n in grp:
                out[n] = gi
        return out

    def fused_group_of(self) -> Dict[str, int]:
        """task name -> index of its fusion group (absent = not fused)."""
        out: Dict[str, int] = {}
        for gi, grp in enumerate(self.fused):
            for n in grp:
                out[n] = gi
        return out

    def compute_dependencies(self) -> None:
        """Edges between tasks whose blocks overlap: later start depends on
        earlier (reference builds deps from GPU-overlap ∩ boa,
        ``milp.py:489-511``). Members of one co-schedule group are exempt:
        their overlap is the point — the engine interleaves them on a shared
        launcher rather than ordering them. Members of one FUSION group are
        exempt for the stronger reason: they are one program, and their
        assignments are identical by construction."""
        group_of = self.coschedule_group_of()
        fgroup_of = self.fused_group_of()
        deps: Dict[str, List[str]] = {name: [] for name in self.assignments}
        items = list(self.assignments.items())
        for i, (n1, a1) in enumerate(items):
            for n2, a2 in items[i + 1 :]:
                g1, g2 = group_of.get(n1), group_of.get(n2)
                if g1 is not None and g1 == g2:
                    continue
                f1, f2 = fgroup_of.get(n1), fgroup_of.get(n2)
                if f1 is not None and f1 == f2:
                    continue
                if a1.block.overlaps(a2.block):
                    if a1.start <= a2.start:
                        deps[n2].append(n1)
                    else:
                        deps[n1].append(n2)
        self.dependencies = deps

    def migrations_from(self, previous: "Plan") -> Dict[str, dict]:
        """Per-task placement diff against ``previous`` — the elastic
        replanner's migration report (``resilience/replan.py``). A task
        "moved" when its sub-mesh size or block changed: its next interval
        must restore state onto a different mesh (cross-mesh checkpoint
        migration, ``utils/checkpoint.py::restore_sharded``) instead of
        reusing live device buffers."""
        out: Dict[str, dict] = {}
        for name, a in self.assignments.items():
            p = previous.assignments.get(name)
            if p is None:
                out[name] = {"moved": True, "from": None,
                             "to": [a.apportionment, a.block.offset]}
                continue
            moved = (
                a.apportionment != p.apportionment
                or a.block.offset != p.block.offset
                or a.block.size != p.block.size
            )
            out[name] = {
                "moved": moved,
                "from": [p.apportionment, p.block.offset],
                "to": [a.apportionment, a.block.offset],
            }
        return out

    # Wire format for the multi-host control plane: the coordinator solves,
    # every rank executes the SAME decoded plan (core/distributed.py
    # broadcast_json) — a time-limited HiGHS run is not deterministic
    # across processes.
    def to_json(self) -> dict:
        return {
            "makespan": self.makespan,
            "assignments": {
                n: [a.apportionment, a.block.offset, a.block.size, a.start,
                    a.runtime]
                for n, a in self.assignments.items()
            },
            "dependencies": self.dependencies,
            "coschedule": [list(g) for g in self.coschedule],
            "fused": [list(g) for g in self.fused],
        }

    @staticmethod
    def from_json(d: dict) -> "Plan":
        return Plan(
            assignments={
                n: Assignment(int(app), Block(int(off), int(size)), float(st),
                              float(rt))
                for n, (app, off, size, st, rt) in d["assignments"].items()
            },
            makespan=float(d["makespan"]),
            dependencies={k: list(v) for k, v in d["dependencies"].items()},
            # absent in plans journaled before the co-schedule term existed
            coschedule=[list(g) for g in d.get("coschedule", [])],
            # absent in plans journaled before fused stacking existed
            fused=[list(g) for g in d.get("fused", [])],
        )


class DeviceTimeline:
    """Per-device busy intervals with the earliest-free-slot rule.

    The single Python implementation of the list-scheduling primitive that
    ``warm_schedule`` and ``greedy_plan`` share and that ``evaluate`` in
    ``native/spase.cpp:47-90`` mirrors in C++ — occupied windows are padded by
    the caller's ordering slack, finish times exclude the pad, and a task
    starts at the earliest t where [t, t+duration) is free on every device of
    its block. Property-tested for exact equivalence against the native
    constructor (``tests/test_native.py``); the warm plan's "never worse"
    guarantee rests on all three agreeing.
    """

    def __init__(self, capacity: int):
        self._events: Dict[int, List[Tuple[float, float]]] = {
            d: [] for d in range(capacity)
        }

    def earliest_free(self, blk: Block, duration: float) -> float:
        """Earliest t such that [t, t+duration) is free on all devices of blk."""
        busy = sorted(
            iv for d in range(blk.offset, blk.end) for iv in self._events[d]
        )
        t0 = 0.0
        for s, e in busy:
            if t0 + duration <= s:
                break
            t0 = max(t0, e)
        return t0

    def occupy(self, blk: Block, start: float, end: float) -> None:
        for d in range(blk.offset, blk.end):
            self._events[d].append((start, end))

    def place(self, blk: Block, runtime: float, slack: float) -> float:
        """Book the earliest slack-padded slot for ``runtime`` on ``blk``;
        returns the start time."""
        st = self.earliest_free(blk, runtime + slack)
        self.occupy(blk, st, st + runtime + slack)
        return st


def warm_schedule(
    task_list: List,
    topology: SliceTopology,
    previous: Plan,
    ordering_slack: float = 1.0,
    insert_missing: bool = False,
    weights: Optional[Dict[str, float]] = None,
    insertion_probe_cap: Optional[int] = None,
) -> Optional[Plan]:
    """Fix-and-optimize warm start: keep each task's previous (size, block)
    choice, list-schedule starts under CURRENT runtimes in previous start
    order. O(N² log N), always feasible — the analog of the reference seeding
    Gurobi with last interval's solution (``milp.py:103-104,151-155,323``).

    Returns None if any task lacks a previous assignment or its previous
    choice no longer exists (strategy became infeasible / capacity changed) —
    unless ``insert_missing`` is set, in which case such tasks are appended
    AFTER the pinned incumbent structure, each at its min-finish
    (strategy, block) slot, in descending ``weights`` order (priority-first;
    ties broken longest-first). This is the online service's incremental
    warm start: one arrival or departure perturbs the live plan instead of
    invalidating it.

    ``insertion_probe_cap`` bounds the (strategy, block) slots probed per
    inserted task: probes run in the deterministic sorted option order and
    stop at the cap once at least one feasible slot was found (the cap never
    leaves a schedulable task unplaced — it only stops the search for a
    *better* slot). The anytime solver's tier-0 budget depends on this: one
    newcomer with a rich option set on a big mesh must cost O(cap) probes,
    not O(sizes x blocks).
    """
    pinned: List[Tuple[object, int, Block, float]] = []  # (task, size, blk, rt)
    loose: List = []
    for t in task_list:
        a = previous.assignments.get(t.name)
        strat = (
            t.feasible_strategies().get(a.apportionment) if a is not None else None
        )
        if a is None or strat is None or a.block.end > topology.capacity:
            if not insert_missing:
                return None
            loose.append(t)
            continue
        pinned.append((t, a.apportionment, a.block, strat.runtime))

    # Previous start order preserves the incumbent schedule's structure.
    pinned.sort(key=lambda p: previous.assignments[p[0].name].start)

    timeline = DeviceTimeline(topology.capacity)
    assignments: Dict[str, Assignment] = {}
    for t, size, blk, rt in pinned:
        st = timeline.place(blk, rt, ordering_slack)
        assignments[t.name] = Assignment(size, blk, st, rt)

    w = weights or {}
    loose.sort(
        key=lambda t: (
            -w.get(t.name, 0.0),
            -min(s.runtime for s in t.feasible_strategies().values()),
        )
    )
    for t in loose:
        best = None  # (finish, start, size, blk, rt)
        probes = 0
        for size, strat in sorted(t.feasible_strategies().items()):
            if size > topology.capacity:
                continue
            for blk in topology.blocks(size):
                if (insertion_probe_cap is not None
                        and probes >= insertion_probe_cap
                        and best is not None):
                    break  # deterministic cutoff: keep the best slot so far
                probes += 1
                st = timeline.earliest_free(blk, strat.runtime + ordering_slack)
                fin = st + strat.runtime
                if best is None or fin < best[0]:
                    best = (fin, st, size, blk, strat.runtime)
            if (insertion_probe_cap is not None
                    and probes >= insertion_probe_cap
                    and best is not None):
                break
        if best is None:
            return None  # a loose task fits no block: no warm plan exists
        fin, st, size, blk, rt = best
        timeline.occupy(blk, st, fin + ordering_slack)
        assignments[t.name] = Assignment(size, blk, st, rt)

    makespan = max((a.start + a.runtime for a in assignments.values()), default=0.0)
    plan = Plan(assignments=assignments, makespan=makespan)
    plan.compute_dependencies()
    return plan


def _host_fraction_of(task, size: int) -> float:
    """Measured host fraction of a task's strategy at ``size``, clamped to
    [0, 1]. 0.0 when unmeasured (pre-existing cache entries, dummy
    strategies) — which makes the predicted interleave gain 1.0x and keeps
    the pair out of the co-location term entirely."""
    strat = getattr(task, "strategies", {}).get(size)
    if strat is None:
        return 0.0
    hf = float(getattr(strat, "host_fraction", 0.0) or 0.0)
    return min(max(hf, 0.0), 1.0)


def _fillable_fraction_of(task, size: int) -> float:
    """Fraction of a steady-state batch during which the job's DEVICES are
    idle and a co-scheduled partner could run: measured host-side staging
    (``host_fraction``) plus the analytic schedule bubble
    (``bubble_fraction`` — pipeline warmup/cooldown ticks). Clamped to
    [0, 1]. A GPipe job donates its (S-1)/(M+S-1) bubble to a partner; the
    same job under 1F1B donates only (S-1)/(M+2(S-1)), so switching
    schedules shrinks the predicted interleave win — exactly the trade the
    co-location term must see."""
    strat = getattr(task, "strategies", {}).get(size)
    if strat is None:
        return 0.0
    hf = float(getattr(strat, "host_fraction", 0.0) or 0.0)
    bubble = float(getattr(strat, "bubble_fraction", 0.0) or 0.0)
    return min(max(hf, 0.0) + max(bubble, 0.0), 1.0)


def coschedule_candidates(
    task_list: List,
    choices: Dict[str, List[Tuple[int, "Block", float]]],
    min_gain: float,
) -> List[Tuple[str, str, List[Tuple[int, int, float]]]]:
    """Task pairs whose measured host fractions predict an interleave win.

    For each pair and each (size, block) option BOTH tasks could take, the
    interleaved pair occupies the block for
    ``comb = max(rt1, rt2, dev1 + dev2)`` where ``dev = (1 - fillable) *
    rt`` and ``fillable = host_fraction + bubble_fraction`` — device phases
    serialize on the shared block; host staging AND schedule bubbles
    (pipeline warmup/cooldown) hide under the partner's device windows. The
    pair is a candidate only when the best common option predicts
    ``(rt1 + rt2) / comb >= min_gain``: two compute-bound bubble-free jobs
    give ``comb = rt1 + rt2`` (gain 1.0x) and never qualify, which is
    exactly the "choose co-location only when the profile predicts a win"
    contract — and a job whose solver-picked schedule is 1F1B offers a
    smaller bubble than the same job under GPipe, so pairs that only
    cleared ``min_gain`` on the fatter GPipe bubble drop out. Returns
    ``(n1, n2, [(i1, i2, comb), ...])`` with option indices into each
    task's choice list.
    """
    by_name = {t.name: t for t in task_list}
    names = [t.name for t in task_list]
    out: List[Tuple[str, str, List[Tuple[int, int, float]]]] = []
    for i, n1 in enumerate(names):
        for n2 in names[i + 1 :]:
            opt2 = {
                (s, b.offset, b.size): (j, rt)
                for j, (s, b, rt) in enumerate(choices[n2])
            }
            common: List[Tuple[int, int, float]] = []
            best_gain = 0.0
            for i1, (s, b, rt1) in enumerate(choices[n1]):
                hit = opt2.get((s, b.offset, b.size))
                if hit is None:
                    continue
                i2, rt2 = hit
                f1 = _fillable_fraction_of(by_name[n1], s)
                f2 = _fillable_fraction_of(by_name[n2], s)
                comb = max(rt1, rt2, (1.0 - f1) * rt1 + (1.0 - f2) * rt2)
                common.append((i1, i2, comb))
                if comb > 1e-9:
                    best_gain = max(best_gain, (rt1 + rt2) / comb)
            if common and best_gain >= min_gain:
                out.append((n1, n2, common))
    return out


class _FusedPseudoTask:
    """Stand-in the MILP schedules in place of a whole fusion group.

    Carries ONLY the fused option set (the sizes the group was actually
    priced at), so no solver path — exact, native, warm, greedy — can place
    the group at a size its fused program was never profiled for. Its
    strategies report zero host/bubble fractions, which keeps it out of the
    co-location candidate generator (a fused stack is already the denser
    packing; interleaving it with a third job is the engine's problem, not
    the solver's).
    """

    def __init__(self, name: str, strategies: Dict[int, Any]):
        self.name = name
        self.strategies = strategies

    def feasible_strategies(self) -> Dict[int, Any]:
        return self.strategies


def _remaining_batches(strat) -> Optional[float]:
    """Remaining batch count implied by a strategy's (runtime, per-batch)
    estimates; None when per-batch time was never measured — fusion pricing
    refuses to guess."""
    pbt = float(getattr(strat, "per_batch_time", 0.0) or 0.0)
    if pbt <= 0.0:
        return None
    return max(0.0, float(strat.runtime) / pbt)


def fusion_priced_groups(
    task_list: List,
    proposed: List[List[str]],
    topology: SliceTopology,
    fusion_exclude=None,
    fusion_fits=None,
) -> List[Tuple[List[str], int, float, float]]:
    """Price each proposed fusion group on MEASURED cost; keep the winners.

    For each candidate group (same ModelSpec fingerprint, from
    ``parallel/fused.fusion_candidates``) and each sub-mesh size at which
    EVERY member holds a feasible strategy with a measured
    ``fused_per_batch_time``, the fused stack occupies the block for

        ``fused_rt = max_m(remaining_batches_m) * max_m(fused_per_batch_time_m)``

    — lockstep: the stack runs until its longest member finishes (shorter
    members detach early, but the block is booked for the stack). The group
    fuses only when that beats BOTH alternatives the solver could otherwise
    pick on the same size:

    - serial: ``sum_m(runtime_m)`` — members run back-to-back;
    - co-scheduled pairs: members paired longest-with-longest, each pair
      priced at the interleaved combined occupancy from
      :func:`coschedule_candidates`'s formula, pairs serialized.

    ``fusion_exclude`` drops individual members (the health guardian's
    quarantined repeat offenders) — the rest of the group can still fuse if
    >= 2 members remain. ``fusion_fits`` is the memlens residency gate:
    ``(member_tasks, size, n_members) -> Optional[bool]``; an explicit False
    (the x N stacked HBM residency exceeds capacity) vetoes the size, None
    (unknown) does not prune — exactly the analyzer's zero-compile
    feasibility-prior contract.

    Returns ``[(member_names, size, fused_runtime, fused_per_batch_time)]``
    with each group priced at its best (smallest fused runtime) size.
    """
    by_name = {t.name: t for t in task_list}
    excl = set(fusion_exclude or ())
    out: List[Tuple[List[str], int, float, float]] = []
    claimed: set = set()
    for group in proposed:
        names = [n for n in group if n in by_name and n not in excl
                 and n not in claimed]
        if len(names) < 2:
            continue
        members = [by_name[n] for n in names]
        common = None
        for m in members:
            sizes = {
                s for s, strat in m.feasible_strategies().items()
                if s <= topology.capacity
                and getattr(strat, "fused_per_batch_time", None) is not None
            }
            common = sizes if common is None else (common & sizes)
        best: Optional[Tuple[float, int, float]] = None  # (fused_rt, size, fpbt)
        for size in sorted(common or ()):
            strats = [m.feasible_strategies()[size] for m in members]
            batches = [_remaining_batches(s) for s in strats]
            if any(b is None for b in batches):
                continue  # a member's per-batch time was never measured
            fpbt = max(float(s.fused_per_batch_time) for s in strats)
            fused_rt = max(batches) * fpbt
            serial = sum(float(s.runtime) for s in strats)
            # Co-scheduled alternative: longest-with-longest pairs, each at
            # the interleaved combined occupancy, pairs serialized on the
            # block (the engine runs one shared launcher at a time).
            ordered = sorted(
                zip(members, strats), key=lambda p: -float(p[1].runtime)
            )
            cosched = 0.0
            i = 0
            while i < len(ordered):
                if i + 1 < len(ordered):
                    (t1, s1), (t2, s2) = ordered[i], ordered[i + 1]
                    f1 = _fillable_fraction_of(t1, size)
                    f2 = _fillable_fraction_of(t2, size)
                    rt1, rt2 = float(s1.runtime), float(s2.runtime)
                    cosched += max(
                        rt1, rt2, (1.0 - f1) * rt1 + (1.0 - f2) * rt2
                    )
                    i += 2
                else:
                    cosched += float(ordered[i][1].runtime)
                    i += 1
            if fused_rt >= min(serial, cosched):
                continue  # measured cost does not favor fusion at this size
            if fusion_fits is not None and fusion_fits(
                members, size, len(members)
            ) is False:
                continue  # memlens: stacked residency would not fit
            if best is None or fused_rt < best[0]:
                best = (fused_rt, size, fpbt)
        if best is not None:
            fused_rt, size, fpbt = best
            out.append((names, size, fused_rt, fpbt))
            claimed.update(names)
    return out


def solve(
    task_list: List,
    topology: SliceTopology,
    time_limit: Optional[float] = None,
    ordering_slack: float = 1.0,
    milp_task_limit: int = 12,
    warm: Optional[Plan] = None,
    weights: Optional[Dict[str, float]] = None,
    coschedule_min_gain: float = 1.15,
    coschedule_exclude=None,
    fusion: Optional[List[List[str]]] = None,
    fusion_exclude=None,
    fusion_fits=None,
) -> Plan:
    """Build and solve the joint strategy/placement/schedule MILP.

    Each task contributes its *feasible* strategies (``params is not None`` —
    the reference's dummy-strategy exclusion, ``PerformanceEvaluator.py:96-110``).
    Tasks with no feasible strategy raise — better than silently dropping.

    Above ``milp_task_limit`` tasks, the exact MILP's pairwise big-M
    constraints explode (O(N²·devices) rows); the native C++ scheduler
    (``native/spase.cpp``) takes over — same option set, validated plan.

    ``warm`` (the previous interval's plan) warm-starts both paths, parity
    with the reference's ``warmStart=True`` (``milp.py:323``): the exact MILP
    gets the fix-and-optimize makespan as an upper-bound cut (scipy's HiGHS
    wrapper cannot inject an incumbent) and returns the warm plan instead of
    greedy when the time limit strikes out; the native search is seeded with
    the previous (size, block) choices. Tasks absent from ``warm`` (online
    arrivals) are inserted into the fix-and-optimize incumbent rather than
    discarding it (``warm_schedule(insert_missing=True)``).

    ``weights`` (task name -> nonnegative urgency, from the service's
    admission controller) adds a priority term to the objective: among
    makespan-equal schedules, higher-weight tasks start earlier. The term is
    scaled to at most ~0.5% of the horizon so it can only reorder, never
    trade away meaningful makespan — minimizing batch makespan stays the
    primary objective (the paper's SPASE formulation).

    ``coschedule_min_gain``: minimum predicted pair speedup (sequential
    runtime sum over interleaved combined occupancy, from the trial runner's
    measured host fractions) for a pair to enter the co-location term — see
    :func:`coschedule_candidates`. Only the exact MILP proposes co-schedule
    groups; the native/greedy/warm paths stay conservatively serial.

    ``coschedule_exclude``: task names barred from co-location (the health
    guardian's detached repeat offenders). Exclusion happens at the
    CANDIDATE level — pairs touching an excluded name never get a ``co``
    binary — because group members hold overlapping assignments: stripping
    a member from an already-solved group would be a device race.

    ``fusion``: proposed fusion groups (lists of task names sharing a
    ModelSpec fingerprint, from ``parallel/fused.fusion_candidates``). Each
    group is priced on measured cost by :func:`fusion_priced_groups`; the
    winners are collapsed to one :class:`_FusedPseudoTask` each, the reduced
    batch is solved normally (every path — exact MILP, native, warm, greedy
    — sees the pseudo-task), and the decoded plan is expanded so every
    member holds the representative's assignment and ``Plan.fused`` records
    the groups. ``fusion_exclude`` bars individual members (quarantined
    repeat offenders); ``fusion_fits`` is the memlens stacked-residency gate
    — see :func:`fusion_priced_groups`.
    """
    for t in task_list:
        if not t.feasible_strategies():
            raise ValueError(f"task {t.name} has no feasible strategy; run search first")
        if all(size > topology.capacity for size in t.feasible_strategies()):
            raise ValueError(
                f"task {t.name}: no strategy fits topology capacity {topology.capacity}"
            )

    if fusion:
        winners = fusion_priced_groups(
            task_list, fusion, topology,
            fusion_exclude=fusion_exclude, fusion_fits=fusion_fits,
        )
        if winners:
            from saturn_tpu.core.strategy import Strategy as _Strategy

            by_name = {t.name: t for t in task_list}
            fused_member: Dict[str, int] = {}  # member name -> winner index
            reduced: List = []
            red_weights = dict(weights) if weights else {}
            for wi, (names, _, _, _) in enumerate(winners):
                for n in names:
                    fused_member[n] = wi
            for wi, (names, _, _, _) in enumerate(winners):
                rep = names[0]
                # Pseudo-option set: every size the group was priced at
                # (fusion_priced_groups returns only the best size, so
                # re-derive the full priced set to keep the solver's choice).
                strategies: Dict[int, Any] = {}
                common = None
                for n in names:
                    sizes = {
                        s for s, st in by_name[n].feasible_strategies().items()
                        if s <= topology.capacity
                        and getattr(st, "fused_per_batch_time", None) is not None
                        and _remaining_batches(st) is not None
                    }
                    common = sizes if common is None else (common & sizes)
                for size in sorted(common or ()):
                    strats = [
                        by_name[n].feasible_strategies()[size] for n in names
                    ]
                    fpbt = max(
                        float(s.fused_per_batch_time) for s in strats
                    )
                    fused_rt = (
                        max(_remaining_batches(s) for s in strats) * fpbt
                    )
                    if fusion_fits is not None and fusion_fits(
                        [by_name[n] for n in names], size, len(names)
                    ) is False:
                        continue
                    strategies[size] = _Strategy(
                        executor=strats[0].executor,
                        apportionment=size,
                        params=dict(strats[0].params or {}),
                        runtime=fused_rt,
                        per_batch_time=fpbt,
                    )
                reduced.append(_FusedPseudoTask(rep, strategies))
                if weights:
                    red_weights[rep] = max(
                        (weights.get(n, 0.0) for n in names), default=0.0
                    )
            reduced.extend(t for t in task_list if t.name not in fused_member)
            inner = solve(
                reduced, topology, time_limit=time_limit,
                ordering_slack=ordering_slack,
                milp_task_limit=milp_task_limit, warm=warm,
                weights=red_weights or None,
                coschedule_min_gain=coschedule_min_gain,
                coschedule_exclude=coschedule_exclude,
            )
            assignments = dict(inner.assignments)
            for names, _, _, _ in winners:
                rep_a = assignments[names[0]]
                for n in names[1:]:
                    assignments[n] = Assignment(
                        rep_a.apportionment, rep_a.block, rep_a.start,
                        rep_a.runtime,
                    )
            plan = Plan(
                assignments=assignments, makespan=inner.makespan,
                coschedule=inner.coschedule,
                fused=[list(names) for names, _, _, _ in winners],
            )
            plan.compute_dependencies()
            log.info(
                "fusion pre-pass: %d group(s) priced in favor of stacking "
                "(%s)", len(winners),
                "; ".join(
                    f"{len(names)}@{size} fused={rt:.1f}s"
                    for names, size, rt, _ in winners
                ),
            )
            return plan

    wplan = (
        warm_schedule(task_list, topology, warm, ordering_slack,
                      insert_missing=True, weights=weights)
        if warm is not None
        else None
    )

    if len(task_list) > milp_task_limit:
        from saturn_tpu.solver import native_sched

        plan = native_sched.solve_native(
            task_list, topology,
            # honor an explicit caller budget (e.g. orchestrate's interval/2);
            # 5s is only the default when none was given.
            time_limit=time_limit if time_limit is not None else 5.0,
            ordering_slack=ordering_slack,
            warm=warm,
        )
        if plan is not None:
            log.info("large batch (%d tasks): native scheduler makespan %.1fs",
                     len(task_list), plan.makespan)
            if wplan is not None and wplan.makespan < plan.makespan:
                return wplan
            return plan
        if wplan is not None:
            return wplan
        return greedy_plan(task_list, topology, ordering_slack, weights=weights)

    # Cheap native pass first (~0.1-0.2s at these sizes): its plan is a
    # guaranteed-feasible incumbent that (a) upper-bounds the MILP via a cut
    # and (b) floors the result quality if HiGHS strikes out. At >= 8 tasks
    # with rich option sets the exact solver rarely proves optimality inside
    # a 30s budget and the native search often leads — combining them is
    # never worse than either.
    # Its cost (incl. a possible first-call g++ build) is deducted from the
    # caller's budget below so solve() never overruns time_limit.
    import time as _time

    from saturn_tpu.solver import native_sched

    t_pre = _time.perf_counter()
    nplan = native_sched.solve_native(
        task_list, topology, time_limit=min(1.0, time_limit or 1.0),
        ordering_slack=ordering_slack, warm=warm,
    )
    if time_limit is not None:
        time_limit = max(0.1, time_limit - (_time.perf_counter() - t_pre))
    incumbent = nplan
    if wplan is not None and (incumbent is None or wplan.makespan < incumbent.makespan):
        incumbent = wplan

    m = Model("spase")
    # Joint (strategy,block) choice per task.
    choices: Dict[str, List[Tuple[int, Block, float]]] = {}
    x: Dict[str, List] = {}
    for t in task_list:
        opts = []
        for size, strat in sorted(t.feasible_strategies().items()):
            if size > topology.capacity:
                continue
            for blk in topology.blocks(size):
                opts.append((size, blk, strat.runtime))
        choices[t.name] = opts
        x[t.name] = [m.binary(f"x_{t.name}_{s}_{b.offset}") for s, b, _ in opts]
        m.add(sum(x[t.name][1:], Expr.of(x[t.name][0])) == 1)

    # Horizon T: serial-sum of worst-case runtimes plus per-pair ordering
    # slack — no valid schedule needs starts beyond it. The big-M must relax
    # ``sta_i >= sta_j + rt_j + slack - M`` even at sta_j = T, so M ≈ 2T
    # (the reference sidestepped this with M=1e10 and solver IntFeasTol,
    # ``milp.py:163``; HiGHS prefers tight-but-sufficient).
    T = sum(max(s.runtime for s in t.feasible_strategies().values()) for t in task_list)
    T += max(0, len(task_list) - 1) * ordering_slack
    T = max(T, 1.0) * 1.05
    M = 2.0 * T + 1.0

    sta = {t.name: m.continuous(f"sta_{t.name}", lb=0.0, ub=T) for t in task_list}
    makespan = m.continuous("makespan", lb=0.0, ub=T)

    def runtime_expr(name: str) -> Expr:
        e = Expr()
        for xi, (_, _, rt) in zip(x[name], choices[name]):
            e = e + xi * rt
        return e

    def occ_expr(name: str, dev: int) -> Expr:
        """Linear expression: does task occupy device ``dev``? (analog of the
        reference's tga occupancy vars, ``milp.py:184-195`` — here derived,
        not free variables)."""
        e = Expr()
        for xi, (_, blk, _) in zip(x[name], choices[name]):
            if blk.offset <= dev < blk.end:
                e = e + xi
        return e

    names = [t.name for t in task_list]

    # ------------------------------------------------------- co-location term
    # For pairs whose measured host fractions predict an interleave win, a
    # binary ``co`` lets the solver pack both jobs onto the SAME (size,
    # block) option at the SAME start: their windows then interleave on one
    # launcher (engine CoScheduleGroup) instead of serializing. When co=1:
    # both tasks are pinned to a common option (identical choice), starts
    # are tied, the pair's own ordering-exclusion rows relax away, and each
    # member's EFFECTIVE runtime — what third parties on the block and the
    # makespan see — rises to the pair's combined occupancy ``comb``
    # (device phases serialize; host phases hide). Tasks without a measured
    # host fraction produce no candidates, no binaries, no new rows.
    co_pairs = coschedule_candidates(task_list, choices, coschedule_min_gain)
    if coschedule_exclude:
        excl = set(coschedule_exclude)
        co_pairs = [
            (n1, n2, c) for n1, n2, c in co_pairs
            if n1 not in excl and n2 not in excl
        ]
    co_of: Dict[Tuple[str, str], Any] = {}
    eff: Dict[str, Expr] = {n: runtime_expr(n) for n in names}
    per_task_cos: Dict[str, List] = {}
    for n1, n2, common in co_pairs:
        co = m.binary(f"co_{n1}_{n2}")
        co_of[(n1, n2)] = co
        per_task_cos.setdefault(n1, []).append(co)
        per_task_cos.setdefault(n2, []).append(co)
        common1 = {i1 for i1, _, _ in common}
        common2 = {i2 for _, i2, _ in common}
        # co=1 restricts both tasks to their COMMON options...
        for j, xi in enumerate(x[n1]):
            if j not in common1:
                m.add(Expr.of(xi) <= Expr.of(1.0) - co)
        for j, xi in enumerate(x[n2]):
            if j not in common2:
                m.add(Expr.of(xi) <= Expr.of(1.0) - co)
        # ...forces the identical choice, and ties the starts.
        for i1, i2, _ in common:
            m.link_when(co, x[n1][i1], x[n2][i2], 1.0)
        m.link_when(co, sta[n1], sta[n2], M)
    if per_task_cos:
        for n, cos in per_task_cos.items():
            # One co-partner per task: groups stay pairs, and the engine's
            # shared launcher never has to merge transitively-linked chains.
            if len(cos) > 1:
                m.add(sum(cos[1:], Expr.of(cos[0])) <= 1)
            ert = m.continuous(f"ert_{n}", lb=0.0, ub=M)
            m.add(Expr.of(ert) >= runtime_expr(n))
            eff[n] = Expr.of(ert)
        for n1, n2, common in co_pairs:
            co = co_of[(n1, n2)]
            comb_expr = Expr()
            for i1, _, comb in common:
                comb_expr = comb_expr + x[n1][i1] * comb
            # co=1 (choice pinned to a common option, sum of common x's = 1)
            # makes comb_expr the selected option's combined occupancy.
            m.add(eff[n1] >= comb_expr - (Expr.of(1.0) - co) * M)
            m.add(eff[n2] >= comb_expr - (Expr.of(1.0) - co) * M)

    # makespan >= start + effective runtime of the selected option
    # (``milp.py:170-177``; eff == runtime for every non-co-scheduled task)
    for t in task_list:
        m.add(makespan >= sta[t.name] + eff[t.name])

    # Worker exclusion: tasks sharing any device must be fully ordered with no
    # overlap in time (``milp.py:277-319``) — unless their co-schedule binary
    # is set, which relaxes BOTH rows (the pair overlaps by design, and a
    # third task on the block is still excluded from the whole interleaved
    # span via the pair members' effective runtimes).
    for i, n1 in enumerate(names):
        for n2 in names[i + 1 :]:
            # skip pairs that can never overlap (disjoint choice sets)
            may_overlap = any(
                b1.overlaps(b2)
                for _, b1, _ in choices[n1]
                for _, b2, _ in choices[n2]
            )
            if not may_overlap:
                continue
            boa = m.binary(f"boa_{n1}_{n2}")  # 1 => n1 before n2
            co = co_of.get((n1, n2))
            co_relax = Expr.of(co) * M if co is not None else Expr.of(0.0)
            for dev in range(topology.capacity):
                o1, o2 = occ_expr(n1, dev), occ_expr(n2, dev)
                # if both occupy dev and boa=1: sta2 >= sta1 + rt1
                m.add(
                    sta[n2]
                    >= sta[n1]
                    + eff[n1]
                    + ordering_slack
                    - M * (1 - Expr.of(boa))
                    - M * (2 - o1 - o2)
                    - co_relax
                )
                m.add(
                    sta[n1]
                    >= sta[n2]
                    + eff[n2]
                    + ordering_slack
                    - M * Expr.of(boa)
                    - M * (2 - o1 - o2)
                    - co_relax
                )

    # Valid inequality (area cut): the selected options' total work area
    # cannot exceed makespan × capacity. Redundant for integer solutions but
    # tightens the LP relaxation — the big-M ordering rows relax to nothing,
    # so without it HiGHS's dual bound starts near max-single-runtime.
    # A co-scheduled pair's host phases consume no device area — the pair
    # occupies ``comb * size``, not ``(rt1 + rt2) * size`` — so each pair
    # gets a savings variable, active only when its co binary is (sav <= 0
    # otherwise), bounded by the SELECTED common option's area saving.
    area = Expr()
    for t in task_list:
        for xi, (size, _, rt) in zip(x[t.name], choices[t.name]):
            area = area + xi * (size * rt)
    for n1, n2, common in co_pairs:
        co = co_of[(n1, n2)]
        sav = m.continuous(f"sav_{n1}_{n2}", lb=0.0, ub=M * topology.capacity)
        savings_expr = Expr()
        for i1, i2, comb in common:
            size, _, rt1 = choices[n1][i1]
            _, _, rt2 = choices[n2][i2]
            savings_expr = savings_expr + x[n1][i1] * (
                max(0.0, rt1 + rt2 - comb) * size
            )
        m.add(Expr.of(sav) <= savings_expr)
        m.add(Expr.of(sav) <= Expr.of(co) * (M * topology.capacity))
        area = area - Expr.of(sav)
    m.add(makespan >= area * (1.0 / topology.capacity))

    # Tiny pressure AGAINST co-location: among makespan-equal schedules
    # (e.g. the pair also fits side-by-side on disjoint blocks) prefer the
    # plain plan — interleaving should only engage when it buys wall-clock.
    # Scaled to ~0.01% of the horizon per pair so it can never trade a real
    # makespan win away.
    co_term = sum((Expr.of(c) for c in co_of.values()), Expr()) * (1e-4 * T)

    if weights:
        # Priority pressure: weighted start times, normalized so the whole
        # term is <= 0.5% of the horizon — a tie-breaker among makespan-equal
        # schedules (high-weight tasks start first), never a makespan trade.
        wsum = sum(max(weights.get(n, 0.0), 0.0) for n in names) or 1.0
        wterm = Expr()
        for n in names:
            wn = max(weights.get(n, 0.0), 0.0)
            if wn > 0.0:
                wterm = wterm + sta[n] * (wn / wsum)
        m.minimize(makespan + wterm * 5e-3 + co_term)
    else:
        # Tiny pressure toward early starts (keeps solutions canonical).
        m.minimize(
            makespan
            + sum((sta[n] for n in names), Expr()) * (1e-6 / max(len(names), 1))
            + co_term
        )

    if incumbent is not None:
        # Incumbent cut (native and/or warm fix-and-optimize plan): feasible,
        # so its makespan upper-bounds the optimum — prunes every
        # branch-and-bound node whose relaxation exceeds it.
        m.add(makespan <= incumbent.makespan + 1e-6 * max(incumbent.makespan, 1.0))

    res = m.solve(time_limit=time_limit)
    if not res.ok:
        if incumbent is not None:
            # Timed out without beating the cut: the incumbent IS the answer
            # (never worse than the native/previous-interval plan).
            log.info("MILP timeout — keeping native/warm incumbent plan")
            return incumbent
        log.warning("MILP infeasible/error — falling back to greedy")
        return greedy_plan(task_list, topology, ordering_slack, weights=weights)

    assignments: Dict[str, Assignment] = {}
    for t in task_list:
        vals = [res.value(xi) for xi in x[t.name]]
        k = max(range(len(vals)), key=lambda i: vals[i])  # argmax like ``milp.py:471-486``
        size, blk, rt = choices[t.name][k]
        assignments[t.name] = Assignment(
            apportionment=size,
            block=blk,
            start=max(0.0, res.value(sta[t.name])),
            runtime=rt,
        )
    groups = [
        [n1, n2] for (n1, n2), co in co_of.items() if res.value(co) > 0.5
    ]
    plan = Plan(
        assignments=assignments, makespan=res.value(makespan),
        coschedule=groups,
    )
    plan.compute_dependencies()
    return plan


def makespan_lower_bound(
    task_list: List, topology: SliceTopology, time_limit: float = 10.0
) -> float:
    """Valid lower bound on the optimal makespan (VERDICT r2 item 5).

    The reference proved optimality outright by solving its full batch exactly
    (``milp.py:322-327``); above ``milp_task_limit`` this system runs the
    native local search instead, so quality must be certified against a bound.
    Three bounds, max taken:

    - longest single task: every task needs at least its fastest option's
      runtime somewhere;
    - whole-ring serialization: tasks whose every option occupies the full
      ring pairwise overlap and must run serially;
    - assignment LP: per-task fractional option choice with ordering dropped
      and capacity kept as the area inequality (makespan ≥ selected work area
      / capacity, and ≥ each task's own mixed runtime). This dominates the
      pure area bound and stays an LP — solved in milliseconds at 64 tasks.

    The bound is loose by construction (it assumes perfectly efficient
    packing), so 'gap vs LB' *over*states the true optimality gap.
    """
    cap = topology.capacity
    per_task: List[List[Tuple[int, float]]] = []
    for t in task_list:
        opts = [
            (size, strat.runtime)
            for size, strat in sorted(t.feasible_strategies().items())
            if size <= cap
        ]
        if not opts:
            raise ValueError(f"task {t.name}: no option fits capacity {cap}")
        per_task.append(opts)

    longest = max(min(rt for _, rt in opts) for opts in per_task)
    serial = sum(
        min(rt for _, rt in opts)
        for opts in per_task
        if all(size == cap for size, _ in opts)
    )

    m = Model("spase_lb")
    mk = m.continuous("mk", lb=0.0)
    area = Expr()
    for i, opts in enumerate(per_task):
        xs = [m.continuous(f"x_{i}_{k}", lb=0.0, ub=1.0) for k in range(len(opts))]
        m.add(sum(xs[1:], Expr.of(xs[0])) == 1)
        rt_expr = Expr()
        for xi, (size, rt) in zip(xs, opts):
            rt_expr = rt_expr + xi * rt
            area = area + xi * (size * rt)
        m.add(mk >= rt_expr)
    m.add(mk >= area * (1.0 / cap))
    m.minimize(mk)
    res = m.solve(time_limit=time_limit, relax=True)
    # Only a PROVEN LP optimum is a valid bound — a time-limited feasible
    # primal of a minimization LP upper-bounds the LP optimum and could
    # exceed the true MILP optimum, silently breaking the certificate.
    lp_bound = res.objective if res.status == "optimal" else 0.0
    return max(longest, serial, lp_bound)


def greedy_plan(
    task_list: List, topology: SliceTopology, ordering_slack: float = 0.0,
    weights: Optional[Dict[str, float]] = None,
) -> Plan:
    """List-scheduling fallback: longest task first, earliest feasible
    (block, time) slot, choosing the strategy that minimizes finish time.
    Used when the MILP times out dry — the reference had no fallback and
    would just fail. With ``ordering_slack`` this is exactly the native
    constructor (``spase.cpp`` LPT order + min-finish choice), via the shared
    ``DeviceTimeline`` slot rule. ``weights`` prepends a priority key to the
    LPT order so the fallback respects the service's admission weights."""
    timeline = DeviceTimeline(topology.capacity)
    w = weights or {}
    order = sorted(
        task_list,
        key=lambda t: (
            -w.get(t.name, 0.0),
            -min(s.runtime for s in t.feasible_strategies().values()),
        ),
    )
    assignments: Dict[str, Assignment] = {}
    for t in order:
        best = None  # (finish, start, size, blk, rt)
        for size, strat in sorted(t.feasible_strategies().items()):
            if size > topology.capacity:
                continue
            for blk in topology.blocks(size):
                st = timeline.earliest_free(blk, strat.runtime + ordering_slack)
                fin = st + strat.runtime
                if best is None or fin < best[0]:
                    best = (fin, st, size, blk, strat.runtime)
        if best is None:
            raise ValueError(
                f"task {t.name}: no strategy fits topology capacity {topology.capacity}"
            )
        fin, st, size, blk, rt = best
        timeline.occupy(blk, st, fin + ordering_slack)
        assignments[t.name] = Assignment(size, blk, st, rt)

    makespan = max((a.start + a.runtime for a in assignments.values()), default=0.0)
    plan = Plan(assignments=assignments, makespan=makespan)
    plan.compute_dependencies()
    return plan


def resolve(
    task_list: List,
    topology: SliceTopology,
    previous: Optional[Plan],
    interval: float,
    threshold: float = 0.0,
    time_limit: Optional[float] = None,
    warm_budget_frac: float = 0.25,
    weights: Optional[Dict[str, float]] = None,
    coschedule_exclude=None,
    fusion: Optional[List[List[str]]] = None,
    fusion_exclude=None,
    fusion_fits=None,
) -> Plan:
    """Introspective re-solve with compare-and-swap (``milp.py:354-444``).

    Adopt the fresh plan iff (a) there was no previous plan, (b) the task set
    shrank (``milp.py:376-379``), or (c) the fresh makespan beats the slid-down
    old plan by more than ``threshold`` (``milp.py:394-427``). Otherwise keep
    the old plan with all start times slid down by ``interval``
    (``milp.py:429-442``). The previous plan also warm-starts the re-solve
    (reference ``warmStart=True``, ``milp.py:323``) — and because the warm
    fix-and-optimize plan is a guaranteed-feasible incumbent no worse than
    last interval's schedule, the re-solve only gets ``warm_budget_frac`` of
    the caller's time budget: a long proof phase buys nothing when any
    timeout falls back to the warm plan. This is where the reference's Gurobi
    warm start saved its time too (incumbent reuse, ``milp.py:323``); interval
    re-solves are cheap, only the cold initial solve pays the full budget.
    """
    tl = time_limit
    if previous is not None and time_limit is not None:
        # Reduce the budget only when the warm incumbent actually exists —
        # if the task set changed (new task, choice now infeasible) the
        # fix-and-optimize floor is unavailable and the re-solve must get
        # the full budget like a cold solve. (Arrivals/departures get the
        # insertion-extended incumbent inside solve(), but its quality for a
        # changed set is unproven — full budget is the safe default there.)
        if warm_schedule(task_list, topology, previous) is not None:
            tl = max(1.0, time_limit * warm_budget_frac)
    fresh = solve(task_list, topology, time_limit=tl, warm=previous,
                  weights=weights, coschedule_exclude=coschedule_exclude,
                  fusion=fusion, fusion_exclude=fusion_exclude,
                  fusion_fits=fusion_fits)
    if previous is None:
        return fresh

    prev_names = set(previous.assignments)
    cur_names = {t.name for t in task_list}
    if cur_names - prev_names:
        return fresh  # new tasks appeared: old plan can't cover them
    if len(cur_names) < len(prev_names):
        return fresh  # reference adopts on shrink (``milp.py:376-379``)

    slid = Plan(
        assignments={
            n: Assignment(
                a.apportionment,
                a.block,
                max(0.0, a.start - interval),
                a.runtime,
            )
            for n, a in previous.assignments.items()
            if n in cur_names
        },
        makespan=max(0.0, previous.makespan - interval),
        # surviving co-schedule groups slide with the plan; a group whose
        # partner finished degenerates below 2 members and is dropped
        coschedule=[
            kept
            for grp in previous.coschedule
            if len(kept := [n for n in grp if n in cur_names]) >= 2
        ],
        # surviving fusion groups slide too: a stack whose member finished
        # (or was unfused) shrinks; below 2 members it stops being a stack
        fused=[
            kept
            for grp in previous.fused
            if len(kept := [n for n in grp if n in cur_names]) >= 2
        ],
    )
    if coschedule_exclude:
        # A freshly detached member may still sit in the slid plan's groups
        # (members hold OVERLAPPING assignments, so the group can't just be
        # stripped) — in that case the fresh plan, solved without the
        # excluded pairs, is the only valid choice.
        excl = set(coschedule_exclude)
        if any(excl & set(grp) for grp in slid.coschedule):
            return fresh
    if fusion_exclude:
        # Same rule for a freshly quarantined fusion member: its groupmates
        # hold the stack's shared assignment, so the slid plan cannot simply
        # strip it — only the fresh solve (priced without it) is valid.
        excl = set(fusion_exclude)
        if any(excl & set(grp) for grp in slid.fused):
            return fresh
    slid.compute_dependencies()
    if fresh.makespan < slid.makespan - threshold:
        return fresh
    return slid
