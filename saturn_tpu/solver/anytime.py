"""Anytime tiered solver: deadline-bounded re-solves that scale to 10k jobs.

The SPASE MILP (``solver/milp.py``) assumes the batch fits inside the
execution interval; once the gateway admits thousands of jobs the full
re-solve blows the interval budget (ROADMAP item 1). This front-end
*always* returns a plan inside a caller-supplied deadline by racing down a
quality ladder, cheapest-sufficient tier first:

- **tier 0 — incremental**: warm-started delta re-placement. Survivors keep
  last interval's (size, block) choice; only the delta since the last
  adopted plan (arrivals, evictions, strategy changes) is inserted, each at
  a probe-capped min-finish slot. Extends ``warm_schedule`` below
  ``_INCR_BACKFILL_N`` tasks (backfill quality); above it a frontier
  timeline keeps placement O(block size).
- **tier 1 — hierarchical decomposition**: partition jobs by slice affinity
  (previous block) and preferred size class, solve each partition's MILP
  independently under a per-partition time slice, stitch with a
  conflict-resolving merge (partition start order, min-finish block choice
  on the partition-chosen size). A single-partition instance degenerates to
  the exact MILP — small batches lose nothing.
- **tier 2 — LP relaxation + randomized rounding**: the apportionment LP
  over the Amdahl cost model (per-task fractional size choice + the area
  bound), built directly on scipy arrays (the ``solver/lp`` Expr layer is
  O(terms²) at this scale), then seeded rounding rounds list-scheduled on
  the frontier. Round 0 is the plain greedy, so tier 2 is never worse than
  the floor; the LP optimum doubles as a quality lower bound.
- **tier 3 — greedy floor**: ``milp.greedy_plan`` (backfill) at small N,
  frontier greedy at large N. Never fails; adopted only when every richer
  tier was deadline-starved.

Every produced plan is a plain :class:`~saturn_tpu.solver.milp.Plan` that
passes the ``analysis/plan_verifier`` gate; large plans carry sparse
per-device *chain* dependencies (consecutive occupants of each device)
instead of the O(N²) all-overlapping-pairs edge set — same race-freedom
guarantee (any two tasks sharing a device are connected through that
device's chain), linear size.

``anytime_resolve`` mirrors ``milp.resolve``'s compare-and-swap contract
and is what the orchestrator, the service loop, and the elastic replanner
call; it emits one ``solver_tier`` metrics event per re-solve (tier chosen,
wall time, deadline, job count, quality estimate) — surfaced by
``python -m saturn_tpu.analysis solver``.

Operator knobs (environment):

- ``SATURN_TPU_SOLVE_DEADLINE``: global per-re-solve deadline override in
  seconds (wins over the interval-derived budget at every wired site).
- ``SATURN_TPU_PARTITION_MAX``: max jobs per tier-1 partition (default 10;
  also the size below which an instance is solved exactly).
"""

from __future__ import annotations

import logging
import os
import random
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from saturn_tpu.core.mesh import Block, SliceTopology
from saturn_tpu.solver import milp
from saturn_tpu.solver.milp import Assignment, Plan
from saturn_tpu.utils import metrics

log = logging.getLogger("saturn_tpu")

DEADLINE_ENV = "SATURN_TPU_SOLVE_DEADLINE"
PARTITION_MAX_ENV = "SATURN_TPU_PARTITION_MAX"

TIER_NAMES = {0: "incremental", 1: "partition", 2: "lp_round", 3: "greedy"}

# --- ladder applicability thresholds (calibrated on the bench host; every
# estimate errs high so a tier that starts is expected to finish in budget).
_INCR_BACKFILL_N = 160    # below: tier 0 reuses warm_schedule's backfill rule
_CHAIN_DEP_N = 256        # above: plans carry sparse chain dependencies
_INSERTION_PROBE_CAP = 32  # tier-0 per-newcomer (strategy, block) probe cap
_MIN_PART_SLICE = 0.25    # tier 1 needs at least this much budget/partition
_MAX_PARTS = 48           # beyond this many partitions, stitch overhead wins
_DEFAULT_DEADLINE = 5.0   # only when a site passes neither deadline nor env


def partition_max() -> int:
    try:
        return max(2, int(os.environ.get(PARTITION_MAX_ENV, "10")))
    except ValueError:
        return 10


@dataclass
class AnytimeReport:
    """What the ladder did for one re-solve (attached to the returned plan
    as ``plan.anytime`` and emitted as the ``solver_tier`` metrics event)."""

    tier: int                 # tier that produced the adopted plan
    wall_s: float             # total front-end wall time
    deadline_s: float         # the budget this re-solve was given
    n_tasks: int
    n_loose: int              # delta size seen by tier 0 (0 = full warm)
    makespan: float
    lower_bound: float        # cheap/LP makespan lower bound (0 if unknown)
    quality: Optional[float]  # makespan / lower_bound (>= 1.0; None if no lb)
    tiers_tried: List[int] = field(default_factory=list)
    outcome: str = "fresh"    # "fresh" or "slid" (compare-and-swap kept old)
    # How many of the adopted plan's assignments rest on shardflow
    # cold-start priors (``Strategy.static_prior``) rather than trials —
    # the "this plan is partly an educated guess" signal in solver_tier.
    n_static_prior: int = 0

    @property
    def tier_name(self) -> str:
        return TIER_NAMES.get(self.tier, str(self.tier))

    @property
    def deadline_missed(self) -> bool:
        """True when the front-end overran the budget it was given (the
        ladder's contract is that this never happens; campaign harnesses
        count these as hard failures)."""
        return self.wall_s > self.deadline_s


# ---------------------------------------------------------------------------
# frontier timeline: O(block size) placement for 10k-task plans
# ---------------------------------------------------------------------------

class FrontierTimeline:
    """Per-device next-free-time frontier.

    Unlike :class:`~saturn_tpu.solver.milp.DeviceTimeline` there is no
    backfill — a task starts at the max frontier of its block — which trades
    a little packing quality for O(block size) placement instead of
    O(N log N) per call. The large-N tiers live on this.
    """

    __slots__ = ("free",)

    def __init__(self, capacity: int):
        self.free = [0.0] * capacity

    def earliest_free(self, blk: Block) -> float:
        free = self.free
        return max(free[d] for d in range(blk.offset, blk.end))

    def place(self, blk: Block, runtime: float, slack: float) -> float:
        free = self.free
        st = max(free[d] for d in range(blk.offset, blk.end))
        end = st + runtime + slack
        for d in range(blk.offset, blk.end):
            free[d] = end
        return st


def chain_dependencies(assignments: Dict[str, Assignment],
                       coschedule: Optional[List[List[str]]] = None,
                       fused: Optional[List[List[str]]] = None,
                       ) -> Dict[str, List[str]]:
    """Sparse per-device chain edges: on every device, each occupant depends
    on the previous occupant (start order). Any two tasks whose blocks
    overlap share at least one device, so they are connected through that
    device's chain — the same race-freedom property the O(N²)
    ``Plan.compute_dependencies`` edge set guarantees, at O(total occupancy)
    size. Members of one co-schedule group are exempt, as in the dense form;
    so are members of one FUSION group (they are one stacked program holding
    identical assignments by construction).
    """
    group_of: Dict[str, int] = {}
    for gi, grp in enumerate(coschedule or []):
        for n in grp:
            group_of[n] = gi
    fgroup_of: Dict[str, int] = {}
    for gi, grp in enumerate(fused or []):
        for n in grp:
            fgroup_of[n] = gi
    per_device: Dict[int, List[Tuple[float, str]]] = {}
    for name, a in assignments.items():
        for d in range(a.block.offset, a.block.end):
            per_device.setdefault(d, []).append((a.start, name))
    deps: Dict[str, set] = {name: set() for name in assignments}
    for occ in per_device.values():
        occ.sort()
        for (_, n1), (_, n2) in zip(occ, occ[1:]):
            g1, g2 = group_of.get(n1), group_of.get(n2)
            if g1 is not None and g1 == g2:
                continue
            f1, f2 = fgroup_of.get(n1), fgroup_of.get(n2)
            if f1 is not None and f1 == f2:
                continue
            deps[n2].add(n1)
    return {name: sorted(d) for name, d in deps.items()}


def _finish_plan(assignments: Dict[str, Assignment],
                 coschedule: Optional[List[List[str]]] = None,
                 fused: Optional[List[List[str]]] = None) -> Plan:
    """Wrap assignments in a Plan with scale-appropriate dependencies."""
    makespan = max((a.start + a.runtime for a in assignments.values()),
                   default=0.0)
    plan = Plan(assignments=assignments, makespan=makespan,
                coschedule=list(coschedule or []), fused=list(fused or []))
    if len(assignments) > _CHAIN_DEP_N:
        plan.dependencies = chain_dependencies(assignments, plan.coschedule,
                                               plan.fused)
    else:
        plan.compute_dependencies()
    return plan


def _options_of(task, capacity: int) -> List[Tuple[int, Block, float]]:
    opts = []
    for size, strat in sorted(task.feasible_strategies().items()):
        if size > capacity:
            continue
        for blk in _blocks_cached(size, capacity):
            opts.append((size, blk, strat.runtime))
    return opts


_BLOCK_CACHE: Dict[Tuple[int, int], List[Block]] = {}


def _blocks_cached(size: int, capacity: int) -> List[Block]:
    key = (size, capacity)
    blks = _BLOCK_CACHE.get(key)
    if blks is None:
        blks = [Block(off, size) for off in range(0, capacity, size)
                ] if 0 < size <= capacity else []
        _BLOCK_CACHE[key] = blks
    return blks


def _validate(task_list: Sequence, topology: SliceTopology) -> None:
    for t in task_list:
        feas = t.feasible_strategies()
        if not feas:
            raise ValueError(
                f"task {t.name} has no feasible strategy; run search first")
        if all(size > topology.capacity for size in feas):
            raise ValueError(
                f"task {t.name}: no strategy fits topology capacity "
                f"{topology.capacity}")


def cheap_lower_bound(task_list: Sequence, topology: SliceTopology) -> float:
    """O(N) valid makespan lower bound: longest single task's fastest
    option, and the work-area bound (best-case area / capacity). Loose by
    construction — 'quality vs bound' overstates the true gap."""
    cap = topology.capacity
    longest = 0.0
    area = 0.0
    for t in task_list:
        best_rt = None
        best_area = None
        for size, strat in t.feasible_strategies().items():
            if size > cap:
                continue
            if best_rt is None or strat.runtime < best_rt:
                best_rt = strat.runtime
            a = size * strat.runtime
            if best_area is None or a < best_area:
                best_area = a
        if best_rt is None:
            continue
        longest = max(longest, best_rt)
        area += best_area or 0.0
    return max(longest, area / max(cap, 1))


# ---------------------------------------------------------------------------
# tier 3 — greedy floor
# ---------------------------------------------------------------------------

def fast_greedy_plan(task_list: Sequence, topology: SliceTopology,
                     ordering_slack: float = 1.0,
                     weights: Optional[Dict[str, float]] = None) -> Plan:
    """Frontier list-scheduling floor: priority-then-LPT order, min-finish
    (size, block) choice. Same decision rule as ``milp.greedy_plan`` minus
    backfill — O(N · capacity) total, ~10k tasks in well under a second."""
    cap = topology.capacity
    w = weights or {}
    order = sorted(
        task_list,
        key=lambda t: (
            -w.get(t.name, 0.0),
            -min(s.runtime for s in t.feasible_strategies().values()),
        ),
    )
    timeline = FrontierTimeline(cap)
    free = timeline.free
    assignments: Dict[str, Assignment] = {}
    for t in order:
        best = None  # (finish, start, size, blk, rt)
        for size, strat in sorted(t.feasible_strategies().items()):
            if size > cap:
                continue
            rt = strat.runtime
            for blk in _blocks_cached(size, cap):
                st = max(free[d] for d in range(blk.offset, blk.end))
                fin = st + rt
                if best is None or fin < best[0]:
                    best = (fin, st, size, blk, rt)
        if best is None:
            raise ValueError(
                f"task {t.name}: no strategy fits topology capacity {cap}")
        fin, st, size, blk, rt = best
        end = fin + ordering_slack
        for d in range(blk.offset, blk.end):
            free[d] = end
        assignments[t.name] = Assignment(size, blk, st, rt)
    return _finish_plan(assignments)


def _greedy_floor(task_list, topology, ordering_slack, weights) -> Plan:
    if len(task_list) <= _CHAIN_DEP_N:
        return milp.greedy_plan(task_list, topology, ordering_slack,
                                weights=weights)
    return fast_greedy_plan(task_list, topology, ordering_slack, weights)


# ---------------------------------------------------------------------------
# tier 0 — warm-started incremental delta re-placement
# ---------------------------------------------------------------------------

def split_delta(task_list: Sequence, topology: SliceTopology,
                previous: Optional[Plan]) -> Tuple[List, List]:
    """(pinned, loose): tasks whose previous (size, block) choice is still
    valid vs the delta the incremental tier must re-place."""
    if previous is None:
        return [], list(task_list)
    pinned, loose = [], []
    for t in task_list:
        a = previous.assignments.get(t.name)
        strat = (t.feasible_strategies().get(a.apportionment)
                 if a is not None else None)
        if a is None or strat is None or a.block.end > topology.capacity:
            loose.append(t)
        else:
            pinned.append(t)
    return pinned, loose


def incremental_plan(task_list: Sequence, topology: SliceTopology,
                     previous: Plan, ordering_slack: float = 1.0,
                     weights: Optional[Dict[str, float]] = None,
                     probe_cap: int = _INSERTION_PROBE_CAP,
                     ) -> Optional[Plan]:
    """Tier 0: survivors keep their previous (size, block) in previous start
    order; the delta is inserted at probe-capped min-finish slots. Below
    ``_INCR_BACKFILL_N`` this IS ``warm_schedule(insert_missing=True)``
    (backfill quality); above it, the frontier rule keeps the whole pass
    O(N · block size)."""
    if len(task_list) <= _INCR_BACKFILL_N:
        return milp.warm_schedule(
            task_list, topology, previous, ordering_slack,
            insert_missing=True, weights=weights,
            insertion_probe_cap=probe_cap,
        )

    cap = topology.capacity
    pinned_t, loose = split_delta(task_list, topology, previous)
    pinned: List[Tuple[Any, int, Block, float]] = []
    for t in pinned_t:
        a = previous.assignments[t.name]
        rt = t.feasible_strategies()[a.apportionment].runtime
        pinned.append((t, a.apportionment, a.block, rt))
    pinned.sort(key=lambda p: previous.assignments[p[0].name].start)

    timeline = FrontierTimeline(cap)
    free = timeline.free
    assignments: Dict[str, Assignment] = {}
    for t, size, blk, rt in pinned:
        st = max(free[d] for d in range(blk.offset, blk.end))
        end = st + rt + ordering_slack
        for d in range(blk.offset, blk.end):
            free[d] = end
        assignments[t.name] = Assignment(size, blk, st, rt)

    w = weights or {}
    loose.sort(
        key=lambda t: (
            -w.get(t.name, 0.0),
            -min(s.runtime for s in t.feasible_strategies().values()),
        ),
    )
    for t in loose:
        best = None
        probes = 0
        for size, strat in sorted(t.feasible_strategies().items()):
            if size > cap:
                continue
            rt = strat.runtime
            for blk in _blocks_cached(size, cap):
                if probes >= probe_cap and best is not None:
                    break
                probes += 1
                st = max(free[d] for d in range(blk.offset, blk.end))
                fin = st + rt
                if best is None or fin < best[0]:
                    best = (fin, st, size, blk, rt)
            if probes >= probe_cap and best is not None:
                break
        if best is None:
            return None
        fin, st, size, blk, rt = best
        end = fin + ordering_slack
        for d in range(blk.offset, blk.end):
            free[d] = end
        assignments[t.name] = Assignment(size, blk, st, rt)
    return _finish_plan(assignments)


# ---------------------------------------------------------------------------
# tier 1 — hierarchical decomposition (partition / solve / stitch)
# ---------------------------------------------------------------------------

def _partitions(task_list: Sequence, previous: Optional[Plan],
                max_size: int) -> List[List]:
    """Group by (preferred size class, previous-block slice affinity), then
    chunk each group to ``max_size``. Tasks that shared a block region last
    interval land in one partition, so the per-partition MILP sees the
    ordering conflicts that actually matter."""
    groups: Dict[Tuple[int, int], List] = {}
    for t in task_list:
        feas = t.feasible_strategies()
        pref = min(feas.items(), key=lambda kv: kv[1].runtime)[0]
        a = previous.assignments.get(t.name) if previous is not None else None
        affinity = a.block.offset // max(a.block.size, 1) if a is not None else -1
        groups.setdefault((pref, affinity), []).append(t)
    parts: List[List] = []
    for key in sorted(groups, key=lambda k: (k[0], k[1])):
        grp = groups[key]
        for i in range(0, len(grp), max_size):
            parts.append(grp[i:i + max_size])
    return parts


def partition_plan(task_list: Sequence, topology: SliceTopology,
                   budget: float, ordering_slack: float = 1.0,
                   weights: Optional[Dict[str, float]] = None,
                   previous: Optional[Plan] = None,
                   coschedule_exclude=None,
                   fusion: Optional[List[List[str]]] = None,
                   fusion_exclude=None, fusion_fits=None) -> Optional[Plan]:
    """Tier 1: solve each partition's MILP under its time slice, then stitch.

    The merge keeps each task's partition-chosen apportionment (the
    MILP-optimized size) and its partition-internal start for ordering, then
    re-places every task on the frontier in global start order, choosing the
    min-finish block of the chosen size — always feasible, conflict-free by
    construction. A single partition returns the exact plan untouched
    (co-schedule AND fusion groups included); multi-partition stitches are
    conservatively serial, so co-location and fusion proposals only appear
    at exact scale (the merge's re-placement cannot honor a group's shared
    assignment).
    """
    t0 = time.perf_counter()
    parts = _partitions(task_list, previous, partition_max())
    if len(parts) == 1:
        return milp.solve(task_list, topology,
                          time_limit=max(0.05, budget * 0.9),
                          ordering_slack=ordering_slack, weights=weights,
                          warm=previous, coschedule_exclude=coschedule_exclude,
                          fusion=fusion, fusion_exclude=fusion_exclude,
                          fusion_fits=fusion_fits)

    slice_budget = max(_MIN_PART_SLICE, (budget * 0.8) / len(parts))
    placed: List[Tuple[float, int, Any, int, float]] = []  # (start, pi, task, size, rt)
    for pi, part in enumerate(parts):
        remaining = budget - (time.perf_counter() - t0)
        if remaining > slice_budget * 0.5:
            # A huge min_gain keeps the co-location term out: merge
            # re-placement cannot honor a group's tied starts.
            sub = milp.solve(part, topology,
                             time_limit=min(slice_budget, remaining),
                             ordering_slack=ordering_slack, weights=weights,
                             warm=previous, coschedule_min_gain=1e9)
        else:
            # budget exhausted mid-ladder: the leftovers get the greedy rule
            sub = milp.greedy_plan(part, topology, ordering_slack,
                                   weights=weights)
        for t in part:
            a = sub.assignments[t.name]
            placed.append((a.start, pi, t, a.apportionment, a.runtime))

    # Conflict-resolving merge: zipper all partitions by internal start.
    placed.sort(key=lambda p: (p[0], p[1]))
    cap = topology.capacity
    timeline = FrontierTimeline(cap)
    free = timeline.free
    assignments: Dict[str, Assignment] = {}
    for _, _, t, size, rt in placed:
        best = None  # (finish, start, blk)
        for blk in _blocks_cached(size, cap):
            st = max(free[d] for d in range(blk.offset, blk.end))
            if best is None or st + rt < best[0]:
                best = (st + rt, st, blk)
        if best is None:
            return None
        fin, st, blk = best
        end = fin + ordering_slack
        for d in range(blk.offset, blk.end):
            free[d] = end
        assignments[t.name] = Assignment(size, blk, st, rt)
    return _finish_plan(assignments)


# ---------------------------------------------------------------------------
# tier 2 — LP relaxation + seeded randomized rounding
# ---------------------------------------------------------------------------

def lp_round_plan(task_list: Sequence, topology: SliceTopology,
                  ordering_slack: float = 1.0,
                  weights: Optional[Dict[str, float]] = None,
                  seed: int = 0, rounds: int = 3,
                  time_limit: float = 5.0,
                  ) -> Tuple[Optional[Plan], float]:
    """Tier 2: apportionment LP over the Amdahl cost model, then rounding.

    Blocks of one size are symmetric, so the LP only chooses *sizes*:
    minimize mk s.t. per-task option mix sums to 1, mk >= each task's mixed
    runtime, mk >= selected work area / capacity. Built directly on scipy
    arrays — the ``solver/lp`` Expr layer re-copies coefficient dicts per
    term and is quadratic at 10k x 4 options. Rounding: round 0 is plain
    greedy (floor quality guaranteed); later rounds sample each task's size
    from its LP mix with a seeded RNG and list-schedule min-finish on the
    frontier. Returns ``(best plan, LP lower bound)`` — bound 0.0 when the
    LP failed to prove optimality (a time-limited primal is not a bound).
    """
    try:
        import numpy as np
        from scipy import sparse
        from scipy.optimize import linprog
    except Exception:  # pragma: no cover - scipy is in-image; belt and braces
        return None, 0.0

    cap = topology.capacity
    names: List[str] = []
    per_task: List[List[Tuple[int, float]]] = []
    for t in task_list:
        opts = [(size, strat.runtime)
                for size, strat in sorted(t.feasible_strategies().items())
                if size <= cap]
        if not opts:
            return None, 0.0
        names.append(t.name)
        per_task.append(opts)

    n = len(per_task)
    offsets = [0] * n
    total = 0
    for i, opts in enumerate(per_task):
        offsets[i] = total
        total += len(opts)
    nvar = 1 + total  # [mk, x...]

    c = np.zeros(nvar)
    c[0] = 1.0
    eq_r, eq_c, eq_v = [], [], []
    ub_r, ub_c, ub_v = [], [], []
    for i, opts in enumerate(per_task):
        for k, (size, rt) in enumerate(opts):
            j = 1 + offsets[i] + k
            eq_r.append(i); eq_c.append(j); eq_v.append(1.0)
            ub_r.append(i); ub_c.append(j); ub_v.append(rt)       # mixed rt
            ub_r.append(n); ub_c.append(j); ub_v.append(size * rt / cap)
        ub_r.append(i); ub_c.append(0); ub_v.append(-1.0)         # ... <= mk
    ub_r.append(n); ub_c.append(0); ub_v.append(-1.0)
    A_eq = sparse.coo_matrix((eq_v, (eq_r, eq_c)), shape=(n, nvar)).tocsr()
    A_ub = sparse.coo_matrix((ub_v, (ub_r, ub_c)), shape=(n + 1, nvar)).tocsr()
    bounds = [(0.0, None)] + [(0.0, 1.0)] * total
    try:
        res = linprog(c, A_ub=A_ub, b_ub=np.zeros(n + 1), A_eq=A_eq,
                      b_eq=np.ones(n), bounds=bounds, method="highs",
                      options={"time_limit": max(0.05, time_limit)})
    except (ValueError, TypeError):
        return None, 0.0
    lp_bound = 0.0
    frac: Optional[List[List[float]]] = None
    if res.status == 0 and res.x is not None:
        lp_bound = float(res.fun)
        frac = [
            [max(0.0, float(res.x[1 + offsets[i] + k]))
             for k in range(len(per_task[i]))]
            for i in range(n)
        ]

    # Rounding rounds. Order is priority-then-LPT, shared across rounds.
    w = weights or {}
    order = sorted(
        range(n),
        key=lambda i: (
            -w.get(names[i], 0.0),
            -min(rt for _, rt in per_task[i]),
        ),
    )
    by_name = {t.name: t for t in task_list}
    best_plan: Optional[Plan] = None
    for r in range(max(1, rounds)):
        rng = random.Random((seed << 8) ^ r) if r > 0 else None
        timeline = FrontierTimeline(cap)
        free = timeline.free
        assignments: Dict[str, Assignment] = {}
        for i in order:
            opts = per_task[i]
            if rng is not None and frac is not None and len(opts) > 1:
                u, acc, pick = rng.random(), 0.0, len(opts) - 1
                for k, f in enumerate(frac[i]):
                    acc += f
                    if u <= acc:
                        pick = k
                        break
                cand = [opts[pick]]
            else:
                cand = opts  # round 0 (or no LP mix): greedy over all sizes
            best = None  # (finish, start, size, blk, rt)
            for size, rt in cand:
                for blk in _blocks_cached(size, cap):
                    st = max(free[d] for d in range(blk.offset, blk.end))
                    fin = st + rt
                    if best is None or fin < best[0]:
                        best = (fin, st, size, blk, rt)
            if best is None:
                return None, lp_bound
            fin, st, size, blk, rt = best
            end = fin + ordering_slack
            for d in range(blk.offset, blk.end):
                free[d] = end
            assignments[names[i]] = Assignment(size, blk, st, rt)
        plan = _finish_plan(assignments)
        if best_plan is None or plan.makespan < best_plan.makespan:
            best_plan = plan
    return best_plan, lp_bound


# ---------------------------------------------------------------------------
# the ladder front-end
# ---------------------------------------------------------------------------

def _est_floor(n: int) -> float:
    return 0.005 + 2e-5 * n


def _est_incremental(n: int, n_loose: int) -> float:
    return 0.01 + 1.5e-5 * n + 4e-6 * n_loose * _INSERTION_PROBE_CAP


def _est_lp(n: int) -> float:
    return 0.06 + 2.5e-4 * n


def anytime_solve(task_list: Sequence, topology: SliceTopology,
                  deadline: float, previous: Optional[Plan] = None,
                  ordering_slack: float = 1.0,
                  weights: Optional[Dict[str, float]] = None,
                  coschedule_exclude=None, seed: int = 0,
                  fusion: Optional[List[List[str]]] = None,
                  fusion_exclude=None, fusion_fits=None,
                  ) -> Tuple[Plan, AnytimeReport]:
    """Race down the tier ladder; always returns a plan within ~``deadline``.

    Applicability is cost-model driven: a tier only starts when its
    (conservative) estimate fits the remaining budget after reserving the
    greedy floor, so the floor can always still run. The best-makespan plan
    among the tiers that ran is adopted, and the report records which tier
    produced it.
    """
    t0 = time.perf_counter()
    _validate(task_list, topology)
    n = len(task_list)
    deadline = max(float(deadline), 1e-3)
    floor_est = _est_floor(n)

    def remaining() -> float:
        return deadline - (time.perf_counter() - t0)

    best: Optional[Plan] = None
    best_tier = 3
    tried: List[int] = []
    lp_bound = 0.0

    pinned, loose = split_delta(task_list, topology, previous)
    n_loose = len(loose)

    # tier 0 — incremental (needs a mostly-covering previous plan)
    if (previous is not None and n > 0
            and n_loose <= max(8, n // 4)
            and _est_incremental(n, n_loose) <= remaining() - floor_est):
        tried.append(0)
        p0 = incremental_plan(task_list, topology, previous, ordering_slack,
                              weights, probe_cap=_INSERTION_PROBE_CAP)
        if p0 is not None:
            best, best_tier = p0, 0

    # tier 1 — hierarchical decomposition (budget permitting)
    if n > 0:
        n_parts = max(1, -(-n // partition_max()))
        budget = remaining() - floor_est
        tier1_ok = (n_parts <= _MAX_PARTS
                    and budget >= n_parts * _MIN_PART_SLICE)
        if tier1_ok:
            tried.append(1)
            p1 = partition_plan(task_list, topology, budget, ordering_slack,
                                weights, previous=previous,
                                coschedule_exclude=coschedule_exclude,
                                fusion=fusion, fusion_exclude=fusion_exclude,
                                fusion_fits=fusion_fits)
            if p1 is not None and (best is None or p1.makespan < best.makespan):
                best, best_tier = p1, 1
        elif best is None and remaining() - floor_est >= _est_lp(n):
            # tier 2 — LP + rounding (the mid-scale workhorse)
            tried.append(2)
            p2, lp_bound = lp_round_plan(
                task_list, topology, ordering_slack, weights, seed=seed,
                time_limit=max(0.05, (remaining() - floor_est) * 0.5),
            )
            if p2 is not None and (best is None or p2.makespan < best.makespan):
                best, best_tier = p2, 2

    # tier 3 — the never-fail floor
    if best is None:
        tried.append(3)
        best = _greedy_floor(task_list, topology, ordering_slack, weights)
        best_tier = 3

    lb = max(cheap_lower_bound(task_list, topology), lp_bound) if n else 0.0
    wall = time.perf_counter() - t0
    by_name = {getattr(t, "name", None): t for t in task_list}
    n_static = sum(
        1 for name, a in best.assignments.items()
        if getattr(
            getattr(by_name.get(name), "strategies", {}).get(a.apportionment),
            "static_prior", False,
        )
    )
    report = AnytimeReport(
        tier=best_tier, wall_s=wall, deadline_s=deadline, n_tasks=n,
        n_loose=n_loose, makespan=best.makespan, lower_bound=lb,
        quality=(best.makespan / lb) if lb > 1e-9 else None,
        tiers_tried=tried,
        n_static_prior=n_static,
    )
    best.anytime = report
    return best, report


def resolve_deadline(deadline: Optional[float],
                     interval: Optional[float] = None) -> float:
    """The wired sites' deadline derivation: the explicit env override wins,
    then the caller's budget (the orchestrator/service ``tlimit``, which
    already defaults to interval/2), then half the interval, then a
    conservative default."""
    env = os.environ.get(DEADLINE_ENV)
    if env:
        try:
            return max(1e-3, float(env))
        except ValueError:
            log.warning("ignoring unparsable %s=%r", DEADLINE_ENV, env)
    if deadline is not None:
        return max(1e-3, float(deadline))
    if interval is not None and interval > 0:
        return max(1e-3, interval / 2)
    return _DEFAULT_DEADLINE


def _emit_tier_event(report: AnytimeReport, source: str) -> None:
    metrics.event(
        "solver_tier",
        source=source,
        tier=report.tier,
        tier_name=report.tier_name,
        wall_s=round(report.wall_s, 6),
        deadline_s=round(report.deadline_s, 6),
        n_tasks=report.n_tasks,
        n_loose=report.n_loose,
        makespan_s=round(report.makespan, 6),
        quality=(round(report.quality, 4) if report.quality is not None
                 else None),
        tiers_tried=list(report.tiers_tried),
        outcome=report.outcome,
        n_static_prior=report.n_static_prior,
    )


def anytime_resolve(task_list: Sequence, topology: SliceTopology,
                    previous: Optional[Plan], interval: float,
                    threshold: float = 0.0,
                    deadline: Optional[float] = None,
                    weights: Optional[Dict[str, float]] = None,
                    coschedule_exclude=None,
                    warm: Optional[Plan] = None,
                    ordering_slack: float = 1.0,
                    source: str = "resolve", seed: int = 0,
                    fusion: Optional[List[List[str]]] = None,
                    fusion_exclude=None, fusion_fits=None) -> Plan:
    """Deadline-bounded drop-in for ``milp.resolve``: tier-ladder fresh
    solve + the introspective compare-and-swap, one ``solver_tier`` metrics
    event per call.

    ``previous`` plays its two ``milp.resolve`` roles (warm seed + CAS
    incumbent); pass ``warm`` alone (with ``previous=None``) to seed the
    ladder without the compare-and-swap — the replanner's shape, where the
    old plan may reference dead devices and must never be kept.

    The whole call is one ``solver.resolve`` span (``source``, ``n_tasks``,
    ``deadline_s``, then the adopted plan's ``tier``, ``outcome`` and
    ``makespan_s``), whoever the caller and whichever thread.
    """
    dl = resolve_deadline(deadline, interval)
    with metrics.span("solver.resolve", source=source,
                      n_tasks=len(task_list), deadline_s=round(dl, 6)) as sp:
        plan = _resolve(
            task_list, topology, previous, interval, threshold, dl, weights,
            coschedule_exclude, warm, ordering_slack, source, seed, fusion,
            fusion_exclude, fusion_fits,
        )
        report = getattr(plan, "anytime", None)
        sp.set(makespan_s=round(plan.makespan, 6),
               tier=getattr(report, "tier", None),
               outcome=getattr(report, "outcome", None))
        return plan


def _resolve(task_list, topology, previous, interval, threshold, dl, weights,
             coschedule_exclude, warm, ordering_slack, source, seed, fusion,
             fusion_exclude, fusion_fits) -> Plan:
    warm_seed = warm if warm is not None else previous
    fresh, report = anytime_solve(
        task_list, topology, dl, previous=warm_seed,
        ordering_slack=ordering_slack, weights=weights,
        coschedule_exclude=coschedule_exclude, seed=seed,
        fusion=fusion, fusion_exclude=fusion_exclude,
        fusion_fits=fusion_fits,
    )
    if previous is None:
        _emit_tier_event(report, source)
        return fresh

    prev_names = set(previous.assignments)
    cur_names = {t.name for t in task_list}
    adopt_fresh = bool(cur_names - prev_names) or len(cur_names) < len(prev_names)
    slid: Optional[Plan] = None
    if not adopt_fresh:
        slid = Plan(
            assignments={
                n: Assignment(a.apportionment, a.block,
                              max(0.0, a.start - interval), a.runtime)
                for n, a in previous.assignments.items() if n in cur_names
            },
            makespan=max(0.0, previous.makespan - interval),
            coschedule=[
                kept for grp in previous.coschedule
                if len(kept := [n for n in grp if n in cur_names]) >= 2
            ],
            # surviving fusion groups slide like co-schedule groups; a stack
            # shrunk below 2 members stops being a stack
            fused=[
                kept for grp in previous.fused
                if len(kept := [n for n in grp if n in cur_names]) >= 2
            ],
        )
        if coschedule_exclude:
            excl = set(coschedule_exclude)
            if any(excl & set(grp) for grp in slid.coschedule):
                adopt_fresh = True  # a detached member sits in a slid group
        if fusion_exclude:
            excl = set(fusion_exclude)
            if any(excl & set(grp) for grp in slid.fused):
                adopt_fresh = True  # a quarantined member sits in a slid stack
        if not adopt_fresh:
            if len(slid.assignments) > _CHAIN_DEP_N:
                slid.dependencies = chain_dependencies(slid.assignments,
                                                       slid.coschedule,
                                                       slid.fused)
            else:
                slid.compute_dependencies()
            adopt_fresh = fresh.makespan < slid.makespan - threshold

    if adopt_fresh or slid is None:
        _emit_tier_event(report, source)
        return fresh
    report.outcome = "slid"
    _emit_tier_event(report, source)
    slid.anytime = report
    return slid
