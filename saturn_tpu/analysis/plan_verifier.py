"""Pass 1 — static plan verifier.

Checks any :class:`~saturn_tpu.solver.milp.Plan` — fresh solve, warm
re-solve, journal replay, or migration plan — BEFORE it reaches chips:

- **Launch invariants** (the engine's historical dynamic guard, lifted
  here verbatim so there is exactly one implementation): device-block
  overlap races, dependency cycles over the condensed co-schedule graph,
  and intra-group dependency edges.  ``executor.engine._check_disjoint``
  is now a thin call into :func:`check_launch_invariants`.
- **Structure**: dangling names in ``dependencies``/``coschedule``,
  undersized or overlapping groups.
- **Feasibility** (when a :class:`SliceTopology` and/or task list is
  supplied): blocks inside the buddy capacity, apportionment == block
  size, a feasible strategy at the assigned size, co-schedule
  host-fraction preconditions.
- **Timeline**: non-negative starts/runtimes, start order consistent
  with dependency edges, makespan and deadline arithmetic.

Everything here is pure Python over plan/topology data — no JAX, no
solver import — so it runs on any CPU in microseconds and is safe to
call from every plan-adoption site.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from saturn_tpu.analysis.diagnostics import (
    AnalysisReport,
    Diagnostic,
    PlanVerificationError,
    make,
)


# ---------------------------------------------------------------------------
# condensed co-schedule graph (shared with the engine)
# ---------------------------------------------------------------------------

def coschedule_find(names: Iterable[str], plan: Any) -> Callable[[str], str]:
    """Union-find root function over the plan's co-schedule AND fusion
    groups, restricted to ``names``.  Members of one group are one condensed
    node: co-schedule members run interleaved on one shared launcher and
    fusion members run as ONE stacked program, so ordering and race
    properties are checked between groups, never inside one.  Groups that
    share a member merge (one launcher must own a task).

    This is THE implementation — ``engine._coschedule_find`` delegates
    here so the dynamic guard and the static verifier cannot drift.
    """
    running = set(names)
    parent: Dict[str, str] = {n: n for n in running}

    def find(n: str) -> str:
        while parent[n] != n:
            parent[n] = parent[parent[n]]  # path halving
            n = parent[n]
        return n

    for attr in ("coschedule", "fused"):
        for grp in getattr(plan, attr, None) or []:
            members = [n for n in grp if n in running]
            for a, b in zip(members, members[1:]):
                ra, rb = find(a), find(b)
                if ra != rb:
                    parent[ra] = rb
    return find


#: Above this many gang members the O(N²)-pairs + transitive-closure exact
#: check hands off to the per-device sweep (same guarantees for every
#: solver-produced plan; see :func:`_launch_diagnostics_sweep`).
SWEEP_THRESHOLD = 256


def launch_diagnostics(names: Sequence[str], plan: Any, *,
                       force_exact: bool = False,
                       force_sweep: bool = False) -> List[Diagnostic]:
    """The engine's gang-launch invariants as structured diagnostics, in
    the exact order the dynamic guard historically checked (and raised)
    them: intra-group edges, then cycles, then pairwise races.

    The MILP's plans satisfy all three by construction; a hand-built or
    corrupted plan that violates them would either run two XLA programs on
    the same chips concurrently (silent corruption, not a crash) or park
    launcher threads on events that never fire (silent hang).

    Above :data:`SWEEP_THRESHOLD` tasks the exact pairwise check (O(N²)
    pairs plus a transitive closure) is replaced by a per-device sweep that
    is linear in total device occupancy — the anytime solver's 5k-10k-job
    plans verify in milliseconds instead of minutes. The sweep is *sound*
    (it never accepts a plan with a device race the exact check would
    reject) but stricter: it demands a DIRECT ordering edge between
    consecutive occupants of each device, which every solver-produced
    dependency shape provides (all-overlapping-pairs edges and per-device
    chain edges alike). ``force_exact``/``force_sweep`` pin the mode for
    tests and offline audits.
    """
    if force_sweep or (not force_exact and len(set(names)) > SWEEP_THRESHOLD):
        return _launch_diagnostics_sweep(names, plan)
    out: List[Diagnostic] = []
    running = set(names)
    order = list(dict.fromkeys(names))  # stable de-duped iteration order
    find = coschedule_find(running, plan)

    cdeps: Dict[str, set] = {find(n): set() for n in order}
    for n in order:
        rn = find(n)
        for d in plan.dependencies.get(n, ()):
            if d not in running:
                continue
            rd = find(d)
            if rd == rn:
                if d != n:
                    out.append(make(
                        "SAT-P003", "error",
                        f"plan makes co-scheduled task {n!r} depend on its "
                        f"groupmate {d!r}: group members run interleaved on "
                        "one launcher, so an intra-group completion wait "
                        "would deadlock the group",
                        counterexample={"task": n, "groupmate": d},
                        category="launch",
                    ))
                continue
            cdeps[rn].add(rd)

    # Reachability over the condensed dependency DAG; cycle check rides
    # the same DFS (a node reaching itself).
    reach: Dict[str, set] = {}

    def reachable(r: str) -> set:
        if r in reach:
            return reach[r]
        reach[r] = set()  # placeholder breaks self-recursion on cycles
        out_set = set()
        for d in cdeps[r]:
            out_set.add(d)
            out_set |= reachable(d)
        reach[r] = out_set
        return out_set

    for r in cdeps:
        if r in reachable(r):
            out.append(make(
                "SAT-P002", "error",
                f"plan dependency cycle through task {r!r}: the gang "
                "launch would deadlock (every thread in the cycle waits "
                "on another's completion event)",
                counterexample={"cycle_witness": r,
                                "cycle_nodes": sorted(
                                    n for n in cdeps if r in reachable(n)
                                    and n in reachable(r) or n == r)},
                category="launch",
            ))
            break  # one witness is the minimal counterexample

    items = [(n, plan.assignments.get(n)) for n in order]
    for i, (n1, a1) in enumerate(items):
        if a1 is None:
            continue
        for n2, a2 in items[i + 1:]:
            if a2 is None or not a1.block.overlaps(a2.block):
                continue
            r1, r2 = find(n1), find(n2)
            if r1 == r2:
                continue  # co-scheduled: the shared block is the point
            if r1 not in reachable(r2) and r2 not in reachable(r1):
                out.append(make(
                    "SAT-P001", "error",
                    f"plan races tasks {n1!r} and {n2!r}: blocks "
                    f"[{a1.block.offset}:{a1.block.end}] and "
                    f"[{a2.block.offset}:{a2.block.end}] overlap with no "
                    "ordering path or co-schedule edge between them",
                    counterexample={
                        "tasks": [n1, n2],
                        "blocks": [[a1.block.offset, a1.block.end],
                                   [a2.block.offset, a2.block.end]],
                    },
                    category="launch",
                ))
    return out


def _launch_diagnostics_sweep(names: Sequence[str],
                              plan: Any) -> List[Diagnostic]:
    """Large-N launch check: per-device start-order sweep, O(occupancy log).

    Invariants checked (same codes as the exact path):

    - SAT-P003: intra-group dependency edges (identical logic, O(E));
    - SAT-P002: cycles via Kahn's toposort over the condensed graph
      (O(V + E), no transitive closure);
    - SAT-P001: on every device, consecutive occupants in start order must
      be directly ordered by a condensed dependency edge (either direction)
      or share a co-schedule group. A direct edge between every
      same-device-adjacent pair chains into a path between EVERY pair of
      tasks sharing that device, so acceptance implies the exact path's
      race-freedom. Solver-produced plans always carry such edges (the
      dense form links every overlapping pair; the sparse form links
      exactly these neighbors); a hand-built plan relying on a longer
      transitive detour is rejected here — quarantine-safe, and such plans
      only reach this path above SWEEP_THRESHOLD tasks.
    """
    out: List[Diagnostic] = []
    running = set(names)
    order = list(dict.fromkeys(names))
    find = coschedule_find(running, plan)

    cdeps: Dict[str, set] = {find(n): set() for n in order}
    for n in order:
        rn = find(n)
        for d in plan.dependencies.get(n, ()):
            if d not in running:
                continue
            rd = find(d)
            if rd == rn:
                if d != n:
                    out.append(make(
                        "SAT-P003", "error",
                        f"plan makes co-scheduled task {n!r} depend on its "
                        f"groupmate {d!r}: group members run interleaved on "
                        "one launcher, so an intra-group completion wait "
                        "would deadlock the group",
                        counterexample={"task": n, "groupmate": d},
                        category="launch",
                    ))
                continue
            cdeps[rn].add(rd)

    # Kahn's toposort for cycle detection (linear, closure-free).
    indeg: Dict[str, int] = {r: 0 for r in cdeps}
    for r, ds in cdeps.items():
        for d in ds:
            if d in indeg:
                indeg[d] += 1
    queue = [r for r, k in indeg.items() if k == 0]
    seen = 0
    while queue:
        u = queue.pop()
        seen += 1
        for d in cdeps[u]:
            if d in indeg:
                indeg[d] -= 1
                if indeg[d] == 0:
                    queue.append(d)
    if seen != len(cdeps):
        stuck = sorted(r for r, k in indeg.items() if k > 0)
        out.append(make(
            "SAT-P002", "error",
            f"plan dependency cycle through task {stuck[0]!r}: the gang "
            "launch would deadlock (every thread in the cycle waits "
            "on another's completion event)",
            counterexample={"cycle_witness": stuck[0],
                            "cycle_nodes": stuck},
            category="launch",
        ))

    # Per-device sweep: adjacent occupants must be directly ordered.
    per_device: Dict[int, List[Tuple[float, str]]] = {}
    for n in order:
        a = plan.assignments.get(n)
        if a is None:
            continue
        for d in range(a.block.offset, a.block.end):
            per_device.setdefault(d, []).append((a.start, n))
    flagged: set = set()
    for occ in per_device.values():
        occ.sort()
        for (_, n1), (_, n2) in zip(occ, occ[1:]):
            r1, r2 = find(n1), find(n2)
            if r1 == r2:
                continue  # co-scheduled: the shared block is the point
            if r1 in cdeps.get(r2, ()) or r2 in cdeps.get(r1, ()):
                continue
            key = (n1, n2) if n1 <= n2 else (n2, n1)
            if key in flagged:
                continue
            flagged.add(key)
            a1, a2 = plan.assignments[n1], plan.assignments[n2]
            out.append(make(
                "SAT-P001", "error",
                f"plan races tasks {n1!r} and {n2!r}: blocks "
                f"[{a1.block.offset}:{a1.block.end}] and "
                f"[{a2.block.offset}:{a2.block.end}] overlap with no "
                "ordering path or co-schedule edge between them",
                counterexample={
                    "tasks": [n1, n2],
                    "blocks": [[a1.block.offset, a1.block.end],
                               [a2.block.offset, a2.block.end]],
                },
                category="launch",
            ))
    return out


def check_launch_invariants(names: Sequence[str], plan: Any) -> None:
    """Raise ``RuntimeError`` on the FIRST launch-invariant violation, with
    the dynamic guard's historical message — the engine's refusal path.
    """
    for diag in launch_diagnostics(names, plan):
        raise RuntimeError(diag.message)


# ---------------------------------------------------------------------------
# full static verification
# ---------------------------------------------------------------------------

def _structure_diagnostics(plan: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    known = set(plan.assignments)
    for n, deps in (plan.dependencies or {}).items():
        for d in deps:
            if d not in known:
                out.append(make(
                    "SAT-P010", "warning",
                    f"dependency of {n!r} names unknown task {d!r} "
                    "(no assignment in the plan)",
                    counterexample={"task": n, "unknown": d},
                    category="structure",
                ))
    seen_members: Dict[str, int] = {}
    for gi, grp in enumerate(getattr(plan, "coschedule", None) or []):
        for m in grp:
            if m not in known:
                out.append(make(
                    "SAT-P011", "warning",
                    f"co-schedule group {gi} names unknown task {m!r}",
                    counterexample={"group": gi, "unknown": m},
                    category="structure",
                ))
            if m in seen_members and seen_members[m] != gi:
                out.append(make(
                    "SAT-P013", "warning",
                    f"task {m!r} appears in co-schedule groups "
                    f"{seen_members[m]} and {gi} — the engine merges them "
                    "into one launcher",
                    counterexample={"task": m,
                                    "groups": [seen_members[m], gi]},
                    category="structure",
                ))
            seen_members.setdefault(m, gi)
        if len([m for m in grp if m in known]) < 2:
            out.append(make(
                "SAT-P012", "warning",
                f"co-schedule group {gi} has fewer than two assigned "
                "members — nothing to interleave",
                counterexample={"group": gi, "members": list(grp)},
                category="structure",
            ))
    seen_fused: Dict[str, int] = {}
    for gi, grp in enumerate(getattr(plan, "fused", None) or []):
        for m in grp:
            if m not in known:
                out.append(make(
                    "SAT-P014", "warning",
                    f"fusion group {gi} names unknown task {m!r}",
                    counterexample={"group": gi, "unknown": m},
                    category="structure",
                ))
            if m in seen_fused and seen_fused[m] != gi:
                out.append(make(
                    "SAT-P016", "warning",
                    f"task {m!r} appears in fusion groups {seen_fused[m]} "
                    f"and {gi} — one task can belong to only one stacked "
                    "program",
                    counterexample={"task": m,
                                    "groups": [seen_fused[m], gi]},
                    category="structure",
                ))
            seen_fused.setdefault(m, gi)
        if len([m for m in grp if m in known]) < 2:
            out.append(make(
                "SAT-P015", "warning",
                f"fusion group {gi} has fewer than two assigned members — "
                "nothing to stack",
                counterexample={"group": gi, "members": list(grp)},
                category="structure",
            ))
    return out


def _feasibility_diagnostics(plan: Any, topology: Any,
                             tasks: Optional[Sequence[Any]]) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    capacity = getattr(topology, "capacity", None)
    by_name = {getattr(t, "name", None): t for t in (tasks or [])}
    for n, a in plan.assignments.items():
        if capacity is not None and a.block.end > capacity:
            out.append(make(
                "SAT-P020", "error",
                f"assignment for {n!r} occupies devices "
                f"[{a.block.offset}:{a.block.end}] but the topology's buddy "
                f"capacity is {capacity}",
                counterexample={"task": n,
                                "block": [a.block.offset, a.block.end],
                                "capacity": capacity},
                category="feasibility",
            ))
        if a.apportionment != a.block.size:
            out.append(make(
                "SAT-P021", "error",
                f"assignment for {n!r} apportions {a.apportionment} chips "
                f"but its block holds {a.block.size}: the profiled strategy "
                "would run on a mesh it was never measured for",
                counterexample={"task": n, "apportionment": a.apportionment,
                                "block_size": a.block.size},
                category="feasibility",
            ))
        t = by_name.get(n)
        if t is not None:
            strat = getattr(t, "strategies", {}).get(a.apportionment)
            if strat is None or not getattr(strat, "feasible", True):
                out.append(make(
                    "SAT-P022", "error",
                    f"task {n!r} has no feasible strategy at apportionment "
                    f"{a.apportionment} — the plan schedules a configuration "
                    "the sweep rejected or never measured",
                    counterexample={"task": n,
                                    "apportionment": a.apportionment,
                                    "known_sizes": sorted(
                                        getattr(t, "strategies", {}))},
                    category="feasibility",
                ))
    for gi, grp in enumerate(getattr(plan, "coschedule", None) or []):
        assigned = [(m, plan.assignments[m]) for m in grp
                    if m in plan.assignments]
        blocks = {(a.block.offset, a.block.size) for _, a in assigned}
        if len(blocks) > 1:
            out.append(make(
                "SAT-P023", "warning",
                f"co-schedule group {gi} members do not share one device "
                "block — interleaving only hides bubbles when the group is "
                "co-located",
                counterexample={"group": gi,
                                "blocks": sorted(blocks)},
                category="feasibility",
            ))
        for m, a in assigned:
            t = by_name.get(m)
            if t is None:
                continue
            strat = getattr(t, "strategies", {}).get(a.apportionment)
            hf = getattr(strat, "host_fraction", 0.0) if strat else 0.0
            # A pipeline job with a measured-zero host fraction can still be
            # a legitimate co-schedule member: its analytic schedule bubble
            # (GPipe/1F1B warmup-cooldown) is the gap the partner fills.
            bubble = getattr(strat, "bubble_fraction", 0.0) if strat else 0.0
            if (not hf or hf <= 0.0) and (not bubble or bubble <= 0.0):
                out.append(make(
                    "SAT-P024", "warning",
                    f"co-scheduled task {m!r} has no measured host fraction "
                    "or schedule bubble at its apportionment — the "
                    "co-location term had no idle window to fill",
                    counterexample={"task": m, "group": gi,
                                    "apportionment": a.apportionment},
                    category="feasibility",
                ))
    for gi, grp in enumerate(getattr(plan, "fused", None) or []):
        assigned = [(m, plan.assignments[m]) for m in grp
                    if m in plan.assignments]
        slots = {(a.apportionment, a.block.offset, a.block.size, a.start)
                 for _, a in assigned}
        if len(slots) > 1:
            out.append(make(
                "SAT-P025", "error",
                f"fusion group {gi} members do not hold IDENTICAL "
                "(size, block, start) assignments — a stacked program is "
                "one compiled step on one sub-mesh; divergent slots would "
                "dispatch the same stack twice",
                counterexample={"group": gi, "slots": sorted(slots)},
                category="feasibility",
            ))
        for m, a in assigned:
            t = by_name.get(m)
            if t is None:
                continue
            strat = getattr(t, "strategies", {}).get(a.apportionment)
            fpbt = getattr(strat, "fused_per_batch_time", None) if strat else None
            if fpbt is None:
                out.append(make(
                    "SAT-P026", "warning",
                    f"fused task {m!r} has no measured fused_per_batch_time "
                    "at its apportionment — the fusion pre-pass prices "
                    "strictly on measured lockstep cost, so this group was "
                    "fused on guesswork",
                    counterexample={"task": m, "group": gi,
                                    "apportionment": a.apportionment},
                    category="feasibility",
                ))
    return out


def _timeline_diagnostics(plan: Any,
                          tasks: Optional[Sequence[Any]]) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    by_name = {getattr(t, "name", None): t for t in (tasks or [])}
    last_end = 0.0
    for n, a in plan.assignments.items():
        if a.start < 0 or a.runtime < 0:
            out.append(make(
                "SAT-P030", "error",
                f"assignment for {n!r} has negative timing "
                f"(start={a.start}, runtime={a.runtime})",
                counterexample={"task": n, "start": a.start,
                                "runtime": a.runtime},
                category="timeline",
            ))
        last_end = max(last_end, a.start + max(a.runtime, 0.0))
        for d in plan.dependencies.get(n, ()):
            da = plan.assignments.get(d)
            if da is not None and a.start < da.start:
                out.append(make(
                    "SAT-P031", "error",
                    f"task {n!r} starts at {a.start:.1f}s but depends on "
                    f"{d!r} which starts later ({da.start:.1f}s) — the "
                    "schedule contradicts its own ordering edges",
                    counterexample={"task": n, "start": a.start,
                                    "dep": d, "dep_start": da.start},
                    category="timeline",
                ))
        t = by_name.get(n)
        deadline = getattr(t, "deadline", None) if t is not None else None
        if deadline is None and t is not None:
            hints = getattr(t, "hints", None) or {}
            deadline = hints.get("deadline") if isinstance(hints, dict) else None
        if isinstance(deadline, (int, float)) and deadline > 0:
            if a.start + a.runtime > float(deadline):
                out.append(make(
                    "SAT-P033", "warning",
                    f"task {n!r} is scheduled to finish at "
                    f"{a.start + a.runtime:.1f}s, past its deadline "
                    f"{float(deadline):.1f}s",
                    counterexample={"task": n,
                                    "finish": a.start + a.runtime,
                                    "deadline": float(deadline)},
                    category="timeline",
                ))
    makespan = getattr(plan, "makespan", None)
    if isinstance(makespan, (int, float)) and last_end > makespan + 1e-6:
        out.append(make(
            "SAT-P032", "warning",
            f"recorded makespan {makespan:.1f}s is below the last "
            f"assignment's end {last_end:.1f}s — stale after a slide or "
            "hand edit",
            counterexample={"makespan": makespan, "last_end": last_end},
            category="timeline",
        ))
    return out


def verify_plan(plan: Any, topology: Any = None,
                tasks: Optional[Sequence[Any]] = None,
                names: Optional[Sequence[str]] = None,
                subject: str = "plan") -> AnalysisReport:
    """Full static verification of one plan.

    ``topology``/``tasks`` unlock the feasibility checks; without them only
    launch, structure and timeline invariants run (exactly what a journal
    audit can check offline).  ``names`` restricts the launch invariants to
    a subset (the engine passes this interval's gang); default is every
    assigned task.
    """
    report = AnalysisReport(subject=subject)
    launch_names = list(names) if names is not None else list(plan.assignments)
    report.extend(launch_diagnostics(launch_names, plan))
    report.extend(_structure_diagnostics(plan))
    if topology is not None or tasks is not None:
        report.extend(_feasibility_diagnostics(plan, topology, tasks))
    report.extend(_timeline_diagnostics(plan, tasks))
    return report


def verify_or_raise(plan: Any, topology: Any = None,
                    tasks: Optional[Sequence[Any]] = None,
                    names: Optional[Sequence[str]] = None,
                    source: str = "plan") -> AnalysisReport:
    """The mandatory adoption gate: verify, raise
    :class:`PlanVerificationError` on any error-severity diagnostic,
    return the report (warnings and all) otherwise.
    """
    report = verify_plan(plan, topology=topology, tasks=tasks, names=names,
                         subject=source)
    if not report.ok:
        raise PlanVerificationError(report, source=source)
    return report


# ---------------------------------------------------------------------------
# journal audit
# ---------------------------------------------------------------------------

def audit_journal(root: str, topology: Any = None,
                  tasks: Optional[Sequence[Any]] = None) -> AnalysisReport:
    """Audit every ``plan_commit`` record in a durability journal.

    Used by durability recovery (quarantine gate) and the CLI's ``journal``
    subcommand: a crash must never resurrect a plan the verifier rejects.
    """
    report = AnalysisReport(subject=f"journal:{root}")
    try:
        from saturn_tpu.durability import journal as _journal
        records = _journal.replay(root)
    except Exception as e:  # unreadable tree, corrupt segment past quarantine
        report.add(make(
            "SAT-J002", "error",
            f"journal at {root!r} unreadable: {type(e).__name__}: {e}",
            category="journal",
        ))
        return report
    from saturn_tpu.solver import milp
    n_plans = 0
    for rec in records:
        if rec.get("kind") != "plan_commit":
            continue
        n_plans += 1
        seq = rec.get("seq")
        payload = (rec.get("data") or {}).get("plan")
        try:
            plan = milp.Plan.from_json(payload)
        except Exception as e:
            report.add(make(
                "SAT-J002", "error",
                f"plan_commit seq={seq} undecodable: "
                f"{type(e).__name__}: {e}",
                counterexample={"seq": seq},
                category="journal",
            ))
            continue
        sub = verify_plan(plan, topology=topology, tasks=tasks,
                          subject=f"plan_commit seq={seq}")
        if not sub.ok:
            report.add(make(
                "SAT-J001", "error",
                f"plan_commit seq={seq} fails static verification "
                f"({[d.code for d in sub.errors]}) — quarantine on replay",
                counterexample={"seq": seq,
                                "codes": [d.code for d in sub.errors]},
                category="journal",
            ))
        report.extend(sub.diagnostics)
    if n_plans == 0:
        report.add(make(
            "SAT-J000", "info",
            f"journal at {root!r} holds no plan_commit records",
            category="journal",
        ))
    return report
