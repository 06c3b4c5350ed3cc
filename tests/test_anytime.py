"""Anytime tier-ladder tests: deadline races, tier equivalence vs the exact
MILP, and verifier compliance on randomized instances.

Hardware-free (solver consumes only numbers), same layer as
``test_solver.py``; the randomized-instance sweep reuses the
differential-oracle idiom from ``test_analysis_differential.py`` — generate
many random instances, run every tier, and hold each output to the same
``plan_verifier`` gate the orchestrator enforces at adoption.
"""

import os
import random

import pytest

from saturn_tpu.analysis import plan_verifier
from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.core.strategy import Strategy
from saturn_tpu.solver import anytime, milp
from saturn_tpu.utils import metrics


class FakeDev:
    pass


def topo(n=8):
    return SliceTopology([FakeDev() for _ in range(n)])


class FakeTask:
    """Solver-facing duck type: only .name and .feasible_strategies()."""

    def __init__(self, name, runtimes):
        self.name = name
        self.strategies = {
            g: Strategy(object(), g, {}, rt, 0.1) for g, rt in runtimes.items()
        }

    def feasible_strategies(self):
        return self.strategies


def rand_tasks(rng, n, prefix="t"):
    """Amdahl-shaped random instances: bigger slices are faster but with
    diminishing returns, like the profiled strategies the solver really sees."""
    out = []
    for i in range(n):
        base = rng.uniform(2.0, 40.0)
        out.append(FakeTask(f"{prefix}{i}", {
            2: base,
            4: base * rng.uniform(0.55, 0.8),
            8: base * rng.uniform(0.35, 0.6),
        }))
    return out


def verify(plan, tp, tasks):
    plan_verifier.verify_or_raise(plan, tp, tasks=tasks)


@pytest.mark.solver
class TestProbeCap:
    """Satellite: warm_schedule(insert_missing=) per-insertion search cap."""

    def _count_probes(self, monkeypatch, cap):
        tp = topo(8)
        rng = random.Random(3)
        old = rand_tasks(rng, 6)
        prev = milp.greedy_plan(old, tp)
        newcomers = rand_tasks(rng, 4, prefix="new")

        counts = {"n": 0}
        orig = milp.DeviceTimeline.earliest_free

        def counting(self, blk, dur):
            counts["n"] += 1
            return orig(self, blk, dur)

        # patched only around warm_schedule: earliest_free is exactly the
        # per-insertion probe (pinned tasks go through place(), not probes)
        monkeypatch.setattr(milp.DeviceTimeline, "earliest_free", counting)
        plan = milp.warm_schedule(old + newcomers, tp, prev,
                                  insert_missing=True,
                                  insertion_probe_cap=cap)
        monkeypatch.undo()
        return plan, counts["n"]

    def test_cap_bounds_probe_work(self, monkeypatch):
        uncapped, n_uncapped = self._count_probes(monkeypatch, None)
        capped, n_capped = self._count_probes(monkeypatch, 3)
        # 6 pinned re-placements (place() probes once each) are constant;
        # insertion work: 4 newcomers x (4+2+1=7 block slots) uncapped vs
        # 4 x cap=3 capped
        assert n_uncapped == 6 + 4 * 7
        assert n_capped == 6 + 4 * 3
        # the cap bounds work, never placement: every task still lands
        assert len(capped.assignments) == len(uncapped.assignments) == 10

    def test_cap_is_deterministic(self):
        tp = topo(8)
        rng = random.Random(5)
        old = rand_tasks(rng, 5)
        prev = milp.greedy_plan(old, tp)
        tasks = old + rand_tasks(rng, 5, prefix="new")
        a = milp.warm_schedule(tasks, tp, prev, insert_missing=True,
                               insertion_probe_cap=4)
        b = milp.warm_schedule(tasks, tp, prev, insert_missing=True,
                               insertion_probe_cap=4)
        assert {n: (x.apportionment, x.block.offset, x.start)
                for n, x in a.assignments.items()} == \
               {n: (x.apportionment, x.block.offset, x.start)
                for n, x in b.assignments.items()}

    def test_cap_never_strands_a_schedulable_task(self):
        tp = topo(8)
        rng = random.Random(7)
        old = rand_tasks(rng, 4)
        prev = milp.greedy_plan(old, tp)
        tasks = old + rand_tasks(rng, 6, prefix="new")
        plan = milp.warm_schedule(tasks, tp, prev, insert_missing=True,
                                  insertion_probe_cap=1)
        assert plan is not None
        assert set(plan.assignments) == {t.name for t in tasks}
        verify(plan, tp, tasks)


@pytest.mark.solver
class TestTierEquivalence:
    """On instances the exact MILP can solve, every richer tier stays within
    a bounded makespan ratio — the ladder degrades gracefully, not wildly."""

    EXACT_S = 2.0

    def _exact(self, tasks, tp):
        return milp.solve(tasks, tp, time_limit=self.EXACT_S)

    def test_tier0_incremental_matches_exact_structure(self):
        rng = random.Random(11)
        for k in range(4):
            tp = topo(8)
            tasks = rand_tasks(rng, rng.randint(6, 12), prefix=f"i{k}-")
            exact = self._exact(tasks, tp)
            p0 = anytime.incremental_plan(tasks, tp, exact)
            assert p0 is not None
            verify(p0, tp, tasks)
            # re-list-scheduling the exact structure costs only ordering slack
            assert p0.makespan <= exact.makespan * 1.5 + 8.0

    def test_tier1_partition_within_bound(self, monkeypatch):
        monkeypatch.setenv(anytime.PARTITION_MAX_ENV, "4")  # force stitching
        rng = random.Random(13)
        for k in range(3):
            tp = topo(8)
            tasks = rand_tasks(rng, 12, prefix=f"p{k}-")
            exact = self._exact(tasks, tp)
            p1 = anytime.partition_plan(tasks, tp, budget=3.0)
            assert p1 is not None
            verify(p1, tp, tasks)
            assert p1.makespan <= exact.makespan * 1.5 + 8.0

    def test_tier1_single_partition_is_exact(self):
        rng = random.Random(17)
        tp = topo(8)
        tasks = rand_tasks(rng, 6)
        exact = self._exact(tasks, tp)
        p1 = anytime.partition_plan(tasks, tp, budget=self.EXACT_S / 0.9)
        assert abs(p1.makespan - exact.makespan) <= 1e-6

    def test_tier2_lp_round_within_bound(self):
        rng = random.Random(19)
        for k in range(4):
            tp = topo(8)
            tasks = rand_tasks(rng, rng.randint(6, 12), prefix=f"l{k}-")
            exact = self._exact(tasks, tp)
            p2, lb = anytime.lp_round_plan(tasks, tp, seed=k)
            assert p2 is not None
            verify(p2, tp, tasks)
            assert p2.makespan <= exact.makespan * 2.0 + 8.0
            # the LP optimum is a true lower bound when it proved optimality
            if lb > 0:
                assert lb <= exact.makespan + 1e-6


@pytest.mark.solver
class TestRandomizedVerifierSweep:
    """500 random instances: every tier's output passes the adoption gate."""

    N = 500

    def test_all_tiers_verify(self):
        rng = random.Random(23)
        milp_budget_used = 0
        for k in range(self.N):
            tp = topo(8)
            tasks = rand_tasks(rng, rng.randint(2, 10), prefix=f"r{k}-")
            floor = anytime.fast_greedy_plan(tasks, tp)
            verify(floor, tp, tasks)
            p2, _ = anytime.lp_round_plan(tasks, tp, seed=k, rounds=2)
            assert p2 is not None
            verify(p2, tp, tasks)
            p0 = anytime.incremental_plan(tasks, tp, floor)
            assert p0 is not None
            verify(p0, tp, tasks)
            # stitch path with the budget-exhausted greedy rule (fast); the
            # MILP-in-partition variant is budgeted to a small subsample
            os.environ[anytime.PARTITION_MAX_ENV] = "3"
            try:
                if milp_budget_used < 5 and len(tasks) >= 6:
                    p1 = anytime.partition_plan(tasks, tp, budget=1.0)
                    milp_budget_used += 1
                else:
                    p1 = anytime.partition_plan(tasks, tp, budget=1e-6)
                assert p1 is not None
                verify(p1, tp, tasks)
            finally:
                del os.environ[anytime.PARTITION_MAX_ENV]

    def test_ladder_front_end_verifies_and_meets_deadline(self):
        rng = random.Random(29)
        prev = None
        for k in range(40):
            tp = topo(8)
            tasks = rand_tasks(rng, rng.randint(2, 10), prefix=f"f{k}-")
            plan, report = anytime.anytime_solve(tasks, tp, 0.5, previous=prev)
            verify(plan, tp, tasks)
            assert report.wall_s <= 0.5 + 0.1
            prev = plan


@pytest.mark.solver
class TestDeadlineLadder:
    def test_greedy_only_when_starved(self):
        """The floor fires iff the deadline can't afford any richer tier."""
        rng = random.Random(31)
        tp = topo(8)
        tasks = rand_tasks(rng, 400)
        _, starved = anytime.anytime_solve(tasks, tp, deadline=1e-3)
        assert starved.tier == 3
        assert starved.tiers_tried == [3]
        _, roomy = anytime.anytime_solve(tasks, tp, deadline=5.0)
        assert roomy.tier != 3

    def test_incremental_preferred_with_covering_previous(self):
        rng = random.Random(37)
        tp = topo(8)
        tasks = rand_tasks(rng, 300)
        first, _ = anytime.anytime_solve(tasks, tp, deadline=1.0)
        grown = tasks + rand_tasks(rng, 10, prefix="new")
        plan, report = anytime.anytime_solve(grown, tp, deadline=1.0,
                                             previous=first)
        assert 0 in report.tiers_tried
        assert report.n_loose == 10
        verify(plan, tp, grown)

    def test_deadline_env_override(self, monkeypatch):
        monkeypatch.setenv(anytime.DEADLINE_ENV, "0.25")
        assert anytime.resolve_deadline(3.0, 10.0) == 0.25
        monkeypatch.delenv(anytime.DEADLINE_ENV)
        assert anytime.resolve_deadline(3.0, 10.0) == 3.0
        assert anytime.resolve_deadline(None, 10.0) == 5.0
        assert anytime.resolve_deadline(None, None) == anytime._DEFAULT_DEADLINE

    def test_solver_tier_event_emitted(self, tmp_path):
        rng = random.Random(41)
        tp = topo(8)
        tasks = rand_tasks(rng, 8)
        mpath = str(tmp_path / "m.jsonl")
        with metrics.scoped(mpath):
            plan = anytime.anytime_resolve(tasks, tp, None, 1.0,
                                           deadline=1.0, source="test")
            anytime.anytime_resolve(tasks, tp, plan, 1.0, threshold=1e9,
                                    deadline=1.0, source="test")
        evs = metrics.read_events(mpath, kind="solver_tier")
        assert len(evs) == 2
        for ev in evs:
            assert ev["source"] == "test"
            assert ev["tier"] in anytime.TIER_NAMES
            assert ev["tier_name"] == anytime.TIER_NAMES[ev["tier"]]
            assert ev["n_tasks"] == 8
            assert ev["wall_s"] <= ev["deadline_s"] + 0.1
        assert evs[0]["outcome"] == "fresh"
        assert evs[1]["outcome"] == "slid"

    def test_cas_adopts_fresh_on_growth_and_shrink(self):
        rng = random.Random(43)
        tp = topo(8)
        tasks = rand_tasks(rng, 6)
        plan = anytime.anytime_resolve(tasks, tp, None, 1.0, deadline=1.0)
        grown = tasks + rand_tasks(rng, 2, prefix="g")
        p2 = anytime.anytime_resolve(grown, tp, plan, 1.0, deadline=1.0)
        assert p2.anytime.outcome == "fresh"
        assert set(p2.assignments) == {t.name for t in grown}
        p3 = anytime.anytime_resolve(tasks[:4], tp, p2, 1.0, deadline=1.0)
        assert p3.anytime.outcome == "fresh"
        assert set(p3.assignments) == {t.name for t in tasks[:4]}


@pytest.mark.solver
@pytest.mark.analysis
class TestSweepVerifier:
    """The O(N)-ish sweep verifier agrees with the exact analyzer on solver
    output and still catches planted races."""

    def test_sweep_accepts_all_tier_output(self):
        rng = random.Random(47)
        tp = topo(8)
        tasks = rand_tasks(rng, 30)
        for plan in (
            anytime.fast_greedy_plan(tasks, tp),
            anytime.lp_round_plan(tasks, tp, seed=1)[0],
        ):
            names = [t.name for t in tasks]
            exact = plan_verifier.launch_diagnostics(names, plan,
                                                     force_exact=True)
            sweep = plan_verifier.launch_diagnostics(names, plan,
                                                     force_sweep=True)
            assert [d.code for d in exact] == []
            assert [d.code for d in sweep] == []

    def test_sweep_catches_planted_race(self):
        rng = random.Random(53)
        tp = topo(8)
        tasks = rand_tasks(rng, 12)
        plan = anytime.fast_greedy_plan(tasks, tp)
        # Overlap two same-device tasks and sever their dependency edge.
        per_dev = {}
        for n, a in plan.assignments.items():
            per_dev.setdefault(a.block.offset, []).append(n)
        victims = next(v for v in per_dev.values() if len(v) >= 2)[:2]
        n1, n2 = victims
        a2 = plan.assignments[n2]
        plan.assignments[n2] = milp.Assignment(
            a2.apportionment, a2.block,
            plan.assignments[n1].start, a2.runtime)
        plan.dependencies = {
            n: [d for d in deps if {n, d} != {n1, n2}]
            for n, deps in plan.dependencies.items()
        }
        names = list(plan.assignments)
        codes = {d.code for d in plan_verifier.launch_diagnostics(
            names, plan, force_sweep=True)}
        assert "SAT-P001" in codes

    def test_chain_dependencies_are_race_sound(self):
        rng = random.Random(59)
        tp = topo(8)
        tasks = rand_tasks(rng, 300)
        plan = anytime.fast_greedy_plan(tasks, tp)
        assert len(plan.assignments) > anytime._CHAIN_DEP_N
        # chain edges (sparse) must satisfy the sweep race check
        diags = plan_verifier.launch_diagnostics(
            [t.name for t in tasks], plan, force_sweep=True)
        assert [d.code for d in diags] == []
        # and be far sparser than the dense pairwise form
        n_edges = sum(len(v) for v in plan.dependencies.values())
        assert n_edges < len(plan.assignments) * 8
