"""saturn-lint regression tests: one test per diagnostic code, gate
placement (service quarantine crash marker), CLI, and cache fingerprint
coupling. The differential static/dynamic oracle lives in
``test_analysis_differential.py``."""

import json
import os
from types import SimpleNamespace

import pytest

from saturn_tpu import analysis
from saturn_tpu.analysis import jax_lint, plan_verifier
from saturn_tpu.analysis.diagnostics import PlanVerificationError
from saturn_tpu.core.mesh import Block, SliceTopology
from saturn_tpu.solver.milp import Assignment, Plan

pytestmark = pytest.mark.analysis


class FakeDev:
    pass


def topo(n=8):
    return SliceTopology([FakeDev() for _ in range(n)])


def mk_plan(assignments, deps=None, coschedule=None, makespan=None):
    ends = [a.start + a.runtime for a in assignments.values()] or [0.0]
    plan = Plan(
        assignments=assignments,
        makespan=max(ends) if makespan is None else makespan,
        dependencies=deps if deps is not None else {},
        coschedule=coschedule or [],
    )
    if deps is None:
        plan.compute_dependencies()
    return plan


def codes_of(report):
    return set(report.codes())


# --------------------------------------------------------------------- pass 1
class TestLaunchDiagnostics:
    def test_race_code_and_message(self):
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 0.0, 1.0),
            "b": Assignment(4, Block(0, 4), 0.0, 1.0),
        }, deps={"a": [], "b": []})
        report = analysis.verify_plan(plan)
        assert "SAT-P001" in codes_of(report) and not report.ok
        with pytest.raises(RuntimeError, match="races"):
            plan_verifier.check_launch_invariants(["a", "b"], plan)

    def test_cycle_code_and_message(self):
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 0.0, 1.0),
            "b": Assignment(4, Block(4, 4), 0.0, 1.0),
        }, deps={"a": ["b"], "b": ["a"]})
        report = analysis.verify_plan(plan)
        assert "SAT-P002" in codes_of(report)
        with pytest.raises(RuntimeError, match="cycle"):
            plan_verifier.check_launch_invariants(["a", "b"], plan)

    def test_groupmate_code_and_message(self):
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 0.0, 1.0),
            "b": Assignment(4, Block(0, 4), 0.0, 1.0),
        }, deps={"a": [], "b": ["a"]}, coschedule=[["a", "b"]])
        report = analysis.verify_plan(plan)
        assert "SAT-P003" in codes_of(report)
        with pytest.raises(RuntimeError, match="groupmate"):
            plan_verifier.check_launch_invariants(["a", "b"], plan)

    def test_transitive_serialization_accepted(self):
        plan = mk_plan({
            n: Assignment(4, Block(0, 4), float(i), 1.0)
            for i, n in enumerate("abc")
        }, deps={"a": [], "b": ["a"], "c": ["b"]})
        assert analysis.verify_plan(plan).ok

    def test_coschedule_overlap_accepted(self):
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 0.0, 1.0),
            "b": Assignment(4, Block(0, 4), 0.0, 1.0),
        }, deps={"a": [], "b": []}, coschedule=[["a", "b"]])
        assert analysis.verify_plan(plan).ok


class TestStructureDiagnostics:
    def test_unknown_dep_name(self):
        plan = mk_plan({"a": Assignment(4, Block(0, 4), 0.0, 1.0)},
                       deps={"a": ["ghost"]})
        report = analysis.verify_plan(plan)
        assert "SAT-P010" in codes_of(report) and report.ok  # warning only

    def test_unknown_coschedule_member_and_small_group(self):
        plan = mk_plan({"a": Assignment(4, Block(0, 4), 0.0, 1.0)},
                       deps={"a": []}, coschedule=[["a", "ghost"]])
        report = analysis.verify_plan(plan)
        assert {"SAT-P011", "SAT-P012"} <= codes_of(report) and report.ok

    def test_task_in_two_groups(self):
        plan = mk_plan({
            "a": Assignment(2, Block(0, 2), 0.0, 1.0),
            "b": Assignment(2, Block(0, 2), 0.0, 1.0),
            "c": Assignment(2, Block(0, 2), 0.0, 1.0),
        }, deps={}, coschedule=[["a", "b"], ["b", "c"]])
        report = analysis.verify_plan(plan)
        assert "SAT-P013" in codes_of(report)


class TestFeasibilityDiagnostics:
    def test_block_beyond_capacity(self):
        plan = mk_plan({"a": Assignment(8, Block(8, 8), 0.0, 1.0)}, deps={})
        report = analysis.verify_plan(plan, topology=topo(8))
        assert "SAT-P020" in codes_of(report) and not report.ok

    def test_apportionment_block_mismatch(self):
        plan = mk_plan({"a": Assignment(2, Block(0, 4), 0.0, 1.0)}, deps={})
        report = analysis.verify_plan(plan, topology=topo(8))
        assert "SAT-P021" in codes_of(report)

    def test_no_feasible_strategy(self):
        task = SimpleNamespace(
            name="a",
            strategies={4: SimpleNamespace(feasible=False, host_fraction=0.0)},
        )
        plan = mk_plan({"a": Assignment(4, Block(0, 4), 0.0, 1.0)}, deps={})
        report = analysis.verify_plan(plan, topology=topo(8), tasks=[task])
        assert "SAT-P022" in codes_of(report)

    def test_coschedule_group_block_mismatch_and_host_fraction(self):
        tasks = [
            SimpleNamespace(name=n, strategies={
                4: SimpleNamespace(feasible=True, host_fraction=0.0)
            })
            for n in ("a", "b")
        ]
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 0.0, 1.0),
            "b": Assignment(4, Block(4, 4), 0.0, 1.0),
        }, deps={}, coschedule=[["a", "b"]])
        report = analysis.verify_plan(plan, topology=topo(8), tasks=tasks)
        assert {"SAT-P023", "SAT-P024"} <= codes_of(report)
        assert report.ok  # advisory, not gate-blocking


class TestTimelineDiagnostics:
    def test_negative_start(self):
        plan = mk_plan({"a": Assignment(4, Block(0, 4), -1.0, 1.0)}, deps={})
        report = analysis.verify_plan(plan)
        assert "SAT-P030" in codes_of(report) and not report.ok

    def test_start_order_contradicts_dependency(self):
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 5.0, 1.0),
            "b": Assignment(4, Block(0, 4), 0.0, 1.0),
        }, deps={"a": [], "b": ["a"]})
        report = analysis.verify_plan(plan)
        assert "SAT-P031" in codes_of(report)

    def test_stale_makespan(self):
        plan = mk_plan({"a": Assignment(4, Block(0, 4), 0.0, 10.0)},
                       deps={}, makespan=1.0)
        report = analysis.verify_plan(plan)
        assert "SAT-P032" in codes_of(report) and report.ok

    def test_deadline_overrun(self):
        task = SimpleNamespace(
            name="a",
            strategies={4: SimpleNamespace(feasible=True, host_fraction=0.0)},
            deadline=5.0,
        )
        plan = mk_plan({"a": Assignment(4, Block(0, 4), 0.0, 10.0)}, deps={})
        report = analysis.verify_plan(plan, tasks=[task])
        assert "SAT-P033" in codes_of(report) and report.ok


class TestVerifyOrRaise:
    def test_raises_plan_verification_error(self):
        plan = mk_plan({
            "a": Assignment(4, Block(0, 4), 0.0, 1.0),
            "b": Assignment(4, Block(0, 4), 0.0, 1.0),
        }, deps={"a": [], "b": []})
        with pytest.raises(PlanVerificationError) as ei:
            analysis.verify_or_raise(plan, source="unit-test")
        assert isinstance(ei.value, RuntimeError)  # legacy callers unchanged
        assert "SAT-P001" in str(ei.value)
        assert ei.value.report.errors

    def test_clean_plan_returns_report(self):
        plan = mk_plan({"a": Assignment(4, Block(0, 4), 0.0, 1.0)}, deps={})
        report = analysis.verify_or_raise(plan, topology=topo(8))
        assert report.ok


# --------------------------------------------------------------------- pass 2
class TestRetraceRegistry:
    def test_novel_signature_flagged(self):
        reg = jax_lint.SignatureRegistry()
        sig_a = (("p", (8, 8), "float32"),)
        sig_b = (("p", (8, 16), "float32"),)
        assert reg.note("bundle", 4, sig_a) is None
        assert reg.note("bundle", 4, sig_a) is None  # same shapes: no risk
        diag = reg.note("bundle", 4, sig_b)
        assert diag is not None and diag.code == "SAT-L001"
        assert reg.note("bundle", 8, sig_b) is None  # different K: new key
        assert [d.code for d in reg.drain()] == ["SAT-L001"]


def _hot_loop_with_sync(xs):
    total = 0.0
    for x in xs:
        x.block_until_ready()
        total += float(x)
    return total


def _hot_loop_sanctioned(xs):
    total = 0.0
    for x in xs:
        x.block_until_ready()  # lint: sanctioned-host-sync
        total += 1
    return total


def _drain_after_loop(xs):
    last = None
    for x in xs:
        last = x
    return float(last)


class TestHostSyncLint:
    def test_sync_in_loop_flagged_with_location(self):
        diags = jax_lint.lint_host_syncs(_hot_loop_with_sync)
        assert {d.code for d in diags} == {"SAT-L002"}
        assert len(diags) == 2  # block_until_ready + float
        assert all(d.location and __file__.rstrip("c") in d.location
                   for d in diags)

    def test_sanction_marker_respected(self):
        assert jax_lint.lint_host_syncs(_hot_loop_sanctioned) == []

    def test_drain_after_loop_clean(self):
        assert jax_lint.lint_host_syncs(_drain_after_loop) == []

    def test_interval_hot_loop_is_clean(self):
        """The real dispatch hot loop carries exactly one sanctioned sync
        (the warmup fence) and nothing unsanctioned."""
        from saturn_tpu.parallel.spmd_base import SPMDTechnique

        assert jax_lint.lint_host_syncs(SPMDTechnique.interval_dispatches) == []


def _donation_bug(fused_fn, state, window):
    state, loss = fused_fn(state, window)
    return loss, window.sum()  # reads the donated window stack


def _donation_ok(fused_fn, stage, state, n):
    loss = None
    for i in range(n):
        window = stage(i)
        state, loss = fused_fn(state, window)
    return state, loss


class TestDonationLint:
    def test_donated_read_flagged(self):
        diags = jax_lint.lint_donation(_donation_bug,
                                       {"fused_fn": (0, 1)})
        assert [d.code for d in diags] == ["SAT-L003"]
        assert diags[0].counterexample["name"] == "window"
        assert diags[0].location

    def test_restaged_window_clean(self):
        assert jax_lint.lint_donation(_donation_ok,
                                      {"fused_fn": (0, 1)}) == []

    def test_interval_hot_loop_donation_clean(self):
        from saturn_tpu.parallel.spmd_base import SPMDTechnique

        assert jax_lint.lint_donation(
            SPMDTechnique.interval_dispatches,
            {"fused_fn": (0, 1), "single_fn": (0, 1)},
        ) == []


# Deliberately-broken rule functions for the seeded sharding-lint tests.
# Their def lines anchor the file:line assertions below.
def _bad_axis_rules(path, shape, mesh_axes):
    from jax.sharding import PartitionSpec as P

    return P("modell")  # typo'd axis name — not in any mesh


def _bad_divis_rules(path, shape, mesh_axes):
    from jax.sharding import PartitionSpec as P

    return P("data")  # shards dim 0 regardless of divisibility


class TestShardingLint:
    MESH_AXES = {"data": 4, "model": 2}

    def test_unknown_axis_file_line(self):
        report = jax_lint.lint_rules(
            _bad_axis_rules, {"w": (8, 8)}, self.MESH_AXES
        )
        assert [d.code for d in report.errors] == ["SAT-L010"]
        loc = report.errors[0].location
        assert loc and os.path.basename(__file__).rstrip("c") in loc
        # the line number points at the rule function's def
        assert int(loc.rsplit(":", 1)[1]) > 0

    def test_divisibility_violation_file_line(self):
        report = jax_lint.lint_rules(
            _bad_divis_rules, {"w": (6, 8)}, self.MESH_AXES
        )
        codes = [d.code for d in report.diagnostics]
        assert codes == ["SAT-L011"]
        assert report.diagnostics[0].severity == "warning"
        assert report.diagnostics[0].location
        strict = jax_lint.lint_rules(
            _bad_divis_rules, {"w": (6, 8)}, self.MESH_AXES, strict=True
        )
        assert not strict.ok  # strict mode promotes to error

    def test_rank_overflow(self):
        from jax.sharding import PartitionSpec as P

        diags = jax_lint.check_pspec(P("data", "model"), (8,),
                                     self.MESH_AXES)
        assert [d.code for d in diags] == ["SAT-L012"]

    def test_pspec_tree_gate_raises_on_bad_axis(self, devices8):
        """The pre-compile gate: a rule naming a nonexistent mesh axis is
        refused at pspec_tree time with the rule's file:line, on CPU."""
        import jax

        from saturn_tpu.core.mesh import make_submesh
        from saturn_tpu.parallel import sharding as shr

        mesh = make_submesh(devices8, ("data", "model"), (4, 2))
        shapes = {"w": jax.ShapeDtypeStruct((8, 8), "float32")}
        with pytest.raises(jax_lint.ShardingLintError) as ei:
            shr.pspec_tree(shapes, _bad_axis_rules, mesh)
        assert "SAT-L010" in str(ei.value)
        assert os.path.basename(__file__).rstrip("c") in str(ei.value)

    def test_pspec_tree_accepts_good_rules(self, devices8):
        import jax

        from saturn_tpu.core.mesh import make_submesh
        from saturn_tpu.parallel import sharding as shr

        mesh = make_submesh(devices8, ("data", "model"), (4, 2))
        shapes = {"w": jax.ShapeDtypeStruct((8, 8), "float32")}
        specs = shr.pspec_tree(shapes, shr.fsdp_rules(), mesh)
        assert specs["w"] is not None

    def test_builtin_fsdp_rules_lint_clean(self):
        from saturn_tpu.parallel import sharding as shr

        report = jax_lint.lint_rules(
            shr.fsdp_rules(),
            {"layer/kernel": (768, 3072), "layer/bias": (3072,)},
            {"data": 8},
        )
        assert report.ok and not report.diagnostics


# ------------------------------------------------------------------- journal
def _write_journal_with_plan(tmp_path, plan, name="wal"):
    from saturn_tpu.durability.journal import Journal

    root = str(tmp_path / name)
    j = Journal(root)
    j.append("plan_commit", interval=0, makespan=plan.makespan,
             plan=plan.to_json())
    j.commit()
    j.close()
    return root


def _racy_plan():
    return mk_plan({
        "a": Assignment(4, Block(0, 4), 0.0, 1.0),
        "b": Assignment(4, Block(0, 4), 0.0, 1.0),
    }, deps={"a": [], "b": []})


def _clean_plan():
    return mk_plan({
        "a": Assignment(4, Block(0, 4), 0.0, 1.0),
        "b": Assignment(4, Block(4, 4), 0.0, 1.0),
    }, deps={"a": [], "b": []})


class TestJournalAudit:
    def test_bad_plan_commit_flagged(self, tmp_path):
        root = _write_journal_with_plan(tmp_path, _racy_plan())
        report = analysis.audit_journal(root)
        codes = codes_of(report)
        assert {"SAT-J001", "SAT-P001"} <= codes and not report.ok

    def test_clean_journal_passes(self, tmp_path):
        root = _write_journal_with_plan(tmp_path, _clean_plan())
        report = analysis.audit_journal(root)
        assert report.ok and "SAT-J001" not in codes_of(report)

    def test_recovery_delegate(self, tmp_path):
        from saturn_tpu.durability import recovery as rmod

        root = _write_journal_with_plan(tmp_path, _racy_plan())
        assert not rmod.audit_plan_commits(root).ok


@pytest.mark.crash
class TestServiceQuarantine:
    """Satellite: journal recovery must QUARANTINE a replayed plan that
    fails static verification — fall back to a fresh solve, never adopt."""

    def test_recovered_racy_plan_quarantined(self, tmp_path):
        from saturn_tpu.durability import journal as jmod
        from saturn_tpu.service.server import SaturnService

        root = _write_journal_with_plan(tmp_path, _racy_plan())
        svc = SaturnService(topology=topo(8), durability_dir=root)
        try:
            assert svc._recovered_plan is None  # quarantined, not adopted
            kinds = [r["kind"] for r in jmod.replay(root)]
            assert "plan_quarantine" in kinds  # durable crash marker
        finally:
            svc.journal.close()

    def test_recovered_clean_plan_adopted(self, tmp_path):
        from saturn_tpu.durability import journal as jmod
        from saturn_tpu.service.server import SaturnService

        root = _write_journal_with_plan(tmp_path, _clean_plan())
        svc = SaturnService(topology=topo(8), durability_dir=root)
        try:
            assert svc._recovered_plan is not None
            kinds = [r["kind"] for r in jmod.replay(root)]
            assert "plan_quarantine" not in kinds
        finally:
            svc.journal.close()


# ----------------------------------------------------------------------- CLI
class TestCLI:
    def test_plan_subcommand(self, tmp_path, capsys):
        from saturn_tpu.analysis import cli

        path = str(tmp_path / "plan.json")
        with open(path, "w") as f:
            json.dump(_racy_plan().to_json(), f)
        assert cli.main(["plan", path]) == 1
        assert "SAT-P001" in capsys.readouterr().out
        with open(path, "w") as f:
            json.dump(_clean_plan().to_json(), f)
        assert cli.main(["--json", "plan", path, "--topology", "8"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["ok"] is True and out["schema"] == analysis.SCHEMA_VERSION

    def test_journal_subcommand(self, tmp_path, capsys):
        from saturn_tpu.analysis import cli

        root = _write_journal_with_plan(tmp_path, _racy_plan())
        assert cli.main(["journal", root]) == 1
        assert "SAT-J001" in capsys.readouterr().out

    def test_plan_subcommand_missing_file(self, tmp_path):
        from saturn_tpu.analysis import cli

        assert cli.main(["plan", str(tmp_path / "nope.json")]) == 2


# ---------------------------------------------------------------- fingerprint
class TestAnalysisSchemaInFingerprints:
    def test_profile_cache_fingerprint_tracks_analyzer_schema(self, monkeypatch):
        from saturn_tpu.utils import profile_cache as pcache

        before = pcache.fingerprint("t", "fsdp", 4, "topo", "per-step")
        monkeypatch.setattr("saturn_tpu.analysis.SCHEMA_VERSION",
                            analysis.SCHEMA_VERSION + 1)
        after = pcache.fingerprint("t", "fsdp", 4, "topo", "per-step")
        assert before != after

    def test_aot_runtime_identity_tracks_analyzer_schema(self):
        from saturn_tpu.utils import aot_cache

        ident = aot_cache._runtime_identity()
        assert f"lint{analysis.SCHEMA_VERSION}" in ident
