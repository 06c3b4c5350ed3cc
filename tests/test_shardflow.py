"""saturn-shardflow: sharding-propagation interpreter, SAT-X passes, and
the cold-start solver prior.

Three layers, mirroring the subsystem:

* **Interpreter rules** — hand-built jaxprs with known GSPMD consequences
  (contraction sharded both sides -> all-reduce, ZeRO-3 parameter gather,
  elementwise spec conflict -> reshard, scan trip-count folding,
  shard_map manual-mode suppression) checked byte-for-byte against the
  wire-cost model.
* **Passes** — SAT-X001..X005 each driven to fire and to stay quiet, plus
  the sanction marker's downgrade-never-silence contract.
* **Integration** — the cold-start admission path: a never-profiled task
  is ADMITted purely on static priors (zero trials, journaled
  ``static_prior=True``), realized feedback supersedes the prior, and
  SAT-X005 audits the superseded estimate.

The end-to-end static-vs-compiled-HLO agreement check lives in
``test_shardflow_differential.py``.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from saturn_tpu.analysis.diagnostics import SCHEMA_VERSION, AnalysisReport, make
from saturn_tpu.analysis.shardflow import PASS_VERSION
from saturn_tpu.analysis.shardflow import passes as sf_passes
from saturn_tpu.analysis.shardflow import prior as sf_prior
from saturn_tpu.analysis.shardflow.interp import (
    CollectiveRecord,
    CommLedger,
    Interpreter,
    interpret,
)

pytestmark = pytest.mark.analysis

F32 = jnp.float32


def sds(*shape):
    return jax.ShapeDtypeStruct(shape, F32)


def run_interp(fn, avals, specs, mesh_axes, axis_env=None,
               replicated_threshold=1 << 26):
    """Trace ``fn`` to a jaxpr and run the interpreter with explicit
    input specs (tuple-of-tuples form: one tuple of axis names per dim)."""
    closed = jax.make_jaxpr(fn, axis_env=list(axis_env or []))(*avals)
    it = Interpreter(mesh_axes, replicated_threshold=replicated_threshold)
    it.run(closed, specs)
    return it.ledger


class TestInterpreterRules:
    def test_contraction_sharded_both_sides_all_reduces_output(self):
        # A[4,8] x B[8,4] contracting on a 'data'-sharded dim: partial sums
        # on every shard -> all-reduce of the 4x4 output.
        def f(a, b):
            return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

        led = run_interp(f, [sds(4, 8), sds(8, 4)],
                         [((), ("data",)), (("data",), ())], {"data": 4})
        by = led.by_op()
        assert set(by) == {"all_reduce"}
        assert by["all_reduce"]["bytes"] == 4 * 4 * 4
        # ring cost: 2(n-1)/n of the payload
        assert by["all_reduce"]["wire_bytes"] == pytest.approx(
            2.0 * 3 / 4 * 64)
        assert led.flops == pytest.approx(2.0 * 16 * 8)

    def test_one_sided_contraction_gathers_that_operand(self):
        def f(a, b):
            return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

        led = run_interp(f, [sds(4, 8), sds(8, 4)],
                         [((), ("data",)), ((), ())], {"data": 4})
        by = led.by_op()
        assert set(by) == {"all_gather"}
        assert by["all_gather"]["bytes"] == 4 * 8 * 4  # the lhs, whole

    def test_zero3_parameter_gather(self):
        # batch sharded on 'data' meets a weight whose free dim is also
        # 'data'-sharded: GSPMD all-gathers the parameter (the ZeRO-3 /
        # fsdp pattern).
        def f(x, w):
            return x @ w

        led = run_interp(f, [sds(4, 8), sds(8, 16)],
                         [(("data",), ()), ((), ("data",))], {"data": 4})
        by = led.by_op()
        assert set(by) == {"all_gather"}
        assert by["all_gather"]["bytes"] == 8 * 16 * 4  # the weight, whole

    def test_compatible_shardings_move_no_bytes(self):
        def f(x, w):
            return x @ w

        led = run_interp(f, [sds(4, 8), sds(8, 16)],
                         [(("data",), ()), ((), ("model",))],
                         {"data": 4, "model": 2})
        assert led.records == []
        assert led.flops > 0

    def test_elementwise_conflict_records_reshard(self):
        def f(a, b):
            return a + b

        led = run_interp(f, [sds(8, 8), sds(8, 8)],
                         [(("data",), ()), (("model",), ())],
                         {"data": 2, "model": 2})
        assert led.resharded, "conflicting shardings must record a reshard"
        assert led.resharded[0].op == "reshard"
        assert set(led.resharded[0].axes) == {"data", "model"}

    def test_reduce_over_sharded_dim_all_reduces(self):
        def f(a):
            return a.sum(axis=0)

        led = run_interp(f, [sds(8, 4)], [(("data",), ())], {"data": 4})
        by = led.by_op()
        assert set(by) == {"all_reduce"}
        assert by["all_reduce"]["bytes"] == 4 * 4  # the (4,) output

    def test_explicit_psum_is_counted_and_flagged_explicit(self):
        def f(x):
            return jax.lax.psum(x, "data")

        led = run_interp(f, [sds(8)], [((),)], {"data": 4},
                         axis_env=[("data", 4)])
        assert len(led.records) == 1
        rec = led.records[0]
        assert rec.op == "all_reduce" and rec.explicit
        assert rec.bytes == 8 * 4

    def test_scan_folds_trip_count_and_marks_depth(self):
        def f(x):
            def body(c, _):
                return jax.lax.psum(c, "data"), None

            c, _ = jax.lax.scan(body, x, None, length=5)
            return c

        led = run_interp(f, [sds(4)], [((),)], {"data": 4},
                         axis_env=[("data", 4)])
        assert len(led.records) == 1
        rec = led.records[0]
        assert rec.count == 5 and rec.scan_depth == 1

    def test_one_wide_axis_moves_no_bytes(self):
        def f(x):
            return jax.lax.psum(x, "data")

        led = run_interp(f, [sds(8)], [((),)], {"data": 1},
                         axis_env=[("data", 1)])
        assert led.records == []

    def test_large_replicated_intermediate_is_flagged(self):
        def f(a):
            return jnp.broadcast_to(a.sum(), (64,))

        led = run_interp(f, [sds(8)], [((),)], {"data": 4},
                         replicated_threshold=128)
        assert led.replicated_intermediates
        assert max(b for b, _ in led.replicated_intermediates) >= 64 * 4
        # default 64 MiB threshold stays quiet on the same program
        quiet = run_interp(f, [sds(8)], [((),)], {"data": 4})
        assert quiet.replicated_intermediates == []


class TestShardMapMode:
    """Inside shard_map bodies sharding is manual: implicit GSPMD rules
    must not fire, only the body's explicit collectives count, and flops
    are rescaled from per-shard avals to the global workload."""

    def _mesh(self):
        return jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(4), ("data",))

    def test_only_explicit_collectives_counted(self, devices8):
        from jax import shard_map

        mesh = self._mesh()

        def f(x):
            def body(x):
                # jnp.sum over the locally-sharded dim would trip the
                # implicit reduce rule if manual mode weren't respected
                return jax.lax.psum(jnp.sum(x * 2.0), "data")

            return shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                             out_specs=P(), check_vma=False)(x)

        closed = jax.make_jaxpr(f)(sds(8, 8))
        it = Interpreter({"data": 4})
        it.run(closed, [(("data",), ())])
        by = it.ledger.by_op()
        assert set(by) == {"all_reduce"}
        assert by["all_reduce"]["bytes"] == 4  # the scalar psum
        assert all(r.explicit for r in it.ledger.records)

    def test_flops_rescaled_to_global(self, devices8):
        from jax import shard_map

        mesh = self._mesh()

        def f(x):
            def body(x):
                y = x @ jnp.ones((8, 8), F32)  # per-shard (2,8)@(8,8)
                return jax.lax.psum(jnp.sum(y), "data")

            return shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                             out_specs=P(), check_vma=False)(x)

        closed = jax.make_jaxpr(f)(sds(8, 8))
        it = Interpreter({"data": 4})
        it.run(closed, [(("data",), ())])
        # per-shard 2*16*8 flops x 4 shards == the global 2*64*8
        assert it.ledger.flops == pytest.approx(2.0 * 8 * 8 * 8)


class TestSourcePass:
    """SAT-X002 and the sanction marker contract."""

    BAD = (
        "from jax.experimental import multihost_utils\n"
        "\n"
        "def save(leaf):\n"
        "    return multihost_utils.process_allgather(leaf, tiled=True)\n"
    )
    SANCTIONED = (
        "from jax.experimental import multihost_utils\n"
        "\n"
        "def save(leaf):\n"
        "    # sanctioned-shardflow: unit test fixture\n"
        "    return multihost_utils.process_allgather(leaf, tiled=True)\n"
    )
    DEVICE_PUT = (
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec\n"
        "\n"
        "def gather(leaf, mesh):\n"
        "    return jax.device_put(\n"
        "        leaf, NamedSharding(mesh, PartitionSpec()))\n"
    )

    def _scan(self, tmp_path, src, name="mod.py"):
        p = tmp_path / name
        p.write_text(src)
        report = AnalysisReport(subject="test-sources")
        sf_passes.scan_sources([str(p)], report)
        return report

    def test_unsanctioned_allgather_is_an_error(self, tmp_path):
        report = self._scan(tmp_path, self.BAD)
        assert not report.ok
        (d,) = report.errors
        assert d.code == "SAT-X002"
        assert d.location and d.location.endswith(":4")

    def test_replicated_device_put_is_an_error(self, tmp_path):
        report = self._scan(tmp_path, self.DEVICE_PUT)
        assert [d.code for d in report.errors] == ["SAT-X002"]

    def test_sanction_downgrades_but_never_silences(self, tmp_path):
        report = self._scan(tmp_path, self.SANCTIONED)
        assert report.ok, "sanctioned finding must not gate"
        infos = [d for d in report.diagnostics if d.severity == "info"]
        assert [d.code for d in infos] == ["SAT-X002"]
        assert "sanctioned" in infos[0].message

    def test_unparseable_source_is_sat_x000(self, tmp_path):
        report = self._scan(tmp_path, "def broken(:\n")
        assert [d.code for d in report.errors] == ["SAT-X000"]

    def test_intree_sources_are_clean(self):
        # the lint gate's exact invocation: zero SAT-X002 in the
        # technique/kernel packages AND the checkpoint module — the sharded
        # manifest format (round 19) removed the last gather funnels, so no
        # sanctioned infos remain either
        import saturn_tpu

        repo = __import__("os").path.dirname(
            __import__("os").path.dirname(saturn_tpu.__file__))
        report = AnalysisReport(subject="intree")
        sf_passes.scan_sources(sf_passes.default_source_paths(repo), report)
        assert report.ok, [d.to_json() for d in report.errors]
        assert [d.code for d in report.diagnostics
                if d.severity == "info"] == []


def _traced(step, state_sds, state_spec, batch_sds, batch_spec, mesh_axes,
            axis_env=None):
    return {
        "jaxpr": jax.make_jaxpr(step, axis_env=list(axis_env or []))(
            state_sds, batch_sds),
        "state_shapes": state_sds,
        "state_specs": state_spec,
        "batch_spec": batch_spec,
        "batch_sds": batch_sds,
        "mesh_axes": dict(mesh_axes),
        "technique": "fake",
        "size": 1,
        "config": {},
    }


class TestTracePasses:
    def test_sat_x001_implicit_reshard(self):
        def step(state, batch):
            return state + batch

        traced = _traced(step, sds(8, 8), P("data"), sds(8, 8), P("model"),
                         {"data": 2, "model": 2})
        report, ledger = sf_passes.analyze_traced(traced)
        assert not report.ok
        assert "SAT-X001" in report.codes()
        assert ledger.resharded

    def test_sat_x003_oversized_replicated_intermediate(self):
        def step(state, batch):
            return state + jnp.broadcast_to(jnp.sum(batch), (64,))

        traced = _traced(step, sds(64), P(), sds(8, 8), P("data"),
                         {"data": 4})
        report, _ = sf_passes.analyze_traced(traced,
                                             replicated_threshold=128)
        assert report.ok  # warning-severity: flags, never gates
        assert "SAT-X003" in report.codes()

    def test_sat_x004_cross_slice_collective_in_scan(self):
        def step(state, batch):
            def body(c, _):
                return jax.lax.psum(c, "data"), None

            c, _ = jax.lax.scan(body, state, None, length=3)
            return c + jnp.sum(batch)

        traced = _traced(step, sds(8), P("data"), sds(8, 8), P(),
                         {"data": 8}, axis_env=[("data", 8)])
        # 8 devices over 4-chip slices: the leading axis crosses DCN
        report, _ = sf_passes.analyze_traced(traced, slice_size=4)
        assert "SAT-X004" in [d.code for d in report.errors]
        # same program on a single slice is fine
        quiet, _ = sf_passes.analyze_traced(traced, slice_size=8)
        assert "SAT-X004" not in quiet.codes()

    def test_crossing_axes(self):
        assert sf_passes.crossing_axes({"data": 4, "model": 2}, None) \
            == frozenset()
        assert sf_passes.crossing_axes({"data": 4, "model": 2}, 8) \
            == frozenset()
        assert sf_passes.crossing_axes({"data": 4, "model": 2}, 4) \
            == frozenset({"data"})


class TestTraceStepIntegration:
    def test_dp_trace_yields_gradient_all_reduce(self, tiny_task, devices8):
        from saturn_tpu.parallel import BUILTIN_TECHNIQUES

        tech = BUILTIN_TECHNIQUES["dp"]()
        config = tech.candidate_configs(tiny_task, 4)[0]
        traced = tech.trace_step(tiny_task, devices8[:4], config)
        for key in ("jaxpr", "state_shapes", "state_specs", "batch_spec",
                    "batch_sds", "mesh_axes", "technique", "size"):
            assert key in traced
        assert traced["mesh_axes"] == {"data": 4}
        ledger = interpret(traced)
        by = ledger.by_op()
        assert by.get("all_reduce", {}).get("bytes", 0) > 0
        assert ledger.flops > 0


class TestPrior:
    def _ledger(self, nbytes=1 << 20):
        led = CommLedger()
        led.add(CollectiveRecord(
            op="all_reduce", axes=("data",), bytes=nbytes,
            wire_bytes=1.5 * nbytes, count=1, primitive="psum",
            provenance="x:1", explicit=True))
        led.flops = 1e9
        return led

    def test_estimate_prices_crossing_axes_at_dcn(self):
        led = self._ledger()
        t_ici = sf_prior.estimate_step_seconds(led, 4)
        t_dcn = sf_prior.estimate_step_seconds(
            led, 4, crossing=frozenset({"data"}))
        assert t_dcn > t_ici * 5  # DCN is orders of magnitude slower

    def test_hardware_model_env_override(self, monkeypatch):
        monkeypatch.setenv("SATURN_TPU_PRIOR_MFU", "0.9")
        assert sf_prior.hardware_model()["mfu"] == 0.9

    def test_audit_point_tolerance_boundary(self):
        assert sf_prior.audit_point(1.0, 1.3, "dp", 4) is None  # 23% ok
        d = sf_prior.audit_point(2.0, 1.0, "dp", 4)  # 100% off
        assert d is not None and d.code == "SAT-X005"
        assert d.severity == "warning"
        assert d.counterexample["relative_error"] == pytest.approx(1.0)

    def test_audit_skips_live_priors(self):
        class S:
            static_prior = True
            _static_prior_estimate = 1.0
            per_batch_time = 10.0
            executor = object()

        class T:
            strategies = {4: S()}

        assert sf_prior.audit_task(T()) == []

    def test_synthesize_then_feedback_then_audit(self, tiny_task, devices8):
        """The full prior lifecycle on a real task: synthesize (no trials,
        no compiles) -> live prior -> realized feedback supersedes it ->
        SAT-X005 flags the miscalibration."""
        from saturn_tpu.core.mesh import SliceTopology

        topo = SliceTopology(devices8)
        added = sf_prior.synthesize_strategies(
            tiny_task, topo, technique_names=["dp"])
        assert added == [1, 2, 4, 8]
        for g in added:
            s = tiny_task.strategies[g]
            assert s.static_prior
            assert s.per_batch_time > 0
            assert s.cache_key
            assert s._static_prior_estimate == pytest.approx(
                s.per_batch_time)
        # never overwrites existing points
        assert sf_prior.synthesize_strategies(
            tiny_task, topo, technique_names=["dp"]) == []
        # no audit while the prior is live
        assert sf_prior.audit_task(tiny_task) == []

        strat = tiny_task.strategies[4]
        tiny_task._pending_realized = (strat, strat.per_batch_time * 10)
        tiny_task.apply_realized_feedback()
        assert strat.static_prior is False
        diags = sf_prior.audit_task(tiny_task)
        assert [d.code for d in diags] == ["SAT-X005"]


class TestColdStartAdmission:
    """Acceptance: a never-profiled arrival is gated on the static prior
    alone — zero trials, journaled ``static_prior=True`` — and realized
    feedback later corrects the estimate under a SAT-X005 audit."""

    def test_admit_on_static_prior_then_audit(self, tiny_task, devices8,
                                              tmp_path):
        from saturn_tpu.core.mesh import SliceTopology
        from saturn_tpu.service.admission import ADMIT, AdmissionController
        from saturn_tpu.service.queue import JobRequest, SubmissionQueue
        from saturn_tpu.utils import metrics

        topo = SliceTopology(devices8)
        queue = SubmissionQueue()
        rec = queue.submit(JobRequest(task=tiny_task))
        ctrl = AdmissionController(topo, queue, technique_names=["dp"],
                                   static_priors=True)
        journal = []

        class Journal:
            def append(self, kind, **fields):
                journal.append((kind, fields))

        ctrl.journal = Journal()
        dec = ctrl.admit(rec, topo)

        assert dec.action == ADMIT
        assert dec.static_prior is True
        assert dec.trials_run == 0, "cold start must cost zero trials"
        assert dec.reason == "static prior"
        kinds = [k for k, _ in journal]
        assert kinds == ["job_admission"]
        assert journal[0][1]["static_prior"] is True
        assert all(s.static_prior
                   for s in tiny_task.feasible_strategies().values())

        # realized feedback supersedes the prior; the audit catches the
        # (deliberately huge) miscalibration as SAT-X005
        strat = tiny_task.strategies[max(tiny_task.feasible_strategies())]
        tiny_task._pending_realized = (strat, strat.per_batch_time * 10)
        tiny_task.apply_realized_feedback()
        assert strat.static_prior is False

        mpath = str(tmp_path / "metrics.jsonl")
        with metrics.scoped(mpath):
            ctrl._audit_priors(rec, tiny_task)
        evs = metrics.read_events(mpath, kind="shardflow_audit")
        assert evs and evs[0]["code"] == "SAT-X005"
        assert evs[0]["task"] == rec.name


class TestSolverJournal:
    def test_anytime_report_counts_static_prior_assignments(self, tmp_path):
        from saturn_tpu.core.mesh import SliceTopology
        from saturn_tpu.core.strategy import Strategy
        from saturn_tpu.solver import anytime
        from saturn_tpu.utils import metrics

        class FakeDev:
            pass

        class FakeTask:
            def __init__(self, name, runtimes, static):
                self.name = name
                self.strategies = {
                    g: Strategy(object(), g, {}, rt, 0.1,
                                static_prior=static)
                    for g, rt in runtimes.items()
                }

            def feasible_strategies(self):
                return self.strategies

        tp = SliceTopology([FakeDev() for _ in range(8)])
        tasks = [
            FakeTask("prior-a", {2: 8.0, 4: 5.0}, static=True),
            FakeTask("prior-b", {2: 6.0, 4: 4.0}, static=True),
            FakeTask("measured", {2: 7.0, 4: 4.5}, static=False),
        ]
        plan, report = anytime.anytime_solve(tasks, tp, deadline=0.5)
        assert len(plan.assignments) == 3
        assert report.n_static_prior == 2

        # the journaled solver_tier event carries the count (resolve path)
        mpath = str(tmp_path / "metrics.jsonl")
        with metrics.scoped(mpath):
            anytime.anytime_resolve(tasks, tp, None, 1.0, deadline=0.5,
                                    source="test")
        evs = metrics.read_events(mpath, kind="solver_tier")
        assert evs and evs[-1]["n_static_prior"] == 2


class TestReplanPropagation:
    def _task(self, static):
        from saturn_tpu.core.strategy import Strategy

        class T:
            name = "t"
            total_batches = 16
            chip_range = None

            def __init__(self):
                self.strategies = {
                    4: Strategy(object(), 4, {}, 40.0, 2.5,
                                static_prior=static),
                    8: Strategy(object(), 8, {}, 24.0, 1.5,
                                static_prior=static),
                }

            def feasible_strategies(self):
                return self.strategies

        return T()

    def test_all_static_anchors_propagate_the_flag(self):
        from saturn_tpu.resilience.replan import ElasticReplanner

        t = self._task(static=True)
        added = ElasticReplanner()._synthesize(t, 2)
        assert added
        assert all(t.strategies[g].static_prior for g in added)

    def test_measured_anchors_do_not(self):
        from saturn_tpu.resilience.replan import ElasticReplanner

        t = self._task(static=False)
        added = ElasticReplanner()._synthesize(t, 2)
        assert added
        assert not any(t.strategies[g].static_prior for g in added)


class TestCacheIdentity:
    def test_schema_version_bumped_for_shardflow(self):
        assert SCHEMA_VERSION >= 3
        assert PASS_VERSION >= 1

    def test_profile_fingerprint_tracks_pass_version(self, monkeypatch):
        import saturn_tpu.analysis.shardflow as sf_pkg
        from saturn_tpu.utils import profile_cache as pcache

        before = pcache.fingerprint("task", "dp", 4, "topo")
        monkeypatch.setattr(sf_pkg, "PASS_VERSION", 999 + PASS_VERSION)
        after = pcache.fingerprint("task", "dp", 4, "topo")
        assert before != after

    def test_aot_identity_tracks_pass_version(self, monkeypatch):
        import saturn_tpu.analysis.shardflow as sf_pkg
        from saturn_tpu.utils import aot_cache

        ident = aot_cache._runtime_identity()
        assert f"shardflow{PASS_VERSION}" in ident
        monkeypatch.setattr(sf_pkg, "PASS_VERSION", 999 + PASS_VERSION)
        assert aot_cache._runtime_identity() != ident


class TestCLI:
    def _fake_audit(self, report):
        def audit_intree(size=4, **kw):
            return report, {"dp": CommLedger()}

        return audit_intree

    def test_clean_audit_exits_zero(self, monkeypatch, capsys):
        from saturn_tpu.analysis import cli

        report = AnalysisReport(subject="shardflow-audit")
        monkeypatch.setattr(sf_passes, "audit_intree",
                            self._fake_audit(report))
        rc = cli.main(["--json", "shardflow"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert "ledgers" in payload

    def test_findings_exit_one(self, monkeypatch, capsys):
        from saturn_tpu.analysis import cli

        report = AnalysisReport(subject="shardflow-audit")
        report.add(make("SAT-X001", "error", "implicit reshard",
                        category="shardflow"))
        monkeypatch.setattr(sf_passes, "audit_intree",
                            self._fake_audit(report))
        assert cli.main(["shardflow"]) == 1
        capsys.readouterr()
