"""Persistent profile cache + cost-model pruning (trial_runner/evaluator.py).

Hardware-free: fake techniques count ``search`` invocations so the tests can
assert the sweep's *compile economy* — zero trials on an identical re-run,
anchor-only trials under pruning, no trials below a memory-infeasible size —
without ever jitting a program.
"""

import json
import os

import pytest

from saturn_tpu import library
from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.core.strategy import Strategy
from saturn_tpu.core.technique import BaseTechnique
from saturn_tpu.trial_runner import evaluator
from saturn_tpu.utils import profile_cache as pcache


class FakeDev:
    platform = "cpu"
    device_kind = "fake-cpu"
    process_index = 0


def topo(n=8):
    return SliceTopology([FakeDev() for _ in range(n)])


class FakeSpec:
    def __init__(self, config):
        self.config = config


class FakeDataset:
    batch_size = 8

    def __len__(self):
        return 8

    def example_batch(self):
        import numpy as np

        return np.zeros((8, 64), dtype=np.int32)

    def batch(self, i):
        return self.example_batch()


class FakeHParams:
    optimizer = "adamw"
    kwargs: dict = {}


class FakeTask:
    """Evaluator-facing duck type (name, chip_range, strategies, factories)."""

    def __init__(self, name, model_cfg="cfg-v1", optimizer="adamw"):
        self.name = name
        self.chip_range = None
        self.total_batches = 100
        self.strategies = {}
        self.hints = {}
        self.hparams = FakeHParams()
        self.hparams.optimizer = optimizer
        self._model_cfg = model_cfg

    def get_model(self, **kw):
        return FakeSpec(self._model_cfg)

    def get_dataset(self):
        return FakeDataset()

    def feasible_strategies(self):
        return {g: s for g, s in self.strategies.items() if s.feasible}


class CountingTech(BaseTechnique):
    """Feasible everywhere; records every (task, size) search invocation."""

    name = "counting"
    calls: list = []

    def search(self, task, devices, tid):
        type(self).calls.append((task.name, len(devices)))
        g = len(devices)
        return {"knob": g}, 0.08 / g + 0.02  # Amdahl-ish: a=0.02, b=0.08

    def execute(self, task, devices, tid, override_batch_count=None):
        pass


class MemoryWallTech(BaseTechnique):
    """Memory-infeasible below 8 chips, with an honest search report."""

    name = "memwall"
    memory_monotone = True
    calls: list = []

    def __init__(self):
        self._reports = {}

    def search(self, task, devices, tid):
        g = len(devices)
        type(self).calls.append((task.name, g))
        if g < 8:
            self._reports[(task.name, g)] = {"memory_infeasible": True}
            return None, None
        return {}, 0.01

    def search_report(self, task_name, size):
        return self._reports.pop((task_name, size), None)

    def execute(self, task, devices, tid, override_batch_count=None):
        pass


@pytest.fixture(autouse=True)
def _registry():
    library.register("counting", CountingTech)
    library.register("memwall", MemoryWallTech)
    CountingTech.calls = []
    MemoryWallTech.calls = []
    yield
    library.deregister("counting")
    library.deregister("memwall")


def run_search(tasks, names, cache_dir, prune=False, metrics_path=None, n=8):
    evaluator.search(
        tasks,
        technique_names=names,
        topology=topo(n),
        profile_cache=cache_dir if cache_dir is not None else False,
        prune=prune,
        metrics_path=metrics_path,
    )


def read_events(path, kind):
    with open(path) as f:
        return [json.loads(line) for line in f if json.loads(line)["kind"] == kind]


class TestPersistentCache:
    def test_rerun_is_trial_free(self, tmp_path):
        """Acceptance: a second search() over an unchanged task list performs
        ZERO technique.search executions — every strategy comes from the
        persistent profile cache."""
        cache_dir = str(tmp_path / "cache")
        mpath = str(tmp_path / "m1.jsonl")
        tasks = [FakeTask("a"), FakeTask("b")]
        run_search(tasks, ["counting"], cache_dir, metrics_path=mpath)
        assert len(CountingTech.calls) == 2 * 4  # 2 tasks x sizes {1,2,4,8}
        first = {
            (t.name, g): s.per_batch_time
            for t in tasks for g, s in t.strategies.items() if s.feasible
        }
        assert len(first) == 8

        CountingTech.calls = []
        mpath2 = str(tmp_path / "m2.jsonl")
        rerun = [FakeTask("a"), FakeTask("b")]  # same content, fresh objects
        run_search(rerun, ["counting"], cache_dir, metrics_path=mpath2)
        assert CountingTech.calls == []
        for t in rerun:
            for g, s in t.strategies.items():
                assert s.feasible, (t.name, g)
                assert s.per_batch_time == pytest.approx(first[(t.name, g)])
                assert not s.interpolated
                assert s.cache_key
        hits = read_events(mpath2, "profile_cache")
        assert sum(1 for e in hits if e.get("hit")) == 8
        misses = [e for e in read_events(mpath, "profile_cache") if not e.get("hit")]
        assert len(misses) == 8  # first run consulted and missed every point

    @pytest.mark.parametrize("cached", [True, False])
    def test_a_jobs_fingerprint_is_a_span_of_the_search(self, tmp_path, cached):
        """The one named stretch of the search's own seconds (PR 39): one
        ``search.fingerprint`` a job, a child of ``search``, before any
        trial; none where the sweep runs without a profile cache."""
        mpath = str(tmp_path / "m.jsonl")
        run_search([FakeTask("a"), FakeTask("b")], ["counting"],
                   str(tmp_path / "cache") if cached else None,
                   metrics_path=mpath)
        (search,) = read_events(mpath, "search")
        found = read_events(mpath, "search.fingerprint")
        assert [e["task"] for e in found] == (["a", "b"] if cached else [])
        first_trial = min(e["ts_start"] for e in read_events(mpath, "trial"))
        for e in found:
            assert e["parent"] == search["id"] == e["root"]
            assert e["ts"] <= first_trial + 0.005 and e["dur_s"] >= 0

    def test_model_change_misses(self, tmp_path):
        cache_dir = str(tmp_path / "cache")
        run_search([FakeTask("a", model_cfg="cfg-v1")], ["counting"], cache_dir)
        CountingTech.calls = []
        run_search([FakeTask("a", model_cfg="cfg-v2")], ["counting"], cache_dir)
        assert len(CountingTech.calls) == 4  # every size re-profiled

    def test_optimizer_change_misses(self, tmp_path):
        cache_dir = str(tmp_path / "cache")
        run_search([FakeTask("a")], ["counting"], cache_dir)
        CountingTech.calls = []
        run_search([FakeTask("a", optimizer="sgd")], ["counting"], cache_dir)
        assert len(CountingTech.calls) == 4

    def test_topology_change_misses(self, tmp_path):
        cache_dir = str(tmp_path / "cache")
        run_search([FakeTask("a")], ["counting"], cache_dir, n=8)
        CountingTech.calls = []
        run_search([FakeTask("a")], ["counting"], cache_dir, n=4)
        # sizes {1,2,4} on the 4-dev topology: all missed despite overlapping
        # sizes with the 8-dev run (topology signature differs)
        assert len(CountingTech.calls) == 3

    def test_schedule_set_change_misses(self, tmp_path, monkeypatch):
        """Round 20: the pipeline schedule set is part of the fingerprint —
        a profile recorded under a gpipe-only sweep must miss once 1F1B
        joins the grid (execution would route cached configs differently)."""
        cache_dir = str(tmp_path / "cache")
        monkeypatch.setattr(pcache, "schedule_signature", lambda: "gpipe-only")
        run_search([FakeTask("a")], ["counting"], cache_dir)
        CountingTech.calls = []
        monkeypatch.setattr(
            pcache, "schedule_signature", lambda: "gpipe+1f1b:v1")
        run_search([FakeTask("a")], ["counting"], cache_dir)
        assert len(CountingTech.calls) == 4  # every size re-trialed

    def test_schedule_signature_resolves_from_ops(self):
        from saturn_tpu.ops.pipeline import SCHEDULE_SET_VERSION

        assert pcache.schedule_signature() == SCHEDULE_SET_VERSION

    def test_corrupt_and_stale_entries_are_misses(self, tmp_path):
        cache_dir = str(tmp_path / "cache")
        run_search([FakeTask("a")], ["counting"], cache_dir)
        files = [f for f in os.listdir(cache_dir) if f.endswith(".json")]
        assert len(files) == 4
        # corrupt half the files, swap the rest's key field (stale/foreign)
        for i, fn in enumerate(sorted(files)):
            p = os.path.join(cache_dir, fn)
            if i % 2 == 0:
                with open(p, "w") as f:
                    f.write("{not json at all")
            else:
                with open(p) as f:
                    e = json.load(f)
                e["key"] = "0" * 64
                with open(p, "w") as f:
                    json.dump(e, f)
        CountingTech.calls = []
        run_search([FakeTask("a")], ["counting"], cache_dir)  # must not raise
        assert len(CountingTech.calls) == 4  # everything re-profiled

    def test_infeasible_outcomes_are_cached(self, tmp_path):
        cache_dir = str(tmp_path / "cache")
        run_search([FakeTask("a")], ["memwall"], cache_dir, prune=False)
        # descending sizes: 8 feasible, 4 memory-infeasible, 1/2 pruned
        assert MemoryWallTech.calls == [("a", 8), ("a", 4)]
        MemoryWallTech.calls = []
        t2 = FakeTask("a")
        run_search([t2], ["memwall"], cache_dir, prune=False)
        # hit on 8 (feasible) and 4 (memory-infeasible) -> 1/2 pruned again
        assert MemoryWallTech.calls == []
        assert t2.strategies[8].feasible
        for g in (1, 2, 4):
            assert not t2.strategies[g].feasible

    def test_note_realized_upgrades_entry(self, tmp_path):
        cache = pcache.ProfileCache(str(tmp_path / "c"))
        key = pcache.fingerprint("sig", "dp", 4, "topo")
        cache.put(key, technique="dp", size=4, feasible=True,
                  params={"remat": False}, per_batch_time=0.5)
        assert cache.note_realized(key, 0.8, None, technique="dp", size=4)
        e = cache.get(key)
        assert e["per_batch_time"] == pytest.approx(0.8)
        assert e["source"] == "realized"
        assert e["params"] == {"remat": False}  # kept from the trial entry


class TestPruning:
    def test_anchors_only_full_table(self, tmp_path):
        """Acceptance: with pruning on a >= 4-size grid, at most the anchor
        sizes are compiled per (task, technique), yet every valid size has a
        strategy entry (interpolated ones flagged) and the solver still
        plans on the result."""
        t = FakeTask("a")
        run_search([t], ["counting"], None, prune=True)
        sizes_run = sorted(g for _, g in CountingTech.calls)
        assert sizes_run == [1, 4, 8]  # min, midpoint, max of {1,2,4,8}
        assert set(t.strategies) == {1, 2, 4, 8}
        assert not t.strategies[1].interpolated
        assert not t.strategies[4].interpolated
        assert not t.strategies[8].interpolated
        s2 = t.strategies[2]
        assert s2.feasible and s2.interpolated
        # the Amdahl fit over exact a + b/g points reproduces the law
        assert s2.per_batch_time == pytest.approx(0.08 / 2 + 0.02, rel=1e-6)
        assert s2.params == {"knob": 1} or s2.params == {"knob": 4}

        from saturn_tpu.solver.milp import solve

        plan = solve([t], topo(8), time_limit=10.0)
        assert t.name in plan.assignments

    def test_small_grids_not_pruned(self, tmp_path):
        t = FakeTask("a")
        t.chip_range = [1, 2, 4]
        run_search([t], ["counting"], None, prune=True)
        assert sorted(g for _, g in CountingTech.calls) == [1, 2, 4]
        assert not any(s.interpolated for s in t.strategies.values())

    def test_memory_infeasibility_propagates_down(self, tmp_path):
        """A memory rejection at size g skips every smaller size (per-chip
        memory there is >= the rejected size's) instead of compiling it."""
        t = FakeTask("a")
        mpath = str(tmp_path / "m.jsonl")
        run_search([t], ["memwall"], None, prune=True, metrics_path=mpath)
        # anchors {1, 4, 8} descending: 8 feasible, 4 memory-infeasible,
        # 1 pruned without a search; non-anchor 2 pruned in the fill pass
        assert MemoryWallTech.calls == [("a", 8), ("a", 4)]
        assert t.strategies[8].feasible
        for g in (1, 2, 4):
            assert not t.strategies[g].feasible
        pruned = read_events(mpath, "trial_pruned")
        assert {e["size"] for e in pruned} == {1, 2}
        assert all(e["reason"] == "memory_monotone" for e in pruned)

    def test_interpolation_skipped_without_signal(self, tmp_path):
        """One measured point is no scaling model: unmeasured sizes stay
        infeasible dummies rather than fabricated estimates."""

        class OnlyMax(CountingTech):
            name = "onlymax"
            calls = []

            def search(self, task, devices, tid):
                type(self).calls.append((task.name, len(devices)))
                if len(devices) < 8:
                    return None, None  # infeasible, but NOT memory-reported
                return {}, 0.01

        library.register("onlymax", OnlyMax)
        try:
            t = FakeTask("a")
            run_search([t], ["onlymax"], None, prune=True)
            # no memory report -> no propagation: all anchors searched
            assert sorted(g for _, g in OnlyMax.calls) == [1, 4, 8]
            assert t.strategies[8].feasible
            assert not t.strategies[2].feasible  # dummy, not interpolated
        finally:
            library.deregister("onlymax")


class TestCompileCachePlacement:
    """One function places JAX's persistent compilation cache."""

    @pytest.fixture()
    def decide(self, monkeypatch, tmp_path):
        import jax

        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        monkeypatch.setattr(pcache, "default_compile_cache_dir",
                            lambda: str(tmp_path / ".jax_compile_cache"))

        def run(backend, env):
            monkeypatch.setattr(jax, "default_backend", lambda: backend)
            if env is None:
                monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            else:
                monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
            updates.clear()
            # the undecorated function: the decision itself, uncached
            return pcache.maybe_enable_persistent_compile_cache.__wrapped__()

        return run, updates

    def test_env_set_means_no_directory_is_set_in_code(self, decide):
        run, updates = decide
        assert run("tpu", "/somewhere/outside") == "/somewhere/outside"
        assert "jax_compilation_cache_dir" not in updates
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == 0

    def test_tpu_default_is_the_fixed_checkout_path(self, decide, tmp_path):
        run, updates = decide
        want = str(tmp_path / ".jax_compile_cache")
        assert run("tpu", None) == want
        assert updates["jax_compilation_cache_dir"] == want
        assert os.path.isdir(want)

    def test_cpu_default_is_off(self, decide):
        run, updates = decide
        assert run("cpu", None) is None
        assert updates == {}

    def test_default_path_is_beside_the_package(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert pcache.default_compile_cache_dir() == os.path.join(
            repo, ".jax_compile_cache")


class TestTrialErrorsReported:
    def test_raising_config_is_counted_and_sweep_completes(self):
        """A candidate config that RAISES (on the chip: a kernel variant that
        fails to lower) is counted into the dict ``search()`` returns — the
        sweep still completes, and the technique's other config still wins."""
        from saturn_tpu.parallel.dp import DataParallel

        class OneBadConfig(DataParallel):
            name = "onebad"

            def candidate_configs(self, task, n_devices):
                return [{"variant": "kernel"}, {"variant": "dense"}]

            def _prepare(self, task, devices, config):
                if config["variant"] == "kernel":
                    raise RuntimeError("kernel failed to lower")
                return config

            def _measure(self, task, prepared):
                return 0.01, 0.0

        library.register("onebad", OneBadConfig)
        try:
            t = FakeTask("a")
            stats = evaluator.search(
                [t], technique_names=["onebad"], topology=topo(1),
                profile_cache=False, prune=False,
            )
        finally:
            library.deregister("onebad")
        assert stats["errors"] == 1
        assert "kernel failed to lower" in stats["first_error"]
        assert stats["trials_run"] == 1
        assert t.strategies[1].feasible
        assert t.strategies[1].params == {"variant": "dense"}

    def test_clean_sweep_reports_zero_errors(self):
        stats = evaluator.search(
            [FakeTask("a")], technique_names=["counting"], topology=topo(2),
            profile_cache=False, prune=False,
        )
        assert stats["errors"] == 0 and stats["first_error"] is None

    def test_indivisible_batch_is_a_verdict_not_an_error(self):
        """``InfeasibleConfig`` (here: nothing divides) marks the size
        infeasible without counting as an error."""
        from saturn_tpu.core.technique import InfeasibleConfig
        from saturn_tpu.parallel.dp import DataParallel

        class NeverFits(DataParallel):
            name = "neverfits"

            def candidate_configs(self, task, n_devices):
                return [{}]

            def _prepare(self, task, devices, config):
                raise InfeasibleConfig("batch_size 6 not divisible by data=4")

        library.register("neverfits", NeverFits)
        try:
            t = FakeTask("a")
            stats = evaluator.search(
                [t], technique_names=["neverfits"], topology=topo(1),
                profile_cache=False, prune=False,
            )
        finally:
            library.deregister("neverfits")
        assert stats["errors"] == 0
        assert not t.strategies[1].feasible


class TestRealizedFeedbackUpgrade:
    def test_feedback_clears_interpolated_flag(self, tiny_task):
        s = Strategy(object(), 2, {"remat": False}, 5.0, per_batch_time=0.5,
                     interpolated=True, cache_key="k")
        tiny_task.strategies[2] = s
        tiny_task.select_strategy(2)
        tiny_task.note_realized_per_batch(0.3)
        upd = tiny_task.apply_realized_feedback()
        assert upd is not None
        assert s.interpolated is False
        assert tiny_task.last_feedback_strategy is s


class TestEtaTracker:
    def test_running_average(self):
        eta = evaluator._EtaTracker(planned=4, hits=2, deferred=1)
        assert "4 trials to run" in eta.start_message()
        assert "2 profile-cache hits" in eta.start_message()
        msg = eta.trial_done(2.0)
        assert "1/4" in msg and "avg 2.0s/trial" in msg and "ETA 6s" in msg
        eta.trial_pruned()
        msg = eta.trial_done(4.0)
        assert "2/3" in msg and "avg 3.0s/trial" in msg and "ETA 3s" in msg
