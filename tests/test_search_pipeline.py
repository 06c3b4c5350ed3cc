"""``SPMDTechnique.search`` as two stages: the caller's thread walks the grid
and prepares each point (build, compile, memory check) while a measuring
thread behind it measures (init, stage, timing), one point at a time.

The technique under test is ``SPMDTechnique`` itself over stub bundles: a
stub says how its point ends (timed, refused by the compiler, over the
memory rule, infeasible, raising) and how long its build and its timed
program sleep, so every case is a matter of milliseconds on any host. The
outcomes of case (i) were pinned on the serial walk (one thread, the
technique's order) before the pipeline replaced it.
"""

import threading
import time
import types
from collections import Counter

import jax
import numpy as np
import pytest

from perf.lib import spans as interval_math
from saturn_tpu.core.technique import InfeasibleConfig
from saturn_tpu.parallel import spmd_base
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.resilience.crash import SimulatedKill
from saturn_tpu.utils import aot_cache, metrics, point_records, profile_cache

LIMIT_S = 60.0          # the time limit of a case: none may hang
HBM = 1 << 30           # what the memory rule runs against (the CPU reports none)
MEAS = "meas-"          # the measuring thread's name starts with this


def within_limit(fn, limit_s=LIMIT_S):
    """``fn()`` on a thread of its own, joined with a time limit; its result,
    or its exception raised here."""
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # re-raised below, on the test's thread
            box["exc"] = e

    t = threading.Thread(target=run, name="case", daemon=True)
    t.start()
    t.join(limit_s)
    assert not t.is_alive(), f"search hung for {limit_s:.0f} s"
    if "exc" in box:
        raise box["exc"]
    return box["out"]


class Dataset:
    batch_size = 2

    def batch(self, i):
        return np.full((2, 4), i, np.int32)


class Task:
    def __init__(self, name="piped"):
        self.name = name

    def get_model(self, **kw):
        return types.SimpleNamespace()

    def get_dataset(self):
        return Dataset()


class State:
    """Train state of a stub: counts how many are alive."""

    def __init__(self, book):
        self.book = book
        book.alive += 1
        book.most_alive = max(book.most_alive, book.alive)

    def __del__(self):
        self.book.alive -= 1


class Program:
    """The compiled window program of a stub point."""

    def __init__(self, point, book):
        self.point, self.book = point, book

    def memory_analysis(self):
        return types.SimpleNamespace(
            temp_size_in_bytes=self.point.get("need", 1024),
            argument_size_in_bytes=0, output_size_in_bytes=0,
            alias_size_in_bytes=0)

    def __call__(self, state, window):
        self.book.log("step", self.point)
        if self.point.get("run") == "raise":
            raise RuntimeError("RESOURCE_EXHAUSTED: no room to run")
        if self.point.get("run") == "kill":
            raise SimulatedKill("killed while timing")
        time.sleep(self.point.get("step_s", 0.0))
        return state, np.zeros((8,), np.float32)


class Init:
    """A stub's jitted init: compiled where the point is prepared (no state
    yet), called where it is measured."""

    def __init__(self, bundle):
        self.point, self.book = bundle.point, bundle.book

    def lower(self):
        self.book.log("init_compile", self.point)
        return types.SimpleNamespace(compile=lambda: None)

    def __call__(self):
        self.book.log("init", self.point)
        return State(self.book)


class Bundle:
    """What ``_prepare`` and ``_measure`` need of a ``_Bundle``."""

    step_traces = 1
    _compiled = None

    def __init__(self, point, book):
        self.point, self.book = point, book
        self._program = None

    def has_fused(self, k):
        return self._program is not None

    @property
    def plans(self):
        """What the step's ops were traced as: a fused head in the mode the
        stub says (``recompute`` if it says nothing), none where ``inert``."""
        if self.point.get("inert"):
            return {}
        return {"ce": (types.SimpleNamespace(
            mode=self.point.get("mode", "recompute")),)}

    def fused_compiled(self, k):
        self.book.log("compile", self.point)
        if self.point.get("compile") == "refuse":
            raise aot_cache.CompileRefused(
                "RESOURCE_EXHAUSTED: the program needs 17.1G of 15.7G hbm",
                "recorded", "jit_saturn_window")
        if self._program is None:
            self._program = Program(self.point, self.book)
        return self._program

    def stacked_sharding(self):
        return jax.sharding.SingleDeviceSharding(jax.devices()[0])

    @property
    def init(self):
        return Init(self)

    @property
    def traced(self):
        raise RuntimeError("a stub keeps no trace")  # memlens: best effort


class Book:
    """What the stubs saw, in order, with the thread each call ran on."""

    def __init__(self):
        self.calls = []
        self.alive = self.most_alive = 0
        self._lock = threading.Lock()

    def log(self, what, point):
        with self._lock:
            self.calls.append((what, point["id"], time.perf_counter(),
                               threading.current_thread().name))

    def order(self, what):
        return [c[1] for c in self.calls if c[0] == what]


class Stubbed(SPMDTechnique):
    """``SPMDTechnique``'s own ``search`` over a grid of stub points."""

    name = "stubbed"

    def __init__(self, points):
        super().__init__()
        self.points = points
        self.book = Book()
        self._built = {}

    def candidate_configs(self, task, n_devices):
        return [{k: p[k] for k in ("id", "remat") if k in p}
                for p in self.points]

    def build(self, task, devices, config, use_cache=True):
        (point,) = [p for p in self.points if p["id"] == config["id"]]
        return self._bundle_of(point)

    def _bundle_of(self, point):
        hit = self._built.get(point["id"])
        if hit is not None:
            return hit
        self.book.log("build", point)
        time.sleep(point.get("build_s", 0.0))
        how = point.get("build")
        if how == "infeasible":
            raise InfeasibleConfig("batch_size 2 not divisible by data=4")
        if how == "raise":
            raise ValueError("kernel variant failed to lower")
        if how == "kill":
            raise SimulatedKill("killed while building")
        self._built[point["id"]] = Bundle(point, self.book)
        return self._built[point["id"]]


@pytest.fixture()
def run(tmp_path, monkeypatch):
    """search(points) -> (technique, winner, report, events)."""
    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))

    def go(points, name="piped", technique=Stubbed):
        tech = technique(points)
        path = str(tmp_path / f"{name}.jsonl")
        with metrics.scoped(path):
            with metrics.span("search"):
                best = within_limit(
                    lambda: tech.search(Task(name), jax.devices()[:1], 0))
        events = metrics.read_events(path)
        return tech, best, tech.search_report(name, 1), events
    return go


def no_measuring_thread_left():
    deadline = time.time() + 5.0
    while time.time() < deadline:
        left = [t.name for t in threading.enumerate()
                if t.name.startswith(MEAS)]
        if not left:
            return True
        time.sleep(0.01)
    return False


def of_kind(events, kind):
    return [e for e in events if e["kind"] == kind]


def interval(e):
    return e["ts_start"], e["ts_start"] + e["dur_s"]


# (i) ------------------------------------------------ every way a point ends
MIXED = [
    {"id": "over", "remat": False, "need": HBM},           # over 0.92 x HBM
    {"id": "refused", "remat": False, "compile": "refuse"},
    {"id": "slow", "remat": True, "step_s": 0.04},
    {"id": "fast", "remat": True, "step_s": 0.01},
    {"id": "odd", "build": "infeasible"},
    {"id": "broken", "build": "raise"},
]


def test_mixed_grid_ends_as_the_serial_walk_did(run):
    tech, (config, t), report, events = run(MIXED)
    assert config == {"id": "fast", "remat": True}
    assert 0.01 / 8 <= t < 0.04 / 8
    assert report == {
        "memory_infeasible": False, "configs": 6, "memory_rejected": 2,
        "errors": 1,
        "first_error": "stubbed {'id': 'broken'}: "
                       "ValueError('kernel variant failed to lower')",
        "refusals_fresh": 0, "refusals_replayed": 1, "refusals_unbuilt": 0,
        "prepared_ahead": report["prepared_ahead"],
    }
    assert Counter(e["outcome"] for e in of_kind(events, "trial.config")) == {
        "timed": 2, "refused": 1, "memory_rejected": 1, "infeasible": 1,
        "error": 1}
    notes = {e["config"]["id"]: e for e in of_kind(events, "trial_config")}
    assert len(of_kind(events, "trial_config")) == len(notes) == 6
    assert notes["over"]["memory_rejected"] is True and "refusal" not in notes["over"]
    assert notes["refused"]["memory_rejected"] is True
    assert notes["refused"]["refusal"] == "recorded"
    assert notes["refused"]["compiler"].startswith("RESOURCE_EXHAUSTED")
    assert notes["odd"]["infeasible"] == "batch_size 2 not divisible by data=4"
    assert notes["broken"]["error"] == "ValueError('kernel variant failed to lower')"
    assert notes["fast"]["per_batch_s"] == t
    assert notes["slow"]["per_batch_s"] > t
    for e in notes.values():
        assert e["task"] == "piped" and e["size"] == 1
        assert e["technique"] == "stubbed"
    by = {e["config"]["id"]: e for e in of_kind(events, "trial.config")}
    assert by["odd"]["reason"] == notes["odd"]["infeasible"]
    assert by["refused"]["refusal"] == "recorded"
    assert tech.host_fraction_report("piped", 1) is not None
    # the init program is compiled where a point is prepared, if it fits
    assert tech.book.order("init_compile") == ["slow", "fast"]
    # ... on the caller's thread, like every build; the states are made and
    # the steps taken on the measuring thread
    assert {c[3] for c in tech.book.calls
            if c[0] in ("build", "init_compile")} == {"case"}
    assert {c[3] for c in tech.book.calls
            if c[0] in ("init", "step")} == {"meas-case"}
    assert no_measuring_thread_left()


def test_a_point_that_raises_while_it_runs_is_an_error(run):
    """``RESOURCE_EXHAUSTED`` out of a program that runs is a config that
    raised, on the measuring thread, and the grid goes on."""
    tech, (config, _), report, events = run([
        {"id": "a", "remat": True, "run": "raise"},
        {"id": "b", "remat": False, "step_s": 0.01},
    ])
    assert config == {"id": "b", "remat": False}
    assert report["errors"] == 1 and report["memory_rejected"] == 0
    assert "RESOURCE_EXHAUSTED" in report["first_error"]
    assert Counter(e["outcome"] for e in of_kind(events, "trial.config")) == {
        "error": 1, "timed": 1}
    assert no_measuring_thread_left()


# (ii) ------------------------------------------------------------ the overlap
SLEEPY = [{"id": f"p{i}", "remat": True, "build_s": 0.15, "step_s": 0.2}
          for i in range(3)]    # a timing is three calls: 0.6 s


def test_a_later_build_lies_inside_an_earlier_timing(run):
    t0 = time.perf_counter()
    tech, best, report, events = run(SLEEPY)
    wall = time.perf_counter() - t0
    timings = {e["parent"]: interval(e) for e in of_kind(events, "trial.timing")}
    points = {e["id"]: e["config"]["id"] for e in of_kind(events, "trial.config")}
    builds = {points[e["parent"]]: e for e in of_kind(events, "trial.build")}
    t_first = [iv for parent, iv in timings.items() if points[parent] == "p0"][0]
    # p1 is built while p0 is put on the chip and staged; p2 under its timing
    b_later = interval(builds["p2"])
    assert t_first[0] <= b_later[0] and b_later[1] <= t_first[1], (
        t_first, b_later)
    assert builds["p2"]["thread"] == "case"
    assert of_kind(events, "trial.timing")[0]["thread"].startswith(MEAS)
    serial = sum(e["dur_s"] for e in events
                 if e["kind"] in ("trial.build", "trial.timing"))
    assert wall < serial - 0.2, (wall, serial)
    assert report["prepared_ahead"] == 2   # all but the first were waiting
    assert best[0] is not None and report["configs"] == 3


# (iii) ---------------------------------------- one state, one point measured
def test_init_waits_for_the_timing_before_it(run):
    tech, _, _, events = run(SLEEPY)
    assert tech.book.most_alive == 1 and tech.book.alive == 0
    points = {e["id"]: e["config"]["id"] for e in of_kind(events, "trial.config")}
    inits = [(points[e["parent"]], interval(e))
             for e in of_kind(events, "trial.init")]
    timings = [(points[e["parent"]], interval(e))
               for e in of_kind(events, "trial.timing")]
    assert len(inits) == len(timings) == 3
    for p, (lo, hi) in inits:
        for q, (t_lo, t_hi) in timings:
            if p != q:
                assert hi <= t_lo or lo >= t_hi, (p, q)
    # one measuring thread, for every point
    mine = {e["thread"] for e in events
            if e["kind"] in ("trial.init", "trial.stage", "trial.timing")}
    assert mine == {"meas-case"}
    # and the stubs agree: no init between another point's first and last step
    calls = tech.book.calls
    for i, (what, p, _, _) in enumerate(calls):
        if what == "init":
            before = {c[1] for c in calls[:i] if c[0] == "step"}
            after = {c[1] for c in calls[i:] if c[0] == "step"}
            assert not (before & after) - {p}


# (iv) ------------------------------------------------- the order, and a tie
GRID = [
    {"id": "plain-a", "remat": False}, {"id": "plain-b", "remat": False},
    {"id": "remat-a", "remat": True}, {"id": "remat-b", "remat": True},
    {"id": "silent"},                   # says nothing of remat: as remat off
]


def test_remat_points_are_prepared_first(run):
    tech, _, _, events = run(GRID)
    want = ["remat-a", "remat-b", "plain-a", "plain-b", "silent"]
    assert tech.book.order("build") == want
    assert tech.book.order("init") == want
    assert [e["config"]["id"] for e in of_kind(events, "trial_config")] == want


class Twinned(Stubbed):
    """A grid whose points differ in ``remat`` alone: a stub is found by its
    ``attention`` and its ``remat`` (``Stubbed`` finds it by an ``id`` no
    two points share, so none of its points is another's twin)."""

    def candidate_configs(self, task, n_devices):
        return [{k: p[k] for k in ("attention", "remat")} for p in self.points]

    def build(self, task, devices, config, use_cache=True):
        (point,) = [p for p in self.points
                    if (p["attention"], p["remat"]) == (config["attention"], config["remat"])]
        key = point["id"]
        if key not in self._built:
            self.book.log("build", point)
            self._built[key] = Bundle(point, self.book)
        return self._built[key]


#: how the ``remat: True`` point of a pair ends -> how its twin must
TWINS = {
    "refused": ({"compile": "refuse"}, "implied"),
    "over": ({"need": HBM}, "implied"),
    "fits": ({"step_s": 0.01}, "built"),
}


@pytest.mark.parametrize("case", sorted(TWINS))
def test_a_point_is_over_memory_where_its_remat_twin_was(case, tmp_path, monkeypatch):
    """Rematerialisation only lowers a program's peak: where the point with
    it was refused by the compiler or by the memory rule, the one without it
    ends ``memory_rejected`` and is never built; where it fitted, the one
    without it is prepared as before. Another ``attention`` is no twin."""
    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    frugal, want = TWINS[case]
    tech = Twinned([
        {"id": "plain-dense", "attention": "dense", "remat": False, "step_s": 0.02},
        {"id": "plain-flash", "attention": "flash", "remat": False, "step_s": 0.02},
        {"id": "remat-dense", "attention": "dense", "remat": True, **frugal},
        {"id": "remat-flash", "attention": "flash", "remat": True, "step_s": 0.03},
    ])
    path = str(tmp_path / "twins.jsonl")
    with metrics.scoped(path):
        with metrics.span("search"):
            best = within_limit(lambda: tech.search(Task("twins"), jax.devices()[:1], 0))
    events = metrics.read_events(path)
    report = tech.search_report("twins", 1)
    notes = {(e["config"]["attention"], e["config"]["remat"]): e
             for e in of_kind(events, "trial_config")}
    spans_ = {(e["config"]["attention"], e["config"]["remat"]): e
              for e in of_kind(events, "trial.config")}
    assert len(notes) == len(spans_) == 4 and report["configs"] == 4
    built = tech.book.order("build")
    if want == "implied":
        assert "plain-dense" not in built and built[:2] == ["remat-dense", "remat-flash"]
        assert notes["dense", False]["memory_rejected"] is True
        assert notes["dense", False]["implied_by"] == "remat"
        assert spans_["dense", False]["outcome"] == "memory_rejected"
        assert spans_["dense", False]["implied_by"] == "remat"
        assert "step_traces" not in notes["dense", False]
        assert "implied_by" not in notes["dense", True]
        assert report["memory_rejected"] == 2
        assert best[0] == {"attention": "flash", "remat": False}
    else:
        assert sorted(built) == ["plain-dense", "plain-flash", "remat-dense", "remat-flash"]
        assert report["memory_rejected"] == 0
        assert all("implied_by" not in e for e in notes.values())
        assert best[0]["remat"] is True and best[0]["attention"] == "dense"
    # the flash pair has its own twin, which fitted
    assert "plain-flash" in built and "implied_by" not in notes["flash", False]
    assert no_measuring_thread_left()


# ------------------ a verdict on record by what the point is made from (PR 47)
@pytest.fixture()
def recorded(tmp_path, monkeypatch):
    """Point records on, at a temp directory, for stubs: a stub task has no
    ``ModelSpec`` to be written down, so a point's identity here is its
    config's (the real key is ``tests/test_compile_refusals.py``'s). Returns
    the record files' names."""
    import hashlib
    import json
    import os

    root = tmp_path / "xla-cache"
    root.mkdir()
    monkeypatch.setattr(profile_cache, "maybe_enable_persistent_compile_cache",
                        lambda: str(root))
    monkeypatch.setattr(
        point_records, "_key", lambda technique, task, devices, config, k:
        hashlib.sha256(json.dumps(config, sort_keys=True).encode()).hexdigest())

    def names():
        folder = root / "saturn-refused"
        return sorted(os.listdir(folder)) if folder.exists() else []
    return names


def test_points_on_record_end_unbuilt_and_the_rest_as_before(run, recorded):
    tech, (config, _), report, events = run(MIXED, name="first")
    assert config == {"id": "fast", "remat": True}
    assert report["refusals_unbuilt"] == 0 and len(recorded()) == 2  # over, refused
    assert sorted(tech.book.order("build")) == sorted(p["id"] for p in MIXED)
    assert all(e["identity"] and not e["hit"] for e in of_kind(events, "trial.identity"))

    tech, (config, t), report, events = run(MIXED, name="second")
    assert config == {"id": "fast", "remat": True}
    assert sorted(tech.book.order("build")) == ["broken", "fast", "odd", "slow"]
    assert report == {
        "memory_infeasible": False, "configs": 6, "memory_rejected": 2,
        "errors": 1,
        "first_error": "stubbed {'id': 'broken'}: "
                       "ValueError('kernel variant failed to lower')",
        "refusals_fresh": 0, "refusals_replayed": 2, "refusals_unbuilt": 2,
        "prepared_ahead": report["prepared_ahead"],
    }
    notes = {e["config"]["id"]: e for e in of_kind(events, "trial_config")}
    assert len(of_kind(events, "trial_config")) == 6
    for name, outcome in (("over", "memory_rejected"), ("refused", "refused")):
        e = notes[name]
        assert e["unbuilt"] is True and e["refusal"] == "recorded"
        assert e["memory_rejected"] is True and "step_traces" not in e
        (span,) = [s for s in of_kind(events, "trial.config")
                   if s["config"]["id"] == name]
        assert span["outcome"] == outcome and span["unbuilt"] is True
        assert [s["kind"] for s in events if s.get("parent") == span["id"]] == \
            ["trial.identity"]
    assert notes["refused"]["compiler"].startswith("RESOURCE_EXHAUSTED")
    assert "compiler" not in notes["over"]
    assert all("unbuilt" not in notes[n] for n in ("slow", "fast", "odd", "broken"))
    assert len(recorded()) == 2       # infeasible, error and timed leave none
    assert no_measuring_thread_left()


def test_a_point_that_runs_out_of_room_leaves_no_record(run, recorded):
    _, best, report, _ = run([{"id": "a", "remat": True, "run": "raise"},
                              {"id": "b", "remat": True, "step_s": 0.01}])
    assert best[0]["id"] == "b" and report["errors"] == 1
    assert recorded() == []


def test_a_point_that_fits_takes_its_record_away(run, recorded, monkeypatch):
    run([{"id": "a", "need": HBM}, {"id": "b", "step_s": 0.01}], name="first")
    assert len(recorded()) == 1
    # the record outlives its cause only as long as nothing asks: a changed
    # source file makes it miss, the point takes the full path and now fits
    monkeypatch.setattr(point_records, "_manifest_holds", lambda manifest: False)
    tech, best, report, events = run(
        [{"id": "a", "step_s": 0.001}, {"id": "b", "step_s": 0.01}], name="second")
    assert best[0]["id"] == "a" and report["refusals_unbuilt"] == 0
    assert recorded() == []


def test_a_stale_record_may_cost_a_point_never_a_job(run, recorded):
    """Rule 4: every point of the grid on record and none timed. All of them
    run again in full before memory is reported, and a job that fits now is
    found feasible (the records' cause is gone and nothing told them)."""
    over = [{"id": "a", "remat": True, "need": HBM},
            {"id": "b", "remat": True, "compile": "refuse"}]
    tech, best, report, _ = run(over, name="first")
    assert best == (None, None) and report["memory_infeasible"] is True
    assert len(recorded()) == 2 and tech.book.order("build") == ["a", "b"]

    # still over: ended unbuilt, then run again in full, then reported
    tech, best, report, events = run(over, name="second")
    assert best == (None, None) and report["memory_infeasible"] is True
    assert report["memory_rejected"] == 2 and report["configs"] == 2
    assert report["refusals_unbuilt"] == 0 and tech.book.order("build") == ["a", "b"]
    notes = of_kind(events, "trial_config")
    assert [(e["config"]["id"], e.get("unbuilt", False)) for e in notes] == [
        ("a", True), ("b", True), ("a", False), ("b", False)]
    assert len(recorded()) == 2

    # the same points (the stubs' identity is their config) fit now
    fits = [{"id": "a", "remat": True, "step_s": 0.02},
            {"id": "b", "remat": True, "step_s": 0.01}]
    tech, best, report, events = run(fits, name="third")
    assert best[0] == {"id": "b", "remat": True}
    assert report["memory_infeasible"] is False and report["memory_rejected"] == 0
    assert report["refusals_unbuilt"] == 0 and report["configs"] == 2
    assert tech.book.order("build") == ["a", "b"] and recorded() == []
    assert tech.host_fraction_report("third", 1) is not None
    assert no_measuring_thread_left()


def test_one_timed_point_and_the_records_are_believed(run, recorded):
    """Rule 4 is for a search that found nothing: with a timed point the
    unbuilt ones stay unbuilt."""
    grid = [{"id": "a", "remat": True, "need": HBM},
            {"id": "b", "remat": True, "step_s": 0.01}]
    run(grid, name="first")
    tech, best, report, events = run(grid, name="second")
    assert best[0]["id"] == "b" and tech.book.order("build") == ["b"]
    assert report["refusals_unbuilt"] == 1 and report["memory_rejected"] == 1
    assert len(of_kind(events, "trial_config")) == 2


@pytest.mark.parametrize("case", ["refused", "over"])
def test_implied_by_remat_follows_from_a_replayed_twin(case, tmp_path, monkeypatch,
                                                       recorded):
    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    frugal, _ = TWINS[case]
    points = [
        {"id": "plain-dense", "attention": "dense", "remat": False, "step_s": 0.02},
        {"id": "plain-flash", "attention": "flash", "remat": False, "step_s": 0.02},
        {"id": "remat-dense", "attention": "dense", "remat": True, **frugal},
        {"id": "remat-flash", "attention": "flash", "remat": True, "step_s": 0.03},
    ]
    for tag in ("first", "second"):
        tech = Twinned(points)
        path = str(tmp_path / f"{tag}.jsonl")
        with metrics.scoped(path):
            with metrics.span("search"):
                best = within_limit(
                    lambda: tech.search(Task("twins"), jax.devices()[:1], 0))
        events = metrics.read_events(path)
        notes = {(e["config"]["attention"], e["config"]["remat"]): e
                 for e in of_kind(events, "trial_config")}
        assert best[0] == {"attention": "flash", "remat": False}
        assert notes["dense", False]["implied_by"] == "remat"
        assert "unbuilt" not in notes["dense", False]
        assert len(recorded()) == 1       # the implied end has no record of its own
    # in the second search the twin itself was never built, and its twin followed
    assert notes["dense", True]["unbuilt"] is True
    assert sorted(tech.book.order("build")) == ["plain-flash", "remat-flash"]
    assert tech.search_report("twins", 1)["refusals_unbuilt"] == 1
    assert no_measuring_thread_left()


def test_a_tie_goes_to_the_techniques_order(run, monkeypatch):
    monkeypatch.setattr(spmd_base, "time_fused_window",
                        lambda *a, **k: 0.125)
    _, (config, t), _, _ = run(GRID)
    assert (config, t) == ({"id": "plain-a", "remat": False}, 0.125)


def test_the_fastest_still_wins_wherever_it_stands(run):
    points = [dict(p, step_s=0.03) for p in GRID]
    points[1]["step_s"] = 0.0
    _, (config, _), _, _ = run(points)
    assert config == {"id": "plain-b", "remat": False}


def test_a_grid_of_one_runs_on_the_callers_thread(run):
    tech, (config, _), report, events = run([{"id": "only", "remat": False}])
    assert config == {"id": "only", "remat": False}
    assert {c[3] for c in tech.book.calls} == {"case"}
    assert {e["thread"] for e in events if e["kind"].startswith("trial.")} == {"case"}
    assert report["prepared_ahead"] == 0


# (v) ------------------------------------------- nothing left behind, no hang
def killed(points, match):
    """A search that a ``SimulatedKill`` ends; the technique, for what its
    stubs saw, once no measuring thread is left."""
    tech = Stubbed(points)
    with pytest.raises(SimulatedKill, match=match):
        within_limit(lambda: tech.search(Task(), jax.devices()[:1], 0))
    # no wait: the thread was joined before ``search`` let the kill through
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith(MEAS)]
    return tech


def test_a_killed_preparation_ends_the_search_and_its_thread():
    tech = killed([{"id": "a", "remat": True, "step_s": 0.05},
                   {"id": "b", "remat": True, "build": "kill"},
                   {"id": "c", "remat": False, "build_s": 0.05}],
                  "while building")
    assert tech.book.order("build") == ["a", "b"]   # and no further point


def test_a_killed_measurement_ends_the_search_and_its_thread():
    tech = killed([{"id": "a", "remat": True, "run": "kill"},
                   {"id": "b", "remat": True, "build_s": 0.3},
                   {"id": "c", "remat": False, "build_s": 0.3},
                   {"id": "d", "remat": False, "build_s": 0.3}],
                  "while timing")
    # the point in preparation is finished, none is started after it
    assert tech.book.order("build") == ["a", "b"]
    assert tech.book.order("init") == ["a"]


def test_every_point_raising_leaves_no_thread(run):
    tech, best, report, _ = run(
        [{"id": f"x{i}", "remat": bool(i % 2), "build": "raise"}
         for i in range(4)])
    assert best == (None, None)
    assert report["errors"] == report["configs"] == 4
    assert report["memory_infeasible"] is False
    assert no_measuring_thread_left()


def test_searches_side_by_side_on_one_technique_keep_their_points_apart(
        tmp_path, monkeypatch):
    """The evaluator's shape, harder: one technique instance, more searching
    threads than cores, each with a measuring thread of its own, under a
    switch interval that interleaves them everywhere. Every search still
    sees each of its points exactly once and reports its own winner."""
    import sys

    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    # a host this crowded times nothing to the millisecond: each point's time
    # is given, the first of the technique's order the fastest
    points = [dict(p, t=0.01 * (i + 1)) for i, p in enumerate(GRID)]
    points.append({"id": "refused", "remat": False, "compile": "refuse"})
    monkeypatch.setattr(spmd_base, "time_fused_window",
                        lambda fused, *a, **k: fused.point["t"])
    tech = Stubbed(points)
    tech.build = types.MethodType(         # no memo shared between the tasks
        lambda self, task, devices, config, use_cache=True: Bundle(
            next(p for p in points if p["id"] == config["id"]), self.book),
        tech)
    names = [f"job{i}" for i in range(12)]
    out, path = {}, str(tmp_path / "ev.jsonl")

    def one(name):
        out[name] = tech.search(Task(name), jax.devices()[:1], 0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with metrics.scoped(path), metrics.span("search"):
            threads = [threading.Thread(target=one, args=(n,), name=f"trial-{n}")
                       for n in names]
            for t in threads:
                t.start()
            for t in threads:
                t.join(LIMIT_S)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    events = metrics.read_events(path)
    for name in names:
        assert out[name][0] == {"id": "plain-a", "remat": False}, name
        report = tech.search_report(name, 1)
        assert report["configs"] == 6 and report["memory_rejected"] == 1
        assert report["errors"] == 0 and report["refusals_replayed"] == 1
        mine = [e for e in of_kind(events, "trial_config") if e["task"] == name]
        assert sorted(e["config"]["id"] for e in mine) == sorted(
            p["id"] for p in points)
    spans_ = of_kind(events, "trial.config")
    assert len(spans_) == 12 * 6
    assert Counter(e["outcome"] for e in spans_) == {"timed": 60, "refused": 12}
    assert no_measuring_thread_left()


# (vi) ------------------------------------- who waited for whom (PR 39)
HANDOFF_LIMIT_S = 10.0   # PR 38's wait never ended: these cases' own limit
HOST = ("trial.build", "trial.compile", "trial.memory_check", "trial.memlens")
CHIP = ("trial.init", "trial.stage", "trial.timing")
WAITS = ("trial.wait_prepared", "trial.wait_measured")
# a timing is three calls of the program
BOUND = {
    "host": [{"id": f"h{i}", "remat": True, "build_s": 0.2, "step_s": 0.02 / 3}
             for i in range(4)],
    "chip": [{"id": f"c{i}", "remat": True, "build_s": 0.02, "step_s": 0.2 / 3}
             for i in range(4)],
}


def spanned_search(tmp_path, monkeypatch, points, name="piped"):
    """The evaluator's shape: ``search`` > ``trial`` open on the thread that
    calls the technique's ``search``. (technique, what it raised or None,
    events); never longer than ``HANDOFF_LIMIT_S``."""
    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    tech = Stubbed(points)
    path = str(tmp_path / f"{name}.jsonl")

    def go():
        with metrics.span("search"), metrics.span("trial", task=name):
            return tech.search(Task(name), jax.devices()[:1], 0)

    raised = None
    with metrics.scoped(path):
        try:
            within_limit(go, HANDOFF_LIMIT_S)
        except SimulatedKill as e:
            raised = e
    return tech, raised, metrics.read_events(path)


def seconds(intervals):
    return interval_math.length(intervals)


def overlap(a, b):
    """Seconds in which a stretch of ``a`` and a stretch of ``b`` are open."""
    return seconds(a) + seconds(b) - seconds(list(a) + list(b))


@pytest.mark.parametrize("bound", sorted(BOUND))
def test_the_wait_spans_say_who_waited_for_whom(tmp_path, monkeypatch, bound):
    tech, raised, events = spanned_search(tmp_path, monkeypatch, BOUND[bound])
    assert raised is None and tech.search_report("piped", 1)["configs"] == 4
    (search,), (trial,) = of_kind(events, "search"), of_kind(events, "trial")
    wall = search["dur_s"]
    host = [interval(e) for e in events if e["kind"] in HOST]
    chip = [interval(e) for e in events if e["kind"] in CHIP]
    waits = {k: of_kind(events, k) for k in WAITS}
    # each wait is a child of ``trial`` on the thread that waited
    for e in waits["trial.wait_prepared"]:
        assert e["parent"] == trial["id"] and e["root"] == search["id"]
        assert e["thread"] == "meas-case" and e["dur_s"] >= 1e-3
        assert isinstance(e["ahead"], bool) and "error" not in e
    for e in waits["trial.wait_measured"]:
        assert e["parent"] == trial["id"] and e["thread"] == "case"
        assert e["dur_s"] >= 1e-3 and "error" not in e
    # the four classes, each by its own set arithmetic, add up to the wall
    both = overlap(host, chip)
    wait_for_host, wait_for_chip = seconds(host) - both, seconds(chip) - both
    own = wall - seconds(host + chip)
    assert wait_for_host + both + wait_for_chip + own == pytest.approx(
        wall, rel=0.02)
    assert 0 <= own <= 0.15 * wall, (own, wall)   # stubs keep no books
    # the cross-check: the measuring thread waits where no chip-side span is
    # open, the caller where no host-work span is
    prepared = [interval(e) for e in waits["trial.wait_prepared"]]
    measured = [interval(e) for e in waits["trial.wait_measured"]]
    assert overlap(prepared, chip) <= 0.005
    assert overlap(measured, host) <= 0.005
    # the measuring thread's life is its chip-side spans and its waits
    first = min(lo for lo, _ in prepared)
    idle = (max(hi for _, hi in chip) - first) - seconds(chip)
    if bound == "host":
        # it waited for every point (none was ahead), about a build each
        assert len(prepared) == 4
        assert not any(e["ahead"] for e in waits["trial.wait_prepared"])
        assert seconds(prepared) == pytest.approx(idle, abs=0.05)
        assert seconds(prepared) >= 0.6 and wait_for_host >= 0.6
        assert wait_for_chip <= 0.1
        # the caller found the last point measured within a timing
        assert seconds(measured) <= 0.1
    else:
        # only the first point was waited for; the rest were there
        assert len(prepared) == 1 and seconds(prepared) <= 0.1
        # the caller's idle time: from its last preparation to the end
        (joined,) = waits["trial.wait_measured"]
        last_prepared = max(hi for _, hi in host)
        assert joined["ts_start"] == pytest.approx(last_prepared, abs=0.05)
        assert joined["dur_s"] == pytest.approx(
            trial["ts_start"] + trial["dur_s"] - last_prepared, abs=0.05)
        assert joined["dur_s"] >= 0.5 and wait_for_chip >= 0.5
        assert wait_for_host <= 0.15
    assert no_measuring_thread_left()


def test_a_grid_of_one_waits_for_nobody(tmp_path, monkeypatch):
    _, raised, events = spanned_search(tmp_path, monkeypatch, BOUND["host"][:1])
    assert raised is None and len(of_kind(events, "trial.config")) == 1
    assert not [e for e in events if e["kind"] in WAITS]


KILLED = {
    # the caller is killed while the measuring thread waits for its point:
    # the join runs on the kill's way out and says so
    "preparation": [{"id": "a", "remat": True, "step_s": 0.05},
                    {"id": "b", "remat": True, "build_s": 0.3, "build": "kill"},
                    {"id": "c", "remat": False}],
    # the measuring thread is killed while the caller prepares: the caller
    # ends its point, starts no other, and finds the thread gone
    "measurement": [{"id": "a", "remat": True, "run": "kill"},
                    {"id": "b", "remat": True, "build_s": 0.3},
                    {"id": "c", "remat": False}],
}


@pytest.mark.parametrize("half", sorted(KILLED))
def test_a_kill_in_either_half_closes_the_wait_spans(tmp_path, monkeypatch,
                                                     half):
    tech, raised, events = spanned_search(tmp_path, monkeypatch, KILLED[half])
    assert isinstance(raised, SimulatedKill)
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith(MEAS)]        # joined, not left behind
    assert tech.book.order("build") == ["a", "b"]  # and no further point
    (trial,) = of_kind(events, "trial")
    assert trial["error"] == "SimulatedKill"
    waits = [e for e in events if e["kind"] in WAITS]
    # every wait that began has ended inside ``trial``, as its child
    for e in waits:
        assert e["parent"] == trial["id"]
        assert e["ts_start"] + e["dur_s"] <= trial["ts_start"] + trial["dur_s"] + 0.005
    by = {e["config"]["id"]: e for e in of_kind(events, "trial.config")}
    if half == "preparation":
        assert by["b"]["error"] == "SimulatedKill"
        # the measuring thread sat in the hand-off while "b" was built
        (waited,) = [e for e in of_kind(events, "trial.wait_prepared")
                     if e["dur_s"] >= 0.1]
        assert waited["thread"] == "meas-case" and waited["ahead"] is False
        # the join on the kill's way out carries it, however short
        (joined,) = of_kind(events, "trial.wait_measured")
        assert joined["error"] == "SimulatedKill" and joined["thread"] == "case"
    else:
        assert by["a"]["error"] == "SimulatedKill"
        assert "b" not in by   # prepared, never measured: it ended nowhere
        # no wait was under way when the kill came; none is left open
        assert all("error" not in e for e in waits)


# ------------------------------------- the clock, while another thread traces
def clock_settings():
    import gc
    import sys

    return gc.get_threshold(), sys.getswitchinterval()


def test_a_timed_region_keeps_full_collections_and_long_slices_out():
    from saturn_tpu.utils import timing

    before = clock_settings()
    quiet = ((*before[0][:2], timing._NO_FULL_COLLECTION),
             timing._SWITCH_INTERVAL_S)
    assert before[0][2] < timing._NO_FULL_COLLECTION
    assert before[1] > timing._SWITCH_INTERVAL_S
    seen = []

    def fused(state, window):
        seen.append(clock_settings())
        return state, np.zeros((8,), np.float32)

    assert timing.time_fused_window(fused, None, lambda j: j, 8) >= 0
    # the warm-up call runs as the process was; the two timed ones quietly
    assert seen == [before, quiet, quiet]
    assert clock_settings() == before
    seen.clear()
    timing.time_train_step(fused, None, 0, n_timed=2, n_warmup=1)
    assert seen == [before, quiet, quiet]
    assert clock_settings() == before


# ------------------------------ how many windows a point that fits is timed for
WARMUP_S, WINDOW_S = 0.08, 0.02      # what the fake program's calls sleep
FLOORS = {"past-the-floor": (WARMUP_S / 2, 1), "under-the-floor": (60.0, 2)}


@pytest.mark.parametrize("case", [*FLOORS, "no-warmup"])
def test_the_warmup_decides_how_many_windows_are_timed(case, monkeypatch):
    """A window whose warm-up call lasted the floor or more is timed once,
    a shorter one twice; either way under the quiet clock, each stack
    offered once, and the seconds a batch those of the windows timed."""
    from saturn_tpu.utils import timing

    if case == "no-warmup":
        with pytest.raises(ValueError, match="n_warmup >= 1"):
            timing.time_fused_window(lambda s, w: (s, 0), None, lambda j: j,
                                     8, n_warmup=0)
        return
    floor, n_timed = FLOORS[case]
    monkeypatch.setattr(timing, "_ONE_WINDOW_FLOOR_S", floor)
    before = clock_settings()
    quiet = ((*before[0][:2], timing._NO_FULL_COLLECTION),
             timing._SWITCH_INTERVAL_S)
    staged, offered, seen, starts, told = [], [], [], [], {}

    def fused(state, window):
        starts.append(time.perf_counter())
        offered.append(window)
        seen.append(clock_settings())
        time.sleep(WARMUP_S if len(offered) == 1 else WINDOW_S)
        return state, np.zeros((8,), np.float32)

    t = timing.time_fused_window(
        fused, None, lambda j: staged.append(j) or j, 8,
        note=lambda **fields: told.update(fields))
    end = time.perf_counter()
    # staged before anything ran, each offered once, the warm-up's first
    assert staged == [0, 1, 2] and offered == staged[:1 + n_timed]
    assert seen == [before] + [quiet] * n_timed
    assert clock_settings() == before
    # the timed windows' seconds over their batches: the clock starts after
    # the warm-up, and one window is not divided by two windows' batches
    assert WINDOW_S / 8 <= t <= (end - starts[1]) / (8 * n_timed)
    assert told["n_timed"] == n_timed and told["warmup_s"] >= WARMUP_S


#: the clock's four readings (round the warm-up, round the timed region),
#: warm-up calls, and the windows that should then be timed
CLOCKED = {
    "exactly-the-floor": ([0.0, 2.0, 5.0, 9.0], 1, 1),
    "a-hair-under-it": ([0.0, 1.999, 5.0, 9.0], 1, 2),
    # two warm-up calls of 1.5 s each: a call lasted 1.5 s, not 3
    "by-the-call-not-the-sum": ([0.0, 3.0, 5.0, 9.0], 2, 2),
}


@pytest.mark.parametrize("case", list(CLOCKED))
def test_the_floor_is_two_seconds_of_one_warmup_call(case, monkeypatch):
    """On a scripted clock, against the constant as the package has it."""
    from saturn_tpu.utils import timing

    readings, n_warmup, n_timed = CLOCKED[case]
    clock = iter(readings)
    monkeypatch.setattr(timing, "timeit", types.SimpleNamespace(
        default_timer=lambda: next(clock)))
    calls, told = [], {}
    t = timing.time_fused_window(
        lambda state, w: (calls.append(w) or state, np.zeros(())), None,
        lambda j: j, 8, n_warmup=n_warmup,
        note=lambda **fields: told.update(fields))
    assert calls == list(range(n_warmup + n_timed))
    assert t == pytest.approx(4.0 / (8 * n_timed))
    assert told == {"n_timed": n_timed,
                    "warmup_s": pytest.approx(readings[1] / n_warmup)}
    assert next(clock, None) is None    # four readings, no fence in between


@pytest.mark.parametrize("case", list(FLOORS))
def test_the_timing_span_says_how_many_windows_were_timed(case, run,
                                                          monkeypatch):
    from saturn_tpu.utils import timing

    floor, n_timed = FLOORS[case]
    monkeypatch.setattr(timing, "_ONE_WINDOW_FLOOR_S", floor)
    tech, (config, t), _, events = run(
        [{"id": "only", "remat": True, "step_s": WARMUP_S}])
    assert config == {"id": "only", "remat": True}
    assert tech.book.order("step") == ["only"] * (1 + n_timed)
    assert WARMUP_S / 8 <= t
    (timed,) = of_kind(events, "trial.timing")
    assert (timed["k"], timed["n_timed"]) == (8, n_timed)
    assert WARMUP_S <= timed["warmup_s"] < timed["dur_s"]


def test_clocks_side_by_side_put_the_settings_back_once():
    from saturn_tpu.utils import timing

    before = clock_settings()
    first_in, second_in, first_out = (threading.Event() for _ in range(3))
    seen = {}

    def second():
        first_in.wait(LIMIT_S)
        with timing.undisturbed_clock():
            second_in.set()
            first_out.wait(LIMIT_S)
            # the first clock has stopped: this one still runs undisturbed
            seen["inside"] = clock_settings()

    t = threading.Thread(target=second)
    t.start()
    with timing.undisturbed_clock():
        first_in.set()
        assert second_in.wait(LIMIT_S)
    first_out.set()
    t.join(LIMIT_S)
    assert not t.is_alive()
    assert seen["inside"] == ((*before[0][:2], timing._NO_FULL_COLLECTION),
                              timing._SWITCH_INTERVAL_S)
    assert clock_settings() == before


# ------------- the fused head's rungs: stash first, recompute if refused (PR 51)
MB = 1 << 20


class Laddered(Stubbed):
    """A grid of points whose fused head would stash ``head`` bytes (a point
    that says none has a head nobody is asked about). A point is found by its
    ``attention`` and ``remat``, as ``Twinned``'s; built with ``ce_mode:
    "stash"`` it is another program, which ends as the point's ``stash``
    entry says and is booked as ``<id>+stash``."""

    def candidate_configs(self, task, n_devices):
        return [{k: p[k] for k in ("attention", "remat")} for p in self.points]

    def _point(self, config):
        (point,) = [p for p in self.points if (p["attention"], p["remat"])
                    == (config["attention"], config["remat"])]
        return point

    def _head_rungs(self, task, devices, config):
        point = self._point(config)
        if config.get("ce_mode") or "head" not in point:
            return None
        return {"stash_bytes": point["head"]}

    def _room_after_state(self, task, devices):
        self.book.log("room", self.points[0])   # the task's: once a search
        return self.points[0].get("room", HBM // 2)

    def build(self, task, devices, config, use_cache=True):
        point = self._point(config)
        if config.get("ce_mode") == "stash":
            point = {"mode": "stash", **point, **point["stash"],
                     "id": point["id"] + "+stash"}
        return self._bundle_of(point)


def laddered(point_over, **more):
    return {"id": "a", "attention": "flash", "remat": True, "head": 300 * MB,
            "step_s": 0.03, **point_over, **more}


def only(events, kind):
    (e,) = of_kind(events, kind)
    return e


def test_a_stash_rung_that_fits_is_the_points_one_program(run):
    tech, (config, t), report, events = run(
        [laddered({"stash": {"step_s": 0.01}})], technique=Laddered)
    # one build, one compile, one timed program: the stashing one
    assert tech.book.order("build") == tech.book.order("compile") == ["a+stash"]
    assert set(tech.book.order("step")) == {"a+stash"}
    # ... and the config the search returns says so, like a pinned attention
    assert config == {"attention": "flash", "remat": True, "ce_mode": "stash"}
    assert 0.01 / 8 <= t < 0.03 / 8
    note, span = only(events, "trial_config"), only(events, "trial.config")
    assert note["config"] == span["config"] == config
    assert note["ce_ladder"] == span["ce_ladder"] == {
        "stash_bytes": 300 * MB, "room_bytes": HBM // 2,
        "tried": ["stash"], "kept": "stash"}
    assert span["outcome"] == "timed" and note["per_batch_s"] == t
    assert report["configs"] == 1 and report["memory_rejected"] == 0
    assert report["errors"] == 0
    assert no_measuring_thread_left()


#: how the stash rung is lost -> what ``ce_ladder.refused`` says of it
LOST = {
    "over": ({"need": HBM}, {"outcome": "memory_rejected", "need_bytes": HBM,
                             "limit_bytes": HBM}),
    "refused": ({"compile": "refuse"}, {
        "outcome": "refused", "refusal": "recorded",
        "compiler": "RESOURCE_EXHAUSTED: the program needs 17.1G of 15.7G hbm"}),
    "inert": ({"inert": True}, {"outcome": "inert"}),
}


@pytest.mark.parametrize("case", sorted(LOST))
def test_a_stash_rung_that_is_refused_is_no_refused_point(case, run):
    """The rung over the rule, refused by the compiler, or traced to no
    stashing head: the point is built again as the grid has it and *that*
    program is timed. One grid point, one ``trial_config`` event that counts
    no refusal (the benchmark's ``search_refused_per_job`` reads
    ``memory_rejected`` / ``error`` keys), one timed program."""
    stash, said = LOST[case]
    tech, (config, _), report, events = run(
        [laddered({"stash": stash})], technique=Laddered)
    assert tech.book.order("build") == ["a+stash", "a"]
    # a rung that traced to no stashing head is not compiled
    assert tech.book.order("compile") == (
        ["a"] if case == "inert" else ["a+stash", "a"])
    assert set(tech.book.order("step")) == {"a"}
    assert tech.book.order("init_compile") == ["a"]
    assert config == {"attention": "flash", "remat": True}
    note, span = only(events, "trial_config"), only(events, "trial.config")
    assert note["config"] == span["config"] == config
    assert note["ce_ladder"] == span["ce_ladder"] == {
        "stash_bytes": 300 * MB, "room_bytes": HBM // 2,
        "tried": ["stash", "recompute"], "kept": "recompute", "refused": said}
    assert span["outcome"] == "timed" and "per_batch_s" in note
    assert not {"memory_rejected", "error", "refusal"} & set(note)
    assert report["configs"] == 1 and report["memory_rejected"] == 0
    assert report["refusals_fresh"] == report["refusals_replayed"] == 0
    assert report["errors"] == 0
    # both rungs' host work lies under the point's one span
    assert Counter(e["kind"] for e in events if e.get("parent") == span["id"]
                   and e["kind"] in ("trial.build", "trial.memory_check")) == {
        "trial.build": 2, "trial.memory_check": 1 if case != "over" else 2}
    assert no_measuring_thread_left()


def test_a_refused_rung_on_record_ends_unbuilt(run, recorded):
    """The rung's verdict is a point record like any other (its identity
    holds the rung's config): the next search goes straight to the rung that
    fits. A rung that fits leaves none."""
    grid = [laddered({"stash": {"need": HBM}}),
            laddered({"stash": {"step_s": 0.02}}, id="b", attention="dense")]
    tech, best, report, _ = run(grid, name="first", technique=Laddered)
    assert tech.book.order("build") == ["a+stash", "a", "b+stash"]
    assert len(recorded()) == 1 and report["refusals_unbuilt"] == 0

    tech, (config, _), report, events = run(grid, name="second",
                                            technique=Laddered)
    assert tech.book.order("build") == ["a", "b+stash"]
    notes = {e["config"]["attention"]: e for e in of_kind(events, "trial_config")}
    assert notes["flash"]["ce_ladder"] == {
        "stash_bytes": 300 * MB, "room_bytes": HBM // 2, "skipped": "recorded",
        "tried": ["recompute"], "kept": "recompute"}
    assert "unbuilt" not in notes["flash"] and "per_batch_s" in notes["flash"]
    assert notes["dense"]["ce_ladder"]["kept"] == "stash"
    assert config == {"attention": "dense", "remat": True, "ce_mode": "stash"}
    # the point was built (its other rung): nothing of it counts as unbuilt
    assert report["refusals_unbuilt"] == 0 and report["memory_rejected"] == 0
    identities = of_kind(events, "trial.identity")
    assert [e["hit"] for e in identities] == [True, False, False]
    assert len(recorded()) == 1
    assert no_measuring_thread_left()


def test_a_stale_rung_record_costs_a_rung_never_a_job(run, recorded):
    """Both rungs of the only point on record and nothing timed: the point
    runs again in full, the stash rung included."""
    over = [laddered({"need": HBM, "stash": {"need": HBM}})]
    run(over, name="first", technique=Laddered)
    assert len(recorded()) == 2
    tech, best, report, events = run(over, name="second", technique=Laddered)
    assert best == (None, None) and report["memory_infeasible"] is True
    assert tech.book.order("build") == ["a+stash", "a"]
    first, again = of_kind(events, "trial_config")
    assert first["unbuilt"] is True and first["ce_ladder"]["skipped"] == "recorded"
    assert first["ce_ladder"]["tried"] == [] and first["ce_ladder"]["kept"] is None
    assert "unbuilt" not in again and again["ce_ladder"]["tried"] == [
        "stash", "recompute"]
    assert again["memory_rejected"] is True and again["ce_ladder"]["kept"] is None

    fits = [laddered({"stash": {"step_s": 0.01}})]
    tech, (config, _), report, _ = run(fits, name="third", technique=Laddered)
    # the rung that fits takes its record away; the other rung's stays until
    # somebody asks about that rung again (it is not this point's program)
    assert config["ce_mode"] == "stash" and len(recorded()) == 1
    assert tech.book.order("build") == ["a+stash"]


#: how the ``remat: True`` twin ends on its last rung -> how the point without
#: remat ends: its stash rung is skipped either way (the stash adds the same
#: bytes under either), it is implied over memory only by the twin's last word
TWIN_LADDERS = {
    "twin-recomputes": ({"step_s": 0.03}, "built"),
    "twin-over": ({"need": HBM}, "implied"),
}


@pytest.mark.parametrize("case", sorted(TWIN_LADDERS))
def test_remat_off_skips_the_rung_its_twin_lost(case, run):
    last, want = TWIN_LADDERS[case]
    tech, (config, _), report, events = run([
        laddered({"stash": {"step_s": 0.0}}, id="plain", remat=False,
                 step_s=0.02),
        laddered({"stash": {"need": HBM}, **last}, id="frugal"),
        laddered({"stash": {"step_s": 0.05}}, id="other", attention="dense",
                 step_s=0.05),
    ], technique=Laddered)
    notes = {(e["config"]["attention"], e["config"]["remat"]): e
             for e in of_kind(events, "trial_config")}
    built = tech.book.order("build")
    assert "plain+stash" not in built and built[:2] == ["frugal+stash", "frugal"]
    assert notes["flash", True]["ce_ladder"]["refused"]["outcome"] == "memory_rejected"
    if want == "built":
        assert "plain" in built
        assert notes["flash", False]["ce_ladder"] == {
            "stash_bytes": 300 * MB, "room_bytes": HBM // 2, "skipped": "remat",
            "tried": ["recompute"], "kept": "recompute"}
        assert config == {"attention": "flash", "remat": False}
        assert report["memory_rejected"] == 0
    else:
        assert "plain" not in built
        assert notes["flash", False]["implied_by"] == "remat"
        assert "ce_ladder" not in notes["flash", False]
        assert config == {"attention": "dense", "remat": True, "ce_mode": "stash"}
        assert report["memory_rejected"] == 2
    # another ``attention`` is no twin: its rung was tried, and kept
    assert notes["dense", True]["ce_ladder"]["tried"] == ["stash"]
    assert len(tech.book.order("room")) == 1   # the state is the task's
    assert report["configs"] == 3 and len(notes) == 3
    assert no_measuring_thread_left()


#: a point whose head gets no stash rung -> its ``ce_ladder`` (None: no key)
NO_RUNG = {
    "over-the-static-bound": (
        {"head": 300 * MB, "room": 299 * MB, "stash": {"step_s": 0.0}},
        {"stash_bytes": 300 * MB, "room_bytes": 299 * MB, "skipped": "static",
         "tried": ["recompute"], "kept": "recompute"}),
    "nobody-is-asked": ({"stash": {"step_s": 0.0}}, None),
}


@pytest.mark.parametrize("case", sorted(NO_RUNG))
def test_no_rung_where_the_stash_cannot_fit_or_nobody_is_asked(case, run):
    """A stash larger than what the rule leaves after the train state is not
    worth a compile (a 128k-token job does not pay for a doomed one), and a
    head under the constant, or no fused head, has ``_head_rungs`` None."""
    over, want = NO_RUNG[case]
    point = {"id": "a", "attention": "flash", "remat": True, "step_s": 0.01,
             **over}
    tech, (config, _), report, events = run([point], technique=Laddered)
    assert tech.book.order("build") == tech.book.order("compile") == ["a"]
    assert config == {"attention": "flash", "remat": True}
    note = only(events, "trial_config")
    assert note.get("ce_ladder") == want
    assert only(events, "trial.config").get("ce_ladder") == want


def test_a_stash_rung_that_raises_is_how_the_point_ended(run):
    """Only memory takes a point to its next rung: a stashing kernel that
    fails to lower must not lose to its recomputing twin in silence."""
    tech, best, report, events = run(
        [laddered({"stash": {"build": "raise"}})], technique=Laddered)
    assert best == (None, None) and tech.book.order("build") == ["a+stash"]
    assert report["errors"] == 1 and "failed to lower" in report["first_error"]
    note = only(events, "trial_config")
    assert note["ce_ladder"]["tried"] == ["stash"] and note["ce_ladder"]["kept"] is None
    assert note["config"] == {"attention": "flash", "remat": True}
