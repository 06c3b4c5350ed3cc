"""The eight span metrics on hand-written event lists with known answers
(two gangs whose steps overlap each other's launches and a re-solve, writer
threads, refused grid points on two trial threads), ``idle_unattributed`` on
the trace recorded on the chip (``data/small_trace.xplane.pb``), and every
reader on a run of a commit without spans: None, the metric left out."""

import os

import pytest

from perf.lib import bench, spans, trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")
NEW = ("search_refused_share", "search_timing_share", "solve_share",
       "launch_share", "ckpt_stall", "ckpt_write_gb_per_s",
       "idle_unattributed", "step_flops_share")


class FakeRun:
    def __init__(self, search=(), window=(), trace=None):
        self._events = {"search": list(search), "window": list(window)}
        self.trace = trace
        self.window = {"trace_dir": None}
        self.search = {}

    def events(self, phase, kind):
        return [e for e in self._events.get(phase, [])
                if kind is None or e.get("kind") == kind]


def sp(kind, i, parent, start, end, thread="MainThread", root=1, **fields):
    return dict(kind=kind, id=i, parent=parent, root=root, ts_start=float(start),
                ts=float(end), dur_s=float(end - start), thread=thread, **fields)


def reader(name):
    return bench.load_reader(bench.load_cell("gpt2-medium.steady"), name)


# two gangs on two blocks inside one 40 s orchestrate call
WINDOW = [
    sp("orchestrate", 1, None, 100, 140, n_tasks=2),
    sp("solver.resolve", 2, 1, 100, 102, source="orchestrator-initial"),
    {"kind": "solve", "ts": 102.1, "plan": {}, "makespan_s": 30.0},
    sp("forecast", 4, 1, 102.2, 102.4),
    sp("interval", 3, 1, 102.5, 136),
    # gang 1: launch 103-105, steps 105-125, then its snapshot and its write
    sp("launch.build", 11, 10, 103, 104, thread="launch-g1", task="g1"),
    sp("launch.compile", 12, 10, 104, 105, thread="launch-g1", k=8),
    # a re-solve on the pool's thread, wholly under gang 1's steps
    sp("solver.resolve", 5, 3, 110, 114, thread="solver_0", source="orchestrator"),
    sp("step_flops", 15, 10, 124, 125, thread="launch-g1", cached=False),
    {"kind": "task_interval", "id": 10, "parent": 3, "root": 1, "task": "g1",
     "ts_launch": 103.0, "ts_start": 105.0, "ts": 125.0, "elapsed_s": 20.0,
     "batches": 8},
    sp("ckpt.wait_pending", 16, 10, 125, 125, thread="launch-g1"),
    sp("ckpt.snapshot", 13, 10, 125, 128, thread="launch-g1", bytes=3e9),
    sp("ckpt.write", 14, 13, 128, 134, thread="ckpt-g1.npz", bytes=3e9, n_shards=9),
    # gang 2: launch 103-108 (the last 3 s under gang 1's steps), steps 108-130
    sp("launch.build", 21, 20, 103, 106, thread="launch-g2", task="g2"),
    sp("launch.restore", 22, 20, 106, 108, thread="launch-g2", task="g2", bytes=2e9),
    {"kind": "task_interval", "id": 20, "parent": 3, "root": 1, "task": "g2",
     "ts_launch": 103.0, "ts_start": 108.0, "ts": 130.0, "elapsed_s": 22.0,
     "batches": 8},
    sp("ckpt.wait_pending", 23, 20, 130, 130.5, thread="launch-g2"),
    sp("ckpt.snapshot", 24, 20, 130.5, 133, thread="launch-g2", bytes=2e9),
    sp("ckpt.write", 25, 24, 133, 138, thread="ckpt-g2.npz", bytes=2e9, n_shards=9),
    sp("ckpt.flush", 30, 1, 136, 138, n_pending=1),
    {"kind": "compile", "ts": 125.5, "seconds": 0.2, "program": "jit(concatenate)",
     "cached": False, "in_span": {"name": "readback", "id": 99}},
    # another call's spans in the same file do not count
    sp("ckpt.flush", 41, 40, 150, 170, root=40),
]

SEARCH = [
    sp("search", 1, None, 0, 50, n_tasks=2),
    sp("trial", 2, 1, 0, 30, thread="trial-g1_0"),
    sp("trial.config", 3, 2, 0, 10, thread="trial-g1_0", outcome="refused"),
    sp("trial.config", 4, 2, 10, 30, thread="trial-g1_0", outcome="timed"),
    sp("trial.timing", 5, 4, 25, 29, thread="trial-g1_0", k=8),
    sp("trial", 6, 1, 0, 25, thread="trial-g1_1"),
    sp("trial.config", 7, 6, 0, 5, thread="trial-g1_1", outcome="memory_rejected"),
    sp("trial.config", 8, 6, 5, 25, thread="trial-g1_1", outcome="timed"),
    sp("trial.timing", 9, 8, 20, 24, thread="trial-g1_1", k=8),
    {"kind": "trial_config", "ts": 10.0, "task": "a", "error": "RESOURCE_EXHAUSTED"},
]

KNOWN = {
    "search_refused_share": 100 * (10 + 5) / 50,      # both threads' seconds
    "search_timing_share": 100 * (4 + 4) / 50,
    "solve_share": 100 * 2 / 40,                      # the re-solve is hidden
    "launch_share": 100 * 2 / 40,                     # [103, 105] of both gangs
    "ckpt_stall": 100 * (3 + 0.5 + 2.5 + 2) / 40,     # 125-128, 130-133, 136-138
    "ckpt_write_gb_per_s": (3e9 + 2e9) / (6 + 5) / 1e9,
    "step_flops_share": 100 * 1 / 40,
}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_known_answer(name, capsys):
    run = FakeRun(search=SEARCH, window=WINDOW)
    assert reader(name)(run) == pytest.approx(KNOWN[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_none_without_span_events(name):
    """The parent commit: the old events only."""
    old_window = [{k: v for k, v in e.items() if k not in ("id", "parent", "root")}
                  for e in WINDOW if "dur_s" not in e]
    trace = {"wall_offset_s": 0.0, "devices": {"/device:TPU:0": {"gaps": [(0, 5e9)]}}}
    run = FakeRun(search=[SEARCH[-1]], window=old_window, trace=trace)
    assert reader(name)(run) is None
    assert reader(name)(FakeRun()) is None


def test_refused_share_is_capped_at_the_wall():
    four = [sp("search", 1, None, 0, 10)] + [
        sp("trial.config", 2 + i, 1, 0, 9, thread=f"trial-{i}", outcome="refused")
        for i in range(4)]
    assert reader("search_refused_share")(FakeRun(search=four)) == 100.0


def test_self_time_is_duration_minus_what_children_cover():
    own = spans.self_seconds(WINDOW)
    assert own[10] == pytest.approx(22 - (1 + 1 + 1))   # task_interval g1: 103-125
    assert own[13] == pytest.approx(3.0)    # its write starts when it has ended
    assert own[3] == pytest.approx(33.5 - 27 - 0)   # interval minus 103-130 of its gangs
    assert own[1] == pytest.approx(40 - (2 + 0.2 + 33.5 + 2))  # flush 136-138 past the interval
    table = spans.self_time_table(WINDOW)
    by = {r["kind"]: r for r in table}
    assert by["ckpt.write"]["n"] == 2 and by["ckpt.write"]["threads"] == 2
    assert by["ckpt.write"]["self_s"] == pytest.approx(11.0)
    assert [r["self_s"] for r in table] == sorted(
        (r["self_s"] for r in table), reverse=True)


def test_table_prints_kinds_and_compiles(capsys):
    spans.print_table("window", WINDOW)
    said = capsys.readouterr().out
    assert "ckpt.snapshot" in said and "jit(concatenate)" in said
    assert "in readback" in said


def test_interval_arithmetic():
    assert spans.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert spans.length([(0, 2), (1, 3), (5, 6)]) == 4
    assert spans.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert spans.subtract([(0, 1), (4, 5)], [(0, 5)]) == []
    assert spans.clip([(0, 4), (6, 9)], 3, 7) == [(3, 4), (6, 7)]
    assert [e["kind"] for e in spans.spans(WINDOW, "launch.*")] == [
        "launch.build", "launch.compile", "launch.build", "launch.restore"]


def test_idle_unattributed_on_the_recorded_trace(capsys):
    """Three steps with 30 ms of host sleep after each, recorded on the chip:
    a span over the first sleep leaves the other idle time unattributed."""
    trace = trace_reduce.reduce_trace(TRACE)
    wall_t0 = 5000.0
    trace["wall_offset_s"] = wall_t0 - trace["window_ns"][0] / 1e9
    off = trace["wall_offset_s"]
    gaps = sorted(trace["devices"]["/device:TPU:0"]["gaps"],
                  key=lambda g: g[0] - g[1])
    idle = sum(e - s for s, e in gaps) / 1e9
    first = gaps[0]
    window = [
        sp("orchestrate", 1, None, wall_t0, wall_t0 + trace["window_s"]),
        sp("interval", 2, 1, wall_t0, wall_t0 + trace["window_s"]),
        sp("ckpt.flush", 3, 1, first[0] / 1e9 + off, first[1] / 1e9 + off),
    ]
    run = FakeRun(window=window, trace=trace)
    got = reader("idle_unattributed")(run)
    assert got == pytest.approx(100 * (idle - (first[1] - first[0]) / 1e9) / idle,
                                rel=1e-6)
    assert 60 < got < 70            # one of three equal sleeps is explained
    # spans over everything: nothing is left; the enclosing spans explain nothing
    window.append(sp("readback", 4, 2, wall_t0 - 1, wall_t0 + 1))
    assert reader("idle_unattributed")(FakeRun(window=window, trace=trace)) == 0.0
    assert reader("idle_unattributed")(
        FakeRun(window=window[:2], trace=trace)) == pytest.approx(100.0)
    assert reader("idle_unattributed")(FakeRun(window=window)) is None  # no trace


def test_annotations_and_clock_skew():
    # the recorded trace predates the spans: it holds perf.window alone
    assert spans.annotations(TRACE) == []
    found = [("launch.build", 3.0e9, 4.0e9, "launch-g1"),
             ("launch.build", 3.0e9 + 2e6, 6.0e9, "launch-g2"),
             ("ckpt.flush", 36.0e9 - 1e6, 38.0e9, "main")]
    skew = spans.clock_skew(found, WINDOW, wall_offset_s=100.0)
    # g1's and g2's builds both start at 103.0 in the events: 0 and +2 ms;
    # the flush of the other call makes the counts differ, so it is not paired
    assert skew["n_paired"] == 2 and skew["kind"] == "launch.build"
    assert skew["skew_s"] == pytest.approx(0.002)
    assert spans.clock_skew([], WINDOW, 0.0) is None


def test_annotations_in_a_trace_count_as_cover(tmp_path, capsys):
    """A span that ran with no sink open (the first call's ``import``) is in
    the profiler's trace alone: ``idle_unattributed`` reads it from there."""
    import time

    import jax

    from saturn_tpu.utils import metrics

    d = str(tmp_path / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with metrics.span("import", module="m"):      # no sink: annotation only
        time.sleep(0.02)
    with metrics.span("orchestrate"):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    found = spans.annotations(trace_reduce.find_xplane(d))
    assert [n for n, _, _, _ in found] == ["import", "orchestrate"]
    (_, s, e, _), (_, s2, e2, _) = found
    assert 0.02e9 <= e - s < 0.5e9
    off = 777.0
    trace = {"wall_offset_s": off,
             "devices": {"/device:TPU:0": {"gaps": [(s, e), (s2, e2)]}}}
    window = [sp("orchestrate", 1, None, s2 / 1e9 + off, e2 / 1e9 + off)]
    run = FakeRun(window=window, trace=trace)
    run.window = {"trace_dir": d}
    got = reader("idle_unattributed")(run)
    # the import's gap is explained by its annotation; the gap under the
    # enclosing orchestrate span alone is not
    assert got == pytest.approx(100 * (e2 - s2) / ((e - s) + (e2 - s2)), rel=1e-6)
    assert "saturn.import in the window" in capsys.readouterr().out


def test_benchmark_json_holds_the_eight_entries():
    """Found by name (a list of ``BENCHMARK.json`` grows at its end, so the
    eight are not its last), and with no ``workloads`` list since PR 32:
    every cell runs these layers, so every cell that reports the end-to-end
    metric they move reports them (``train_tokens_per_s`` leaves the four-chip
    cell out: its window spreads by more than a bound may cover)."""
    import json

    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in NEW}
    ends = {m["name"] for m in b["end_to_end"]}
    for name in NEW:
        m = by_name[name]
        assert m["layer"] in layers and m["moves"] in ends
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert os.path.isfile(os.path.join(bench.PERF_DIR, "metrics", name + ".py"))
    for w in b["workloads"]:
        cell = bench.load_cell(w["name"])
        ends = {m["name"] for m in cell.end_to_end}
        want = {name for name in NEW if by_name[name]["moves"] in ends}
        assert want <= {m["name"] for m in cell.per_layer}
        assert (want == set(NEW)) == (w["chips"] == 1)
