"""``search_overlap_share`` (PR 37) on recorded lists of spans: a search that
walks its grid on one thread (the parent's: 0), one that builds and compiles
the next points while a measuring thread times a point (the share of the
timing that hid host work), and a stream with no spans (None: the metric is
left out of the line)."""

import json

import pytest

from perf.lib import bench

from .test_span_metrics import FakeRun, sp


def _reader():
    return bench.load_reader(bench.load_cell("gptj-6b-1chip.steady"),
                             "search_overlap_share")


def _point(i, thread, build, compile_, timing=None, outcome="timed",
           meas=None):
    """A grid point's spans: ``trial.config`` i, its build and compile on
    ``thread``, its timing on ``meas`` (the same thread where None); the
    ``trial.config`` names the thread the point ended on."""
    meas = meas or thread
    end = (timing or compile_)[1]
    out = [sp("trial.config", i, 2, build[0], end,
              thread=meas if timing else thread, outcome=outcome),
           sp("trial.build", i + 1, i, *build, thread=thread),
           sp("trial.compile", i + 2, i, *compile_, thread=thread, k=8)]
    if timing:
        out.append(sp("trial.timing", i + 3, i, *timing, thread=meas, k=8))
    return out


HEAD = [sp("search", 1, None, 0, 60), sp("trial", 2, 1, 0, 60)]
# one thread, the technique's order: two refused points, then two timed
SERIAL = (HEAD
          + _point(10, "MainThread", (0, 3), (3, 4), outcome="refused")
          + _point(20, "MainThread", (4, 7), (7, 8), outcome="memory_rejected")
          + _point(30, "MainThread", (8, 11), (11, 12), timing=(14, 24))
          + _point(40, "MainThread", (24, 27), (27, 28), timing=(30, 40)))
# the same work with a measuring thread and the timed points first: the
# first timing (6-16) hides the builds and compiles of 4-16, the second
# (18-28) nothing
PIPED = (HEAD
         + _point(30, "MainThread", (0, 3), (3, 4), timing=(6, 16),
                  meas="meas-MainThread")
         + _point(40, "MainThread", (4, 7), (7, 8), timing=(18, 28),
                  meas="meas-MainThread")
         + _point(10, "MainThread", (8, 11), (11, 12), outcome="refused")
         + _point(20, "MainThread", (12, 15), (15, 16),
                  outcome="memory_rejected"))


def test_a_serial_search_reads_zero():
    assert _reader()(FakeRun(search=SERIAL)) == 0.0


def test_an_overlapped_search_reads_the_hidden_share():
    # [6, 8] of the second point's preparation and [8, 16] of the third's and
    # the fourth's lie under the first timing: 10 of 20 timed seconds
    assert _reader()(FakeRun(search=PIPED)) == pytest.approx(50.0)


def test_only_another_threads_work_counts():
    same = [dict(e, thread="MainThread") for e in PIPED]
    assert _reader()(FakeRun(search=same)) == 0.0


def test_trial_threads_side_by_side_hide_each_others_work():
    two = (HEAD
           + _point(10, "trial-g1_0", (0, 4), (4, 6), timing=(6, 16))
           + _point(20, "trial-g1_1", (5, 9), (9, 11), timing=(11, 21)))
    # thread 1's build and compile of 6-11 under thread 0's timing; nothing
    # of thread 0's under thread 1's
    assert _reader()(FakeRun(search=two)) == pytest.approx(100 * 5 / 15)


def test_another_calls_spans_do_not_count():
    other = [dict(e, root=99, thread="meas-x") for e in PIPED[2:]]
    assert _reader()(FakeRun(search=SERIAL + other)) == 0.0


def test_nothing_where_there_are_no_spans_or_no_timed_point():
    assert _reader()(FakeRun()) is None
    old = [{"kind": "trial_config", "task": "a", "per_batch_s": 0.3}]
    assert _reader()(FakeRun(search=old)) is None
    refused = HEAD + _point(10, "MainThread", (0, 3), (3, 4), outcome="refused")
    assert _reader()(FakeRun(search=refused)) is None


def test_entry_holds_in_every_cell():
    with open(bench.REPO + "/BENCHMARK.json") as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "search_overlap_share"]
    assert entry == {"name": "search_overlap_share", "unit": "%",
                     "better": "higher", "source": "program_span",
                     "layer": "trial runner", "moves": "search_s_per_job"}
    for w in spec["workloads"]:
        cell = bench.load_cell(w["name"])
        assert "search_overlap_share" in [m["name"] for m in cell.per_layer]
