"""The six readers of PR 39 on hand-made lists of spans with known answers: a
search that walks its grid on one thread (both busy 0), one whose host work
is wholly hidden behind the timing, a multi-block search with two trial
threads, one that timed no point (the partition still adds up to 100), a
stream without spans (None: the metric is left out of the line); and on the
events of a search recorded on the chip, where the program's own wait spans
cross-check the partition."""

import json
import os

import pytest

from perf.lib import bench, critical_path, spans

from .test_span_metrics import FakeRun, sp

SHARES = ("search_wait_for_host_share", "search_both_busy_share",
          "search_wait_for_chip_share", "search_own_share")
NEW = SHARES + ("search_trace_s_per_point", "search_gc_share")
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "search_events_pr39.jsonl")


def read(name, events):
    reader = bench.load_reader(bench.load_cell("gpt2-medium.steady"), name)
    return reader(FakeRun(search=events))


def point(i, trial, thread, build, compile_, timing=None, outcome="timed",
          meas=None, **build_fields):
    """A grid point under ``trial``: ``trial.config`` i, its build and its
    compile on ``thread``, its init and its timing on ``meas``."""
    meas = meas or thread
    end = (timing or compile_)[1]
    out = [sp("trial.config", i, trial, build[0], end,
              thread=meas if timing else thread, outcome=outcome),
           sp("trial.build", i + 1, i, *build, thread=thread, **build_fields),
           sp("trial.compile", i + 2, i, *compile_, thread=thread, k=8,
              program="window", lower_s=0.5)]
    if timing:
        out += [sp("trial.init", i + 3, i, timing[0] - 1, timing[0], thread=meas),
                sp("trial.timing", i + 4, i, *timing, thread=meas, k=8)]
    out.append({"kind": "trial_config", "ts": float(end), "task": "a",
                "outcome": outcome})
    return out


def head(wall, **fields):
    return [sp("search", 1, None, 0, wall, **fields),
            sp("search.fingerprint", 3, 1, 0, 1, trace_s=0.4),
            sp("trial", 2, 1, 1, wall)]


# one thread: two refused points, then two timed ones; the search's own is
# its first second (fingerprints) and its last
SERIAL = (head(40, gc_s=2.0, gc_n=900, gc_full=3, gc_full_max_s=0.25)
          + point(10, 2, "MainThread", (1, 4), (4, 5), outcome="refused",
                  trace_s=2.0)
          + point(20, 2, "MainThread", (5, 8), (8, 9), outcome="memory_rejected",
                  trace_s=2.0)
          + point(30, 2, "MainThread", (9, 12), (12, 13), timing=(14, 24),
                  trace_s=2.5)
          + point(40, 2, "MainThread", (24, 27), (27, 28), timing=(29, 39),
                  trace_s=2.5))
# the same four points, timed ones first, a measuring thread behind: the chip
# waits for the first preparation (1-5) and, idle between the timings, for
# nothing; the preparations of 5-17 lie under the first point's chip side
HIDDEN = (head(30)
          + point(30, 2, "MainThread", (1, 4), (4, 5), timing=(6, 17),
                  meas="meas-MainThread", trace_s=2.5)
          + point(40, 2, "MainThread", (5, 8), (8, 9), timing=(18, 29),
                  meas="meas-MainThread", trace_s=2.5)
          + point(10, 2, "MainThread", (9, 12), (12, 13), outcome="refused",
                  trace_s=2.0)
          + point(20, 2, "MainThread", (13, 16), (16, 17),
                  outcome="memory_rejected", trace_s=2.0))
# two blocks, a trial thread each with its own measuring thread
BLOCKS = ([sp("search", 1, None, 0, 30),
           sp("trial", 2, 1, 0, 30, thread="trial-g1_0"),
           sp("trial", 4, 1, 0, 30, thread="trial-g1_1")]
          + point(10, 2, "trial-g1_0", (0, 4), (4, 6), timing=(7, 17),
                  meas="meas-trial-g1_0", trace_s=3.0)
          + point(20, 4, "trial-g1_1", (2, 8), (8, 10), timing=(11, 29),
                  meas="meas-trial-g1_1", trace_s=5.0))
REFUSED = (head(12)
           + point(10, 2, "MainThread", (1, 4), (4, 5), outcome="refused")
           + point(20, 2, "MainThread", (6, 9), (9, 10), outcome="infeasible"))

KNOWN = {
    # H = [1, 13] + [24, 28], C = [13, 24] + [28, 39], nothing at once
    "serial": (SERIAL, {"wait_for_host": 16, "both_busy": 0,
                        "wait_for_chip": 22, "own": 2}, 40),
    # H = [1, 17], C = [5, 17] + [17, 29]
    "hidden": (HIDDEN, {"wait_for_host": 4, "both_busy": 12,
                        "wait_for_chip": 12, "own": 2}, 30),
    # H = [0, 10], C = [6, 29]: any thread's work counts
    "blocks": (BLOCKS, {"wait_for_host": 6, "both_busy": 4,
                        "wait_for_chip": 19, "own": 1}, 30),
    # no chip-side span at all
    "refused": (REFUSED, {"wait_for_host": 8, "both_busy": 0,
                          "wait_for_chip": 0, "own": 4}, 12),
}


@pytest.mark.parametrize("case", sorted(KNOWN))
def test_the_partition_has_the_known_answer_and_adds_up(case, capsys):
    events, want, wall = KNOWN[case]
    got = {name: read(name, events) for name in SHARES}
    for name in SHARES:
        key = name[len("search_"):-len("_share")]
        assert got[name] == pytest.approx(100.0 * want[key] / wall), name
    assert sum(got.values()) == pytest.approx(100.0, abs=1e-9)


def test_a_serial_search_is_never_busy_on_both_sides():
    assert read("search_both_busy_share", SERIAL) == 0.0
    assert read("search_both_busy_share", REFUSED) == 0.0


def test_fully_hidden_host_work_leaves_only_the_first_preparation():
    p = critical_path.partition(FakeRun(search=HIDDEN))
    # what the chip waited for is the first point's preparation, to the second
    assert p["seconds"]["wait_for_host"] == pytest.approx(4.0)
    assert p["seconds"]["both_busy"] == pytest.approx(12.0)


def test_the_own_share_names_its_stretches(capsys):
    read("search_own_share", SERIAL)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("perf: the search's own")]
    assert line == ["perf: the search's own 2.000s: search.fingerprint 1.000, "
                    "no span 1.000"]
    p = critical_path.partition(FakeRun(search=SERIAL))
    assert critical_path.own_by_name(p) == [("search.fingerprint", 1.0),
                                            (None, pytest.approx(1.0))]
    # a named stretch under which a trial thread works is not the search's own
    over = BLOCKS + [sp("search.fill", 90, 1, 28, 30)]
    rows = dict(critical_path.own_by_name(
        critical_path.partition(FakeRun(search=over))))
    assert rows == {"search.fill": pytest.approx(1.0), None: pytest.approx(0.0)}


def test_trace_seconds_a_point_are_summed_over_every_span():
    # 0.4 (fingerprints) + 2 + 2 + 2.5 + 2.5 over four grid points
    assert read("search_trace_s_per_point", SERIAL) == pytest.approx(9.4 / 4)
    assert read("search_trace_s_per_point", BLOCKS) == pytest.approx(8.0 / 2)
    # the parent stamps no such field: left out
    bare = [{k: v for k, v in e.items() if k != "trace_s"} for e in SERIAL]
    assert read("search_trace_s_per_point", bare) is None


def test_the_first_readers_line_says_where_jax_spent_the_hosts_seconds(capsys):
    moved = [dict(e, thread="trial-g1_0") if e["kind"] == "trial.build"
             and e["id"] == 11 else e for e in SERIAL]
    read("search_wait_for_host_share", moved)
    out = capsys.readouterr().out
    assert "host seconds on the main thread: trace_s 7.400, lower_s 2.000, " \
           "compile_s 0.000, cache_read_s 0.000" in out
    assert "host seconds on other threads: trace_s 2.000, lower_s 0.000" in out
    # the two points that took no timed step: a build and a compile each
    assert "took no timed step: 8.000s" in out
    by = critical_path.host_seconds_by_thread(moved)
    assert by["main"]["trace_s"] + by["others"]["trace_s"] == pytest.approx(9.4)


def test_the_collectors_share_is_the_search_spans(capsys):
    assert read("search_gc_share", SERIAL) == pytest.approx(100 * 2.0 / 40)
    assert "3 full, the longest 0.250s" in capsys.readouterr().out
    assert read("search_gc_share", HIDDEN) is None   # no pass was counted


@pytest.mark.parametrize("name", NEW)
def test_nothing_where_there_are_no_spans(name):
    assert read(name, []) is None
    old = [{"kind": "trial_config", "task": "a", "per_batch_s": 0.3}]
    assert read(name, old) is None


def test_another_calls_spans_do_not_count():
    other = [dict(e, root=99) for e in HIDDEN[1:]]
    assert read("search_both_busy_share", SERIAL + other) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_entry_is_found_by_name_and_holds_in_every_cell(name):
    with open(bench.REPO + "/BENCHMARK.json") as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    assert entry["layer"] == "trial runner"
    assert entry["moves"] == "search_s_per_job" and "workloads" not in entry
    assert entry["source"] in ("program_span", "program_counter")
    for w in spec["workloads"]:
        cell = bench.load_cell(w["name"])
        assert name in [m["name"] for m in cell.per_layer]
        assert callable(bench.load_reader(cell, name))


# ----------------------------------------------- a search recorded on the chip
@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_recorded_search_adds_up_and_its_waits_lie_where_they_should(recorded):
    """The hybrid's search as the chip ran it (PR 39): the four shares add
    up, and the program's own wait spans agree with the partition, trial by
    trial: a ``trial.wait_prepared`` lies where no chip-side span of its
    trial is open, a ``trial.wait_measured`` where no host-work span is."""
    run = FakeRun(search=recorded)
    p = critical_path.partition(run)
    assert sum(p["seconds"].values()) == pytest.approx(p["wall"], rel=1e-9)
    by_id = {e["id"]: e for e in spans.spans(recorded)}

    def trial_of(e):
        while e is not None and e["kind"] != "trial":
            e = by_id.get(e["parent"])   # the static prior has no trial
        return None if e is None else e["id"]

    waits = spans.spans(recorded, "trial.wait_prepared", "trial.wait_measured")
    assert waits and {e["kind"] for e in waits} >= {"trial.wait_prepared"}
    for w in waits:
        mine = [e for e in spans.spans(
            recorded, *(critical_path.CHIP if w["kind"] == "trial.wait_prepared"
                        else critical_path.HOST))
            if trial_of(e) == w["parent"]]
        lo, hi = spans.extent(w)
        inside = spans.length(spans.clip((spans.extent(e) for e in mine), lo, hi))
        assert inside <= 0.01, (w["kind"], w["id"], inside)
    # a host-bound search: the measuring thread's waits are most of what the
    # chip waited for, the rest is the first preparation
    waited = spans.length(spans.extent(e) for e in
                          spans.spans(recorded, "trial.wait_prepared"))
    assert waited <= p["seconds"]["wait_for_host"] + p["seconds"]["own"]
    assert waited >= 0.5 * p["seconds"]["wait_for_host"]
