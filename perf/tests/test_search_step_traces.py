"""``search_step_traces_per_point`` (PR 34) on recorded event lists: a search
of the change (every grid point's bundle traced its step once), a search as
the parent's program would have counted (three traces a point, two for a
point the compiler refused before the memory check's audit), and a stream of
a program that has no such counter, which reads as absent and not as 0."""

from perf.lib import bench

from .test_span_metrics import FakeRun as _FakeRun


class FakeRun(_FakeRun):
    """A run whose search ran (``harness.timed_search`` leaves its wall and
    its counters in ``run.search``; a run that searched nothing has none)."""

    def __init__(self, search=()):
        super().__init__(search=search)
        self.search = {"wall_s": 36.8}


def _reader():
    return bench.load_reader(bench.load_cell("gptj-6b-1chip.steady"),
                             "search_step_traces_per_point")


def _point(config, **fields):
    return {"kind": "trial_config", "task": "steady-s2048-b4", "size": 1,
            "technique": "dp", "config": config, **fields}


# GPT-J's four grid points as the change's traced run emitted them
CHANGE = [
    _point({"remat": False, "attention": "flash"}, step_traces=1,
           memory_rejected=True),
    _point({"remat": False, "attention": "dense"}, step_traces=1,
           memory_rejected=True, refusal="recorded"),
    _point({"remat": True, "attention": "flash"}, step_traces=1,
           per_batch_s=0.30954),
    _point({"remat": True, "attention": "dense"}, step_traces=1,
           per_batch_s=0.34381),
    {"kind": "memlens_calibration", "task": "steady-s2048-b4", "k": 8},
]
# the same points by the parent's three tracing sites (build, window
# program, audit): the refused point never reached the audit
PARENT_STYLE = [dict(e, step_traces=2 if e.get("refusal") else 3)
                if e["kind"] == "trial_config" else e for e in CHANGE]
# what the parent's program really emits: no counter
PARENT = [{k: v for k, v in e.items() if k != "step_traces"} for e in CHANGE]


def test_change_reads_one_trace_a_point():
    assert _reader()(FakeRun(search=CHANGE)) == 1.0


def test_parent_style_counts_read_three_and_two():
    assert _reader()(FakeRun(search=PARENT_STYLE)) == (3 + 2 + 3 + 3) / 4
    three = [dict(e, step_traces=3) for e in CHANGE if e["kind"] == "trial_config"]
    assert _reader()(FakeRun(search=three)) == 3.0


def test_no_counter_reads_as_absent_not_zero():
    assert _reader()(FakeRun(search=PARENT)) is None
    assert _reader()(FakeRun(search=[])) is None
    assert _reader()(_FakeRun(search=CHANGE)) is None   # no search in this run
    # a point that was infeasible before its bundle was built carries no
    # count and is not averaged in as 0
    mixed = CHANGE + [_point({"remat": True}, infeasible="batch not divisible")]
    assert _reader()(FakeRun(search=mixed)) == 1.0


def test_entry_is_the_last_of_per_layer_and_holds_in_every_cell():
    import json
    import os

    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert b["per_layer"][-1] == {
        "name": "search_step_traces_per_point", "unit": "traces/point",
        "better": "lower", "source": "program_counter",
        "layer": "trial runner", "moves": "search_s_per_job"}
    for w in b["workloads"]:
        names = {m["name"] for m in bench.load_cell(w["name"]).per_layer}
        assert "search_step_traces_per_point" in names, w["name"]
    # the guard of ``test_reference_olmo_hybrid.py::test_benchmark_json_
    # appends_the_cell_and_edits_nothing``, which looks for PR 33's entries
    # at the very end (a file the benchmark has is not this PR's to edit):
    # the same checks, PR 33's fourteen found just before this PR's one
    from .test_reference_olmo_hybrid import CELL, NEW_ENTRIES

    before = b["per_layer"][-1 - len(NEW_ENTRIES):-1]
    assert tuple(m["name"] for m in before) == NEW_ENTRIES
    for m in before:
        assert m["workloads"] == [CELL] and m["moves"] == "search_s_per_job"
    assert b["configs"][-1]["name"] == "olmo-hybrid-7b-1chip"
    assert b["workloads"][-1]["name"] == CELL and len(b["workloads"]) == 6
    assert [m["name"] for m in b["end_to_end"]] == [
        "train_tokens_per_s", "search_s_per_job", "setup_s"]
