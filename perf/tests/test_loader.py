"""A new configuration, traffic mix and per-layer metric are picked up from
new files and new entries alone: nothing under ``perf/`` that is there is
edited (the harness finds each by its name in BENCHMARK.json)."""

import json
import os

import pytest

from perf.lib import bench, harness
from perf.tests import tinyroot


def test_every_metric_of_the_benchmark_has_a_reader():
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    for w in real["workloads"]:
        cell = bench.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(bench.load_reader(cell, m["name"]))
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= {m["name"] for m in cell.end_to_end}


def test_new_files_and_entries_are_enough(tmp_path):
    root = str(tmp_path)
    tinyroot.write(root)
    # a later PR: one more config, traffic mix, metric -- files and entries only
    cfg = dict(tinyroot.TINY_CONFIG, name="tiny-gptj", family="gptj", rotary_dim=8)
    cfg["run"] = dict(cfg["run"], preset="gptj-test-tiny")
    with open(os.path.join(root, "perf", "configs", "tiny-gptj.json"), "w") as f:
        json.dump(cfg, f)
    mix = dict(tinyroot.TINY_TRAFFIC, jobs=[
        {"name": "only", "seq": 64, "batch": 2, "lr": 1e-3, "share": 1.0}])
    with open(os.path.join(root, "perf", "traffic", "tiny-one.json"), "w") as f:
        json.dump(mix, f)
    os.makedirs(os.path.join(root, "perf", "metrics"))
    with open(os.path.join(root, "perf", "metrics", "jobs_in_cell.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.jobs))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-gptj", "source": "test",
                         "file": "perf/configs/tiny-gptj.json", "reduced": [], "why": "t"})
    b["workloads"].append({"name": "tiny-gptj.one", "config": "tiny-gptj",
                           "traffic": "tiny-one", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "jobs_in_cell", "unit": "count", "better": "lower",
                           "source": "program_counter", "layer": "engine",
                           "moves": "train_tokens_per_s",
                           "workloads": ["tiny-gptj.one"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = bench.load_cell("tiny-gptj.one", root)
    assert cell.config["family"] == "gptj" and cell.traffic_name == "tiny-one"
    assert "jobs_in_cell" in [m["name"] for m in cell.per_layer]
    other = bench.load_cell("tiny.sweep", root)
    assert "jobs_in_cell" not in [m["name"] for m in other.per_layer]
    run = harness.Run(cell, seed=1, seconds=2.0, trace=True, t_process_start=0.0)
    assert bench.load_reader(cell, "jobs_in_cell")(run) == 1.0
    # the real readers are still found (beside the harness)
    assert bench.load_reader(cell, "mfu")(run) is None  # nothing measured yet
    assert [j.batch_count for j in run.jobs] == [20]


def test_a_configuration_names_its_reference_module(tmp_path, monkeypatch):
    """A new family brings ``perf/reference/<family>.py`` and names it in
    ``run.reference``: nothing that is there is edited."""
    (tmp_path / "other_family_ref.py").write_text(
        "def arch_from_config(cfg, seq_len):\n    return ('other', cfg['n_embd'], seq_len)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    root = str(tmp_path / "root")
    os.makedirs(root)
    name = tinyroot.write(root)
    cell = bench.load_cell(name, root)
    run = harness.Run(cell, seed=1, seconds=2.0, trace=False, t_process_start=0.0)
    assert run.arch(run.jobs[0]).family == "gpt2"  # perf.reference.gpt
    cell.config["run"]["reference"] = "other_family_ref"
    assert run.arch(run.jobs[0]) == ("other", 64, 64)


def test_missing_reader_and_unknown_cell_are_errors(tmp_path):
    root = str(tmp_path)
    name = tinyroot.write(root)
    cell = bench.load_cell(name, root)
    with pytest.raises(bench.BenchmarkError):
        bench.load_reader(cell, "no_such_metric")
    with pytest.raises(bench.BenchmarkError):
        bench.load_cell("no.such.cell", root)


def test_window_work_is_fixed_by_the_traffic_file():
    jobs = harness.plan_jobs({"steps_per_window_second": 2.4, "round_steps_to": 8,
                              "jobs": [{"name": "j", "seq": 8, "batch": 1,
                                        "lr": 1.0, "share": 1.0}]}, 30.0)
    assert jobs[0].batch_count == 72  # 72 of 2.4 x 30, a whole number of K=8 windows
