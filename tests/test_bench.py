"""Tests of bench.py's chip-or-fail contract and of benchmarks/bench_guard.py.

Both live outside the package; bench_guard is loaded via importlib.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def test_bench_refuses_to_run_without_a_chip(tmp_path):
    """bench.py is chip-or-fail: no CPU workload under the metric's name."""
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert run.returncode != 0
    assert "needs a TPU" in run.stderr
    assert run.stdout.strip() == ""


class TestBenchGuard:
    @pytest.fixture()
    def guard(self):
        spec = importlib.util.spec_from_file_location(
            "bench_guard_under_test",
            os.path.join(REPO, "benchmarks", "bench_guard.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _write_record(self, root, n, parsed):
        with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"n": n, "rc": 0, "parsed": parsed}, f)

    def test_latest_record_picks_highest_round(self, guard, tmp_path, monkeypatch):
        monkeypatch.setattr(guard, "REPO", str(tmp_path))
        self._write_record(tmp_path, 3, {"value": 30.0, "platform": "cpu"})
        self._write_record(tmp_path, 5, {"value": 48.2, "platform": "cpu"})
        n, parsed = guard.latest_record()
        assert n == 5 and parsed["value"] == 48.2

    def test_latest_record_skips_unparsed(self, guard, tmp_path, monkeypatch):
        monkeypatch.setattr(guard, "REPO", str(tmp_path))
        self._write_record(tmp_path, 3, {"value": 30.0, "platform": "cpu"})
        with open(tmp_path / "BENCH_r07.json", "w") as f:
            json.dump({"n": 7, "rc": 124, "parsed": None}, f)
        n, _ = guard.latest_record()
        assert n == 3

    def test_regression_and_ok_verdicts(self, guard, tmp_path, monkeypatch, capsys):
        monkeypatch.setattr(guard, "REPO", str(tmp_path))
        shape = {"platform": "cpu", "batch_size": 2, "seq_len": 256}
        self._write_record(tmp_path, 5, {"value": 50.0, **shape})

        monkeypatch.setattr(guard, "run_bench", lambda: {"value": 44.0, **shape})
        assert guard.main() == 1  # 12% down: regression
        assert json.loads(capsys.readouterr().out)["status"] == "regression"

        monkeypatch.setattr(guard, "run_bench", lambda: {"value": 46.0, **shape})
        assert guard.main() == 0  # 8% down: within the 10% band
        assert json.loads(capsys.readouterr().out)["status"] == "ok"

    def test_shape_mismatch_skips(self, guard, tmp_path, monkeypatch, capsys):
        monkeypatch.setattr(guard, "REPO", str(tmp_path))
        self._write_record(
            tmp_path, 5,
            {"value": 50.0, "platform": "cpu", "batch_size": 2, "seq_len": 256},
        )
        monkeypatch.setattr(
            guard, "run_bench", lambda: {"value": 9000.0, "platform": "tpu"}
        )
        assert guard.main() == 0
        assert json.loads(capsys.readouterr().out)["status"] == "skipped"


class TestPipelineScheduleRow:
    """Round 20: the pipeline-schedule bench row contract."""

    GOOD = {
        "metric": "pipeline_schedule",
        "stages": 4,
        "microbatches": 4,
        "devices": 8,
        "gpipe_ms": 158.1,
        "f1b_ms": 75.4,
        "speedup_1f1b_vs_gpipe": 2.0981,
        "bubble_gpipe": 3 / 7,
        "bubble_1f1b": 3 / 10,
        "status": "ok",
    }

    @pytest.fixture()
    def guard(self):
        spec = importlib.util.spec_from_file_location(
            "bench_guard_pp_row",
            os.path.join(REPO, "benchmarks", "bench_guard.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_good_row_passes(self, guard):
        assert guard.validate_pipeline_row(dict(self.GOOD)) == []

    def test_missing_key_and_non_dict(self, guard):
        row = dict(self.GOOD)
        del row["f1b_ms"]
        assert any("f1b_ms" in p for p in guard.validate_pipeline_row(row))
        assert guard.validate_pipeline_row([1]) != []

    def test_bool_in_count_field_flagged(self, guard):
        row = dict(self.GOOD, stages=True)
        assert any("is bool" in p for p in guard.validate_pipeline_row(row))

    def test_speedup_below_one_fails_the_bar(self, guard):
        row = dict(self.GOOD, speedup_1f1b_vs_gpipe=0.97)
        assert any("beat GPipe" in p for p in
                   guard.validate_pipeline_row(row))

    def test_bubble_ordering_enforced(self, guard):
        row = dict(self.GOOD, bubble_1f1b=0.5)  # >= bubble_gpipe 0.4286
        assert any("smaller one" in p for p in
                   guard.validate_pipeline_row(row))
        row = dict(self.GOOD, bubble_gpipe=1.4)
        assert any("outside" in p for p in guard.validate_pipeline_row(row))
