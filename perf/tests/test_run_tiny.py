"""``perf/run.py`` end to end at tiny size on the CPU, behind the test-only
override of the device check -- and, without the override, chip or fail."""

import json
import os
import subprocess
import sys

from perf.lib import bench
from perf.tests import tinyroot

RUN = os.path.join(bench.PERF_DIR, "run.py")


def _run(root, env_extra, trace="1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.sweep", "--seed", "3000000007",
         "--seconds", "2", "--trace", trace, "--bench-root", root],
        capture_output=True, text=True, env=env, timeout=900)


def test_no_accelerator_no_result(tmp_path):
    tinyroot.write(str(tmp_path))
    done = _run(str(tmp_path), {"PERF_REHEARSAL_PLATFORM": ""})
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_rehearsal_runs_every_phase_and_reports_no_number(tmp_path):
    tinyroot.write(str(tmp_path))
    done = _run(str(tmp_path), {"PERF_REHEARSAL_PLATFORM": "cpu"})
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["attempted"] == 2 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    # a CPU run writes no number under the name of a device metric
    assert result["metrics"] == {} and "breakdown" not in result
    with open(os.path.join(str(tmp_path), "BENCHMARK.json")) as f:
        b = json.load(f)
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert not any(f'"{n}"' in lines[-1] for n in names)
    said = "\n".join(lines[:-1])
    for phase in ("search:", "window:", "memory:", "reference check"):
        assert phase in said
    # nothing but (possibly) the XLA cache is left in the checkout
    assert not os.path.exists(os.path.join(bench.REPO, "saturn_ckpts", "a.npz"))


def test_priming_marker_speaks_for_one_path_and_one_tree(tmp_path, monkeypatch):
    import importlib.util

    spec = importlib.util.spec_from_file_location("perf_run_py", RUN)
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)
    here = run_py.checkout_fingerprint()
    assert here == run_py.checkout_fingerprint() and len(here) == 12
    # the same tree at another path, then that tree with one source file changed
    (tmp_path / "saturn_tpu").mkdir()
    (tmp_path / "perf").mkdir()
    (tmp_path / "perf" / "x.py").write_text("a = 1\n")
    monkeypatch.setattr(run_py, "REPO", str(tmp_path))
    monkeypatch.setattr(run_py, "HERE", str(tmp_path / "perf"))
    first = run_py.checkout_fingerprint()
    (tmp_path / "perf" / "x.py").write_text("a = 2\n")
    assert len({here, first, run_py.checkout_fingerprint()}) == 3
