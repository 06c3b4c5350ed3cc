"""CPU rehearsal of ``chip_smoke.py`` at ``test-tiny`` size on virtual devices.

The script itself has no CPU branch (``python chip_smoke.py`` under
``JAX_PLATFORMS=cpu`` exits non-zero — asserted below). The rehearsal imports
its phases, hands them the virtual devices in place of the platform check, and
stands in for the two checks only a chip can pass: that the grid holds a flash
attention point, and that the compiled step holds ``tpu_custom_call``s. All
the control flow, events, plan reading and checkpoint checks run for real.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY_ONE_CHIP = (("tiny-s64-b8", 64, 8), ("tiny-s32-b4", 32, 4))
TINY_FOUR_CHIP = (
    ("tiny-gang-s64-b6", 64, 6),
    ("tiny-gang-s32-b6", 32, 6),
    ("tiny-gang-s64-b8", 64, 8),
)


@pytest.fixture()
def rehearsal(monkeypatch, tmp_path, devices8):
    monkeypatch.setattr(
        chip_smoke, "accelerator_devices", lambda chips: devices8[:chips])
    # off-TPU the grid has no attention variants and no kernel lowers
    monkeypatch.setattr(
        chip_smoke, "kernel_config",
        lambda tech, task, n: tech.candidate_configs(task, n)[0])
    monkeypatch.setattr(chip_smoke, "require_kernel_calls", lambda text, what: {})
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(chip_smoke, "PRESET", "test-tiny")
    monkeypatch.setattr(chip_smoke, "TECHNIQUES", ("dp",))
    monkeypatch.setattr(chip_smoke, "ONE_CHIP_JOBS", TINY_ONE_CHIP)
    monkeypatch.setattr(chip_smoke, "ONE_CHIP_BATCHES", 12)
    monkeypatch.setattr(chip_smoke, "FOUR_CHIP_JOBS", TINY_FOUR_CHIP)
    monkeypatch.setattr(chip_smoke, "FOUR_CHIP_BATCHES", 16)
    monkeypatch.setattr(chip_smoke, "AGREE_SHAPE", (64, 8))
    monkeypatch.setattr(chip_smoke, "AGREE_STEPS", 4)
    return tmp_path / "out"


def _result_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    assert all(l.startswith("chip_smoke: ") for l in lines[:-1])
    return lines, json.loads(lines[-1])


def test_one_chip_phase_rehearsal(rehearsal, capsys):
    assert chip_smoke.main([]) == 0
    lines, result = _result_line(capsys)
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 1}}
    text = "\n".join(lines)
    for name, _, _ in TINY_ONE_CHIP:
        assert f"job {name}: step 12" in text
        assert f"kernels: {name} dp" in text
    assert "search: wall" in text and "plan: makespan" in text
    assert "mfu not measured" in text  # the host CPU has no published peak
    # a fresh save_dir and metrics_path under the output directory
    assert sorted(os.listdir(rehearsal)) == [
        "ckpts", "orchestrate.metrics.jsonl", "search.metrics.jsonl"]


def test_four_chip_phase_rehearsal(rehearsal, capsys):
    assert chip_smoke.main(["--chips", "4"]) == 0
    lines, result = _result_line(capsys)
    assert result["ok"] is True and result["device"]["count"] == 4
    text = "\n".join(lines)
    assert "agree: largest relative difference" in text
    for name, _, _ in TINY_FOUR_CHIP:
        assert f"job {name}: step 16" in text
        assert f"gang {name}: planned block" in text
    # three gangs planned side by side, each holding its own chip
    assert "gangs: 3 pair(s) planned side by side" in text
    # no phase of the one-chip run
    assert "kernels:" not in text and "tiny-s64-b8" not in text


def test_all_sizes_rehearsal_marks_indivisible_batch_infeasible(
        rehearsal, monkeypatch, capsys):
    """With every sub-mesh size allowed (what the chip cannot do yet, see
    ``chip_smoke.FOUR_CHIP_SIZES``), batch 6 on a data axis of 4 is marked
    infeasible by search itself and counts as no error."""
    monkeypatch.setattr(chip_smoke, "FOUR_CHIP_SIZES", None)
    monkeypatch.setattr(chip_smoke, "FOUR_CHIP_JOBS", TINY_FOUR_CHIP[1:])
    assert chip_smoke.main(["--chips", "4"]) == 0
    events = chip_smoke.read_events(
        str(rehearsal / "gangs.search.metrics.jsonl"), "trial_config")
    refused = [e for e in events if e["size"] == 4 and "b6" in e["task"]]
    assert refused and all("infeasible" in e for e in refused)


def test_a_raising_trial_config_fails_the_smoke(rehearsal, monkeypatch):
    from saturn_tpu.parallel.dp import DataParallel

    real = DataParallel._prepare

    def flaky(self, task, devices, config):
        if config.get("remat"):
            raise RuntimeError("kernel variant failed to lower")
        return real(self, task, devices, config)

    monkeypatch.setattr(DataParallel, "_prepare", flaky)
    monkeypatch.setattr(chip_smoke, "ONE_CHIP_JOBS", TINY_ONE_CHIP[:1])
    with pytest.raises(chip_smoke.SmokeFailure, match="failed to lower"):
        chip_smoke.main([])


def test_missing_kernel_call_fails_the_check():
    flash = 'x = custom-call(), custom_call_target="tpu_custom_call", saturn_flash_fwd'
    ce = 'y = custom-call(), custom_call_target="tpu_custom_call", saturn_ce_dx'
    assert chip_smoke.require_kernel_calls(flash + "\n" + ce, "both") == {
        "saturn_flash_": 1, "saturn_ce_": 1}
    with pytest.raises(chip_smoke.SmokeFailure, match="saturn_ce_"):
        chip_smoke.require_kernel_calls(flash, "flash only")


def test_script_refuses_to_run_without_a_chip(tmp_path):
    """No CPU branch: non-zero, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path), timeout=300,
    )
    assert run.returncode != 0
    assert "needs a TPU" in run.stderr
    assert '"ok"' not in run.stdout
    assert not (tmp_path / "chip_smoke_out").exists()
