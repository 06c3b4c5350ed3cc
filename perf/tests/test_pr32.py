"""What PR 32 added: the two new cells' files, the warm-up of a mix of
several intervals, the reference for a state larger than one chip, the
priming marker that names what it vouches for, the fp8 control's readings
through ``refcheck.verdict``, and a metric named
``<reader>.<cell>`` read by ``<reader>.py``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perf.lib import bench, harness, primed
from perf.tests import tinyroot

RUN = os.path.join(bench.PERF_DIR, "run.py")


# ------------------------------------------------------------------ loaders
@pytest.mark.parametrize("cell_name, chips, jobs, technique, steps", [
    ("gptj-6b-4chip.fsdp", 4, [(2048, 16)], "fsdp", 8),
    ("gpt2-medium.sweep2", 1, [(1024, 4), (1024, 2)], "dp", 8),
])
def test_the_new_cells_load(cell_name, chips, jobs, technique, steps):
    cell = bench.load_cell(cell_name)
    assert cell.chips == chips
    assert cell.traffic["technique_names"] == [technique]
    assert cell.traffic["chip_range"] == [chips]
    run = harness.Run(cell, seed=1, seconds=30.0, trace=True, t_process_start=0.0)
    assert [(j.seq, j.batch) for j in run.jobs] == jobs
    assert all(j.batch_count % 8 == 0 and j.batch_count >= 8 for j in run.jobs)
    want = cell.traffic["reference_check"]
    assert want["steps"] == steps and want["sequences"] % chips == 0
    names = {m["name"] for m in cell.per_layer}
    across = {"collective_share", "collective_exposed"}
    kernels = {"flash_roofline", "ce_roofline"}
    assert (across <= names) == (chips == 4) and (kernels <= names) == (chips == 1)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(bench.load_reader(cell, m["name"]))
    # the four-chip cell's window is 3/4 its checkpoint's writer and spreads
    # by more than any bound may cover (the driver's check of PR 32): its rate
    # is per layer there, and every metric it reports moves what it does report
    ends = {"search_s_per_job", "setup_s"} | ({"train_tokens_per_s"} if chips == 1 else set())
    assert {m["name"] for m in cell.end_to_end} == ends
    assert {m["moves"] for m in cell.per_layer} <= ends
    own = {"window_tokens_per_s", "step_ms.fsdp4", "mfu.fsdp4", "ckpt_write_gb_per_s.fsdp4"}
    assert (own <= names) == (chips == 4)
    assert ({"step_ms", "mfu", "ckpt_stall"} <= names) == (chips == 1)


def test_sweep2_is_a_mix_of_three_intervals_with_a_warm_up():
    cell = bench.load_cell("gpt2-medium.sweep2")
    t = cell.traffic
    assert harness.interval_seconds(t, 30.0) == pytest.approx(10.0, rel=1e-3)
    assert t["solver_time_limit"] == 2.0 and t["steps_per_window_second"] == 5.9
    assert [j["share"] for j in t["jobs"]] == [0.5, 0.5]
    assert [j["lr"] for j in t["jobs"]] == [1e-4, 1e-4]
    # the warm-up follows from the interval and the rounding, not from a key
    # of its own: shorter intervals than the window, whole windows of 8
    assert t["interval"]["window_fraction"] < 1 and t["round_steps_to"] == 8
    assert "warm_up" not in t
    # the one-interval mixes pay nothing: their set-up holds nothing new
    for other in ("gptj-6b-1chip.steady", "gpt2-medium.steady",
                  "ouro-2.6b-1chip.steady-4k", "gptj-6b-4chip.fsdp"):
        assert bench.load_cell(other).traffic["interval"]["window_fraction"] >= 1


def test_the_four_chip_configuration_is_the_one_chip_one_but_for_depth():
    one = bench.load_cell("gptj-6b-1chip.steady").config
    four = bench.load_cell("gptj-6b-4chip.fsdp").config
    differ = {k for k in set(one) | set(four) if one.get(k) != four.get(k)}
    assert differ == {"name", "n_layer", "run", "assumed", "deployment"}
    assert four["n_layer"] == 8 and four["run"]["overrides"]["n_layers"] == 8
    assert {k for k in one["run"] if one["run"][k] != four["run"][k]} == {"overrides"}
    assert {k for k in one["assumed"] if one["assumed"][k] != four["assumed"][k]} == {"depth"}
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert [w["name"] for w in b["workloads"] if w["chips"] == 4] == ["gptj-6b-4chip.fsdp"]
    assert len(b["workloads"]) == 5 and len(b["configs"]) == 4 and b["run_seconds"] == 30
    assert all(m["bound"] == 0.1 for m in b["end_to_end"])


def test_a_listless_metric_holds_where_what_it_moves_is_reported(tmp_path):
    """An end-to-end metric may list its cells; a per-layer metric with no
    list then holds in the cells that report what it moves, and one that
    lists a cell which does not is a fault of the file."""
    import json

    root = str(tmp_path)
    name = tinyroot.write(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    next(m for m in b["end_to_end"] if m["name"] == "train_tokens_per_s")["workloads"] = []
    b["per_layer"] = [m for m in b["per_layer"] if "." not in m["name"]]
    with open(path, "w") as f:
        json.dump(b, f)
    cell = bench.load_cell(name, root)
    assert [m["name"] for m in cell.end_to_end] == ["search_s_per_job", "setup_s"]
    assert {m["moves"] for m in cell.per_layer} == {"search_s_per_job"}
    b["per_layer"].append({"name": "mfu.tiny", "unit": "%", "better": "higher",
                           "source": "program_span", "layer": "step program",
                           "moves": "train_tokens_per_s", "workloads": [name]})
    with open(path, "w") as f:
        json.dump(b, f)
    with pytest.raises(bench.BenchmarkError, match="does not report"):
        bench.load_cell(name, root)


def test_a_metric_named_reader_dot_cell_is_read_by_the_reader(tmp_path):
    """A later PR gives a metric that lists its cells a new cell with an
    entry alone: ``flash_roofline.<cell>`` has no file and is read by
    ``flash_roofline.py``; a name with no reader at all is still an error."""
    cell = bench.load_cell(tinyroot.write(str(tmp_path)), str(tmp_path))
    import inspect

    read = bench.load_reader(cell, "flash_roofline.tiny.sweep")
    assert "saturn_flash_" in inspect.getsource(read)
    with pytest.raises(bench.BenchmarkError):
        bench.load_reader(cell, "no_such_reader.tiny.sweep")


# ------------------------------------------------------------------ warm-up
def _rehearse(root, seed, trace="0", devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.sweep", "--seed", str(seed),
         "--seconds", "2", "--trace", trace, "--bench-root", root],
        capture_output=True, text=True, env=env, timeout=900)


def test_rehearsal_of_sweep2_compiles_no_step_program_in_its_window(tmp_path):
    """Intervals of a twentieth of the window end inside a fused window, so
    the jobs meet 1-step tails and partial windows; the warm-up has built
    them, and what the window still compiles is the read-back's."""
    mix = dict(tinyroot.TINY_SWEEP2, interval={"window_fraction": 0.05},
               steps_per_window_second=24.0)
    tinyroot.write(str(tmp_path), mix)
    done = _rehearse(str(tmp_path), 3200000077)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["metrics"] == {}
    # every number compared, beside its limit: the line's last key, and the
    # last lines of standard error
    assert list(result)[-1] == "compared" and len(result["compared"]) == 10
    assert all(c["ok"] and c["value"] <= c["limit"] for c in result["compared"].values())
    assert done.stderr.strip().splitlines()[-1].startswith("perf: compared ")
    said = "\n".join(lines[:-1])
    warm = next(l for l in lines if l.startswith("perf: warm-up:"))
    assert "14 program(s)" in warm and "a K=1" in warm and "b K=7" in warm
    compiled = next(l for l in lines if "programs compiled inside it" in l)
    assert "saturn_step" not in compiled and "saturn_window" not in compiled
    # both jobs reached their batch_count over several intervals each
    for job in ("a", "b"):
        line = next(l for l in lines if l.startswith(f"perf: job {job}: step 24"))
        assert int(line.split(", ")[1].split()[0]) >= 2, line
    assert "NOT CORRECT" not in said


def test_rehearsal_of_the_four_chip_cell_runs_every_phase(tmp_path):
    """fsdp on a block of four (virtual) devices, the reference sharded over
    the same four."""
    tinyroot.write(str(tmp_path), tinyroot.TINY_FSDP, chips=4)
    done = _rehearse(str(tmp_path), 3200000081, trace="1", devices=4)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["device"]["count"] == 4
    assert result["metrics"] == {} and "breakdown" not in result
    assert sum("fsdp" in l and "ms/batch" in l for l in lines) >= 2
    assert not any(l.startswith("perf: warm-up:") for l in lines)
    assert set(c.split(".")[-1] for c in result["compared"]) == {
        "logits_rel_rms", "grad_rel_rms", "update_rel_rms", "loss_max_rel",
        "ckpt_leaves_differ"}
    # the checkpoint of the four chips' shards, read back under their shardings
    back = next(l for l in lines if l.startswith("perf: checkpoint read back:"))
    assert "0 differ" in back and "restored and compared bit for bit" in back
    assert any("checkpoint verified and its step read" in l for l in lines)


# ------------------------------------------------- the sharded reference
def test_the_sharded_reference_agrees_with_the_unsharded_one():
    """d 128, four virtual devices: the same float32 arithmetic partitioned
    by the compiler and a gradient taken one sequence at a time; sums come in
    another order, nothing else differs."""
    import jax

    from perf.reference import gpt

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four (virtual) devices")
    a = gpt.Arch("gptj", 512, 128, 2, 4, 512, 64, rotary_dim=16)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 512, (4, 64)).astype(np.int32) for _ in range(3)]
    one_l, one = gpt.train(a, 5, batches, 1e-3, keep_state=True)
    four_l, four = gpt.train(a, 5, batches, 1e-3, keep_state=True, devices=devices[:4])
    assert four_l == pytest.approx(one_l, rel=1e-5)
    for leaf, m in one["m"].items():
        scale = float(np.abs(m).max())
        assert float(np.abs(four["m"][leaf] - m).max()) <= 2e-4 * scale, leaf
        assert four["moved"][leaf] == pytest.approx(one["moved"][leaf], rel=1e-4)
        assert float(np.abs(four["params"][leaf] - one["params"][leaf]).max()) <= 2e-4
    logits = [np.asarray(gpt.logits_of(a, 5, batches[0], **kw))
              for kw in ({}, {"devices": devices[:4]})]
    assert float(np.abs(logits[0] - logits[1]).max()) <= 1e-5
    shardings = gpt.param_shardings(a, devices[:4])
    assert "" not in shardings and set(shardings) == set(gpt._shapes(a))
    # the stacked layer axis stays whole; the largest other axis is split
    assert tuple(shardings["blocks/mlp_in/kernel"].spec) == (None, None, "chips")
    assert tuple(shardings["wte"].spec) == ("chips", None)
    # one chip, or none named: the unsharded pieces, as before
    assert gpt._over(devices[:1]) is None and gpt._over(None) is None


# ------------------------------------------------------- the priming marker
def test_a_marker_holds_while_what_it_names_is_there(tmp_path, capsys):
    import time

    cache = tmp_path / "cache"
    (cache / primed.REFUSED_SUBDIR).mkdir(parents=True)
    long_ago = time.time() - 3600
    for name in ("jit_old-aaa-cache", "jit_old-aaa-atime",      # untouched
                 "jit_hit-bbb-cache",                           # read: its -atime is new
                 "jit_other-ccc-cache"):                        # no size limit, no trace
        (cache / name).write_bytes(b"x")
        os.utime(cache / name, (long_ago, long_ago))
    (cache / "jit_hit-bbb-atime").write_bytes(b"x")
    (cache / "jit_saturn_window-ddd-cache").write_bytes(b"x")  # written
    (cache / primed.REFUSED_SUBDIR / "r1.json").write_text("{}")
    marker = str(cache / "perf-primed.cell.hash")
    assert not primed.holds(marker, str(cache))            # no marker
    with open(marker, "w") as f:
        f.write("primed in 12.0s\n")                        # the form before PR 32
    assert not primed.holds(marker, str(cache))
    primed.write(marker, str(cache), time.time() - 60)
    with open(marker) as f:
        said = json.load(f)
    assert said["entries"] == ["jit_hit-bbb-cache", "jit_saturn_window-ddd-cache"]
    assert said["refusals"] == [os.path.join(primed.REFUSED_SUBDIR, "r1.json")]
    assert primed.holds(marker, str(cache))
    (cache / "jit_old-aaa-cache").unlink()                  # not what it vouches for
    assert primed.holds(marker, str(cache))
    (cache / "jit_hit-bbb-cache").unlink()                  # the cache was trimmed
    assert not primed.holds(marker, str(cache))
    assert "trimmed" in capsys.readouterr().out
    (cache / "jit_hit-bbb-cache").write_bytes(b"x")
    (cache / primed.REFUSED_SUBDIR / "r1.json").unlink()
    assert not primed.holds(marker, str(cache))


def test_the_marker_names_the_files_jax_keeps_of_a_key():
    """``primed`` goes by the two suffixes of ``jax._src.lru_cache``; where a
    later JAX names them otherwise a marker would name nothing."""
    from jax._src import lru_cache

    assert (primed.ENTRY, primed.ATIME) == (lru_cache._CACHE_SUFFIX,
                                            lru_cache._ATIME_SUFFIX)


def test_the_priming_child_writes_a_marker_that_holds(tmp_path):
    """``run.py --prime <marker>`` (what a checkout's first run starts as a
    child) at tiny size: search, warm-up and reference check, no window and
    no result line, and at its end the marker, which holds."""
    tinyroot.write(str(tmp_path / "root"), tinyroot.TINY_SWEEP2)
    cache = tmp_path / "cache"
    cache.mkdir()
    marker = str(cache / "perf-primed.tiny.sweep.abc")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    done = subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.sweep", "--seed", "3200000631",
         "--seconds", "2", "--trace", "0", "--bench-root", str(tmp_path / "root"),
         "--prime", marker], capture_output=True, text=True, env=env, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    assert "perf: primed in" in done.stdout and "window:" not in done.stdout
    with open(marker) as f:
        said = json.load(f)
    assert set(said) == {"primed_s", "entries", "refusals"}
    assert all(n.endswith(primed.ENTRY) and (cache / n).exists() for n in said["entries"])
    assert primed.holds(marker, str(cache))


# ---------------------------------------- the control's readings, judged
def test_every_recorded_control_reading_is_not_correct_and_every_program_one_is():
    """``limits.json`` keeps what the chip read at each new cell's own size;
    put through ``refcheck.verdict`` as a run's numbers are, the fp8 control
    is outside every limit it is held to at both ends of its range, the sound
    program inside."""
    from perf.lib import refcheck

    with open(refcheck.LIMITS_FILE) as f:
        said = json.load(f)
    limits = refcheck.load_limits()
    judged = 0
    for cell, sides in said["readings_pr32"].items():
        if not isinstance(sides, dict):
            continue
        for side, numbers in sides.items():
            for end in (0, 1):
                one = {k: v[end] for k, v in numbers.items()}
                one.setdefault("loss_drop_rel", 0.0)
                lines = []
                ok = refcheck.verdict(one, limits, lines.append, side)
                assert ok == side.startswith("program"), (cell, side, lines)
                if side.startswith("fp8 control"):
                    # not by one number alone: by each that separates precisions
                    failed = {l.rsplit(" = ", 1)[0].rsplit(" ", 1)[1]
                              for l in lines if "NOT OK" in l}
                    assert {"logits_rel_rms", "grad_rel_rms", "update_rel_rms"} <= failed
                judged += 1
    assert judged >= 8


# ------------------------------------------- the timed path broken underneath
@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch_left_out",
                                   "a_token_altered"])
def test_a_broken_step_is_not_correct(fault):
    """The faults a training cell can have, planted in the reference put in
    the program's place (d 128, CPU) and judged by the committed limits: a
    step that hands back its state as it got it; half of the batch left out
    and the mean taken over the rest; one token of the batch altered where
    the batch is produced."""
    from perf.lib import refcheck
    from perf.reference import gpt

    arch = gpt.Arch("gptj", vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                    d_ff=512, n_positions=128, rotary_dim=16)
    _, batches = refcheck.sample_batches(512, 128, 4, 4, 3200000091)
    ref_losses, _, ref_state = refcheck.reference_side(gpt, arch, 5, batches, 1e-3)
    if fault == "state_unchanged":
        losses = [ref_losses[0]] * len(ref_losses)
        seeded = gpt.flat(gpt.program_layout(
            arch, _host(gpt.seeded_params(arch, gpt.seed_key(5))), xp=np))
        state = {"m": {k: np.zeros_like(v) for k, v in ref_state["m"].items()},
                 "params": seeded}
    else:
        if fault == "half_the_batch_left_out":
            broken = [b[:2] for b in batches]
        else:
            broken = [np.array(b) for b in batches]
            for b in broken:
                b[0, 17] = (b[0, 17] + 1) % 512
        losses, state = gpt.train(arch, 5, broken, 1e-3, keep_state=True)
    numbers = {"logits_rel_rms": 0.0, **refcheck.loss_errors(ref_losses, losses),
               **refcheck.state_errors(ref_state, state)}
    said = []
    assert refcheck.verdict(numbers, refcheck.load_limits(), said.append, fault) is False
    if fault == "state_unchanged":
        # by the norm of the difference an unmoved leaf reads 1
        assert numbers["grad_rel_rms"] == pytest.approx(1.0, abs=1e-6)
        assert numbers["update_rel_rms"] == pytest.approx(1.0, abs=1e-3)


def _host(tree):
    import jax

    return jax.tree_util.tree_map(np.asarray, tree)
