"""Async step pipeline (round 10): window policy, prefetcher, and the
fused-vs-per-step equivalence guarantees.

The tentpole's central claim is that the fused K-step dispatch is a pure
dispatch-shape change: ``lax.scan`` over the SAME train step the 1-step
program runs, so the loss trajectory and final checkpoint are bit-identical
for any K — including when a SimulatedKill lands mid-window (the interval
is all-or-nothing; the retry replays from the checkpoint).
"""

import numpy as np
import pytest

from saturn_tpu.core.strategy import Strategy
from saturn_tpu.data.prefetch import DevicePrefetcher
from saturn_tpu.parallel.spmd_base import (
    DEFAULT_MAX_WINDOW,
    choose_window,
    dispatch_signature,
    max_window,
)
from saturn_tpu.resilience.crash import SimulatedKill
from saturn_tpu.utils import checkpoint as ckpt


class TestWindowPolicy:
    def test_short_intervals_stay_per_step(self):
        assert choose_window(0) == 1
        assert choose_window(1) == 1

    def test_window_capped_by_budget_and_env(self, monkeypatch):
        monkeypatch.delenv("SATURN_TPU_MAX_WINDOW", raising=False)
        assert max_window() == DEFAULT_MAX_WINDOW
        assert choose_window(100) == DEFAULT_MAX_WINDOW
        assert choose_window(3) == 3  # budget below the cap wins
        monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", "4")
        assert choose_window(100) == 4

    def test_cap_of_one_disables_fusion(self, monkeypatch):
        monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", "1")
        assert choose_window(100) == 1

    def test_invalid_env_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", "banana")
        assert max_window() == DEFAULT_MAX_WINDOW
        monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", "0")
        assert max_window() == 1  # clamped, never 0

    def test_dispatch_signature_tracks_window(self, monkeypatch):
        monkeypatch.delenv("SATURN_TPU_MAX_WINDOW", raising=False)
        assert dispatch_signature() == f"fused-scan-v1:k{DEFAULT_MAX_WINDOW}"
        monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", "1")
        assert dispatch_signature() == "per-step"


class TestDevicePrefetcher:
    def test_yields_in_order(self):
        with DevicePrefetcher(10, lambda i: i * i, depth=2) as pf:
            assert list(pf) == [i * i for i in range(10)]

    def test_bounded_depth(self):
        import threading

        staged = []
        gate = threading.Event()

        def stage(i):
            staged.append(i)
            return i

        pf = DevicePrefetcher(10, stage, depth=2)
        try:
            assert next(pf) == 0
            gate.wait(0.3)  # give the producer time to overrun if it could
            # one consumed + at most depth in the queue + one in flight
            assert len(staged) <= 4
        finally:
            pf.close()

    def test_stage_exception_reraised_in_consumer(self):
        def stage(i):
            if i == 3:
                raise ValueError("bad batch")
            return i

        pf = DevicePrefetcher(10, stage, depth=2)
        try:
            got = []
            with pytest.raises(ValueError, match="bad batch"):
                for v in pf:
                    got.append(v)
            assert got == [0, 1, 2]  # everything before the fault arrived
        finally:
            pf.close()

    def test_simulated_kill_crosses_thread(self):
        """SimulatedKill is a BaseException — 'except Exception' would miss
        it; the prefetcher must still deliver it to the consumer."""

        def stage(i):
            if i == 1:
                raise SimulatedKill("mid-staging")
            return i

        pf = DevicePrefetcher(5, stage, depth=2)
        try:
            with pytest.raises(SimulatedKill):
                list(pf)
        finally:
            pf.close()

    def test_close_unblocks_parked_producer(self):
        """A producer blocked on a full queue must exit promptly on close —
        a leaked thread would keep calling stage() on a rolled-back task."""
        pf = DevicePrefetcher(100, lambda i: i, depth=1)
        next(pf)  # let the producer start and fill the queue
        pf.close()
        assert not pf._thread.is_alive()
        with pytest.raises(StopIteration):
            next(pf)


def _pipeline_task(tmp_path, tag, batch_count=6):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    return Task(
        get_model=lambda **kw: build_gpt2("test-tiny", **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=64, batch_size=8, vocab_size=256, n_tokens=64 * 8 * 8
        ),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=batch_count),
        chip_range=[4],
        name="pipe-eq",  # same name both arms: identical init PRNG stream
        save_dir=str(tmp_path / tag),
    )


def _run_interval(task, tech, devices, n, window_size):
    task.strategies = {
        len(devices): Strategy(
            executor=tech, apportionment=len(devices), params={},
            runtime=1.0, per_batch_time=0.1,
        )
    }
    task.select_strategy(len(devices))
    tech.execute(task, devices, 0, override_batch_count=n,
                 window_size=window_size)
    ckpt.flush()
    return ckpt.load_arrays(task.ckpt_path)


class TestFusedEquivalence:
    def test_fused_window_matches_per_step_exactly(self, tmp_path, devices8):
        """K=3 fused windows (+ no tail) vs the legacy 1-step loop: same
        final step count, bit-identical parameters."""
        from saturn_tpu.parallel.dp import DataParallel

        devs = devices8[:4]
        ref = _run_interval(
            _pipeline_task(tmp_path, "per-step"), DataParallel(), devs,
            n=6, window_size=1,
        )
        fused = _run_interval(
            _pipeline_task(tmp_path, "fused"), DataParallel(), devs,
            n=6, window_size=3,
        )
        assert int(ref["step"]) == int(fused["step"]) == 6
        assert set(ref) == set(fused)
        for name in ref:
            np.testing.assert_array_equal(ref[name], fused[name], err_msg=name)

    def test_tail_batches_use_exact_fallback(self, tmp_path, devices8):
        """n=5, K=3: one fused window + a 2-batch per-step tail must equal
        the pure per-step run — the tail is the SAME 1-step program."""
        from saturn_tpu.parallel.dp import DataParallel

        devs = devices8[:4]
        ref = _run_interval(
            _pipeline_task(tmp_path, "ref", batch_count=5), DataParallel(),
            devs, n=5, window_size=1,
        )
        mixed = _run_interval(
            _pipeline_task(tmp_path, "mixed", batch_count=5), DataParallel(),
            devs, n=5, window_size=3,
        )
        assert int(mixed["step"]) == 5
        for name in ref:
            np.testing.assert_array_equal(ref[name], mixed[name], err_msg=name)

    def test_midwindow_kill_discards_interval_then_replay_matches(
        self, tmp_path, devices8
    ):
        """SimulatedKill inside the SECOND fused window: the interval leaves
        no checkpoint and no live state (all-or-nothing), and the replay
        from scratch matches the per-step reference bit-for-bit."""
        from saturn_tpu.parallel.dp import DataParallel

        devs = devices8[:4]
        ref = _run_interval(
            _pipeline_task(tmp_path, "ref"), DataParallel(), devs,
            n=6, window_size=1,
        )

        task = _pipeline_task(tmp_path, "killed")
        tech = DataParallel()
        task.strategies = {
            4: Strategy(executor=tech, apportionment=4, params={},
                        runtime=1.0, per_batch_time=0.1)
        }
        task.select_strategy(4)
        bundle = tech.build(task, devs, {})
        real = bundle.fused_compiled(3)
        calls = {"n": 0}

        def killer(state, window):
            calls["n"] += 1
            if calls["n"] == 2:
                raise SimulatedKill("mid-window")
            return real(state, window)

        bundle._fused[3] = killer
        try:
            with pytest.raises(SimulatedKill):
                tech.execute(task, devs, 0, override_batch_count=6,
                             window_size=3)
        finally:
            bundle._fused[3] = real
        ckpt.flush()
        # All-or-nothing: no checkpoint, no cached device state, no realized
        # feedback from the dead attempt.
        assert not task.has_ckpt()
        assert task._live_state is None
        assert task._pending_realized is None

        replay = _run_interval(task, tech, devs, n=6, window_size=3)
        assert int(replay["step"]) == 6
        for name in ref:
            np.testing.assert_array_equal(ref[name], replay[name],
                                          err_msg=name)


@pytest.mark.slow
def test_orchestrate_equivalent_across_window_caps(tmp_path, devices8,
                                                   monkeypatch):
    """The ISSUE's acceptance run: a seeded 2-task orchestrate under
    SATURN_TPU_MAX_WINDOW=1 vs =4 produces identical final checkpoints and
    the same iteration ledger (all batches retired exactly once)."""
    import saturn_tpu
    from saturn_tpu import HParams, Task, library
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    def mk(tag, name, lr):
        return Task(
            get_model=lambda **kw: build_gpt2("test-tiny", **kw),
            get_dataloader=lambda: make_lm_dataset(
                context_length=64, batch_size=8, vocab_size=256,
                n_tokens=64 * 8 * 8,
            ),
            loss_fn=pretraining_loss,
            hparams=HParams(lr=lr, batch_count=8),
            chip_range=[4],
            name=name,
            save_dir=str(tmp_path / tag),
        )

    topo = SliceTopology(devices8)
    library.register_default_library()
    finals = {}
    for cap in ("1", "4"):
        monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", cap)
        tasks = [mk(f"cap{cap}", "eq-lr3", 1e-3), mk(f"cap{cap}", "eq-lr4", 1e-4)]
        saturn_tpu.search(tasks, technique_names=["dp"], topology=topo)
        saturn_tpu.orchestrate(tasks, interval=30.0, topology=topo,
                               solver_time_limit=5.0)
        for t in tasks:
            assert t.total_batches == 0
            assert t.has_ckpt()
        finals[cap] = {t.name: ckpt.load_arrays(t.ckpt_path) for t in tasks}

    for name in finals["1"]:
        a, b = finals["1"][name], finals["4"][name]
        assert int(a["step"]) == int(b["step"]) == 8
        assert set(a) == set(b)
        for arr in a:
            np.testing.assert_array_equal(a[arr], b[arr],
                                          err_msg=f"{name}/{arr}")
