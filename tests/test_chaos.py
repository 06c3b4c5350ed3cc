"""Chaos campaign harness (round 13): seeded schedules and the ISSUE's
acceptance sweep.

The fast half is hardware-free: schedule determinism/coverage and the
``compare_checkpoints`` bit-identity primitive. The slow half runs the real acceptance campaign — three seeded
mixed-fault sweeps (one per health-fault class each) over two tiny GPT-2
jobs, the first seed killed at the ``post-rollback`` journal barrier — and
asserts zero lost jobs, quarantine surviving the kill via journal replay,
and byte-identical final checkpoints against a fault-free reference run
with the campaign's quarantine pre-applied.
"""

import os

import numpy as np
import pytest

from saturn_tpu.resilience.chaos import (
    CampaignSpec,
    HEALTH_FAULT_CLASSES,
    campaign_schedule,
    compare_checkpoints,
    run_campaign,
)
from saturn_tpu.resilience.faults import FaultKind

pytestmark = pytest.mark.chaos


# ----------------------------------------------------------------- schedule
class TestCampaignSchedule:
    NAMES = ["job-a", "job-b", "job-c"]

    def test_deterministic_for_a_seed(self):
        spec = CampaignSpec(seed=7)
        assert campaign_schedule(self.NAMES, spec) == \
            campaign_schedule(self.NAMES, spec)
        other = campaign_schedule(self.NAMES, CampaignSpec(seed=8))
        assert other != campaign_schedule(self.NAMES, spec)

    def test_one_event_per_health_class(self):
        events = campaign_schedule(self.NAMES, CampaignSpec(seed=3))
        assert [e.kind for e in events] == list(HEALTH_FAULT_CLASSES)
        for e in events:
            assert e.task in self.NAMES
            assert e.at_interval == 0  # max_intervals_hit defaults to 1

    def test_event_payload_by_class(self):
        spec = CampaignSpec(seed=5, poison_range=6, poison_batches=2,
                            stall_s=0.7)
        by_kind = {e.kind: e for e in campaign_schedule(self.NAMES, spec)}
        poison = by_kind[FaultKind.BATCH_POISON]
        assert len(poison.batches) == 2
        assert all(0 <= i < 6 for i in poison.batches)
        assert by_kind[FaultKind.DISPATCH_STALL].stall_s == 0.7
        assert 0 <= by_kind[FaultKind.NUMERIC_NAN].step < 4

    def test_non_health_class_rejected(self):
        spec = CampaignSpec(seed=1, fault_classes=(FaultKind.DEVICE_LOSS,))
        with pytest.raises(ValueError, match="not a health-fault class"):
            campaign_schedule(self.NAMES, spec)

    def test_empty_task_list_rejected(self):
        with pytest.raises(ValueError, match="at least one task"):
            campaign_schedule([], CampaignSpec(seed=1))


# -------------------------------------------------------- compare primitive
class TestCompareCheckpoints:
    def _save(self, d, stem, **arrays):
        os.makedirs(d, exist_ok=True)
        np.savez(os.path.join(d, f"{stem}.npz"), **arrays)

    def test_identical_including_nan(self, tmp_path):
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        w = np.asarray([1.0, np.nan, 3.0], dtype=np.float32)
        self._save(a, "job", w=w, b=np.zeros(2))
        self._save(b, "job", w=w.copy(), b=np.zeros(2))
        assert compare_checkpoints(a, b) == []

    def test_single_bit_flip_caught(self, tmp_path):
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        w = np.arange(4, dtype=np.float32)
        self._save(a, "job", w=w)
        w2 = w.copy()
        w2.view(np.uint32)[1] ^= 1  # flip one mantissa bit
        self._save(b, "job", w=w2)
        assert compare_checkpoints(a, b) == ["job[w]: bytes differ"]

    def test_missing_and_key_mismatch(self, tmp_path):
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        self._save(a, "job", w=np.zeros(2))
        self._save(a, "gone", w=np.zeros(2))
        self._save(b, "job", other=np.zeros(2))
        got = compare_checkpoints(a, b)
        assert any("gone: missing" in m for m in got)
        assert any("key sets differ" in m for m in got)

    def test_explicit_names_limit_the_comparison(self, tmp_path):
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        self._save(a, "job", w=np.zeros(2))
        self._save(a, "junk", w=np.ones(2))
        self._save(b, "job", w=np.zeros(2))
        assert compare_checkpoints(a, b, names=["job"]) == []


# --------------------------------------------------------------- acceptance
SEQ_LEN = 16
BATCH_SIZE = 2
N_BATCHES = 8   # == epoch length, so quarantine comparison stays exact
TASK_NAMES = ("chaos-a", "chaos-b")


def _make_template(save_dir, name):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    return Task(
        get_model=lambda **kw: build_gpt2("test-tiny", seq_len=SEQ_LEN, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=SEQ_LEN, batch_size=BATCH_SIZE, vocab_size=256,
            n_tokens=SEQ_LEN * BATCH_SIZE * N_BATCHES,
        ),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=N_BATCHES),
        chip_range=[2],
        name=name,
        save_dir=save_dir,
    )


def _clone_tasks(templates, save_dir):
    os.makedirs(save_dir, exist_ok=True)
    out = []
    for t in templates:
        c = t.clone(name=t.name)
        c.save_dir = save_dir
        out.append(c)
    return out


@pytest.mark.slow
class TestAcceptanceCampaign:
    """The ISSUE's scenario: >= 4 fault classes x >= 3 seeds, one seed killed
    mid-recovery, zero lost jobs, quarantine surviving the kill, and
    bit-identical post-rollback trajectories."""

    SEEDS = (11, 23, 47)

    @pytest.fixture(scope="class")
    def profiled_templates(self, tmp_path_factory):
        import jax

        import saturn_tpu
        from saturn_tpu import library
        from saturn_tpu.core.mesh import SliceTopology
        from saturn_tpu.health import SentinelConfig, sentinel

        library.register_default_library()
        # The campaign injects 1e9 spikes; the EWMA screen (off by default —
        # divergence thresholds are workload policy) must be on to see them.
        sentinel.set_config(
            SentinelConfig(enabled=True, spike_factor=8.0, warmup_steps=2)
        )
        tmp = tmp_path_factory.mktemp("chaos-acceptance")
        templates = [
            _make_template(str(tmp / "templates"), n) for n in TASK_NAMES
        ]
        topo = SliceTopology(jax.devices())
        saturn_tpu.search(templates, technique_names=["dp"], topology=topo)
        yield templates, topo, tmp
        sentinel.set_config(None)

    def test_campaign_sweep(self, profiled_templates):
        import saturn_tpu
        from saturn_tpu.durability import replay_batch_state

        templates, topo, tmp = profiled_templates
        orchestrate_kw = dict(interval=30.0, topology=topo,
                              solver_time_limit=2.0)
        kills = 0
        for i, seed in enumerate(self.SEEDS):
            spec = CampaignSpec(seed=seed, kill_during_rollback=(i == 0),
                                poison_range=N_BATCHES, stall_s=0.25)
            save = str(tmp / f"camp{seed}" / "ckpts")
            wal = str(tmp / f"camp{seed}" / "wal")
            result = run_campaign(
                lambda: _clone_tasks(templates, save), spec, wal,
                **orchestrate_kw,
            )

            # zero lost jobs, across every restart
            assert sorted(result.completed) == sorted(TASK_NAMES)
            assert result.failed == {}
            kills += result.kills

            # quarantine survived: what the harness reports IS what a fresh
            # incarnation would replay out of the durable journal
            assert result.quarantined == replay_batch_state(wal).quarantined

            # bit-identical trajectory: a fault-free run over the same
            # surviving batch sequence produces the same bytes
            ref_save = str(tmp / f"camp{seed}" / "ref")
            ref_tasks = _clone_tasks(templates, ref_save)
            for t in ref_tasks:
                t.quarantine_batches(result.quarantined.get(t.name, []))
            saturn_tpu.orchestrate(ref_tasks, **orchestrate_kw)
            assert compare_checkpoints(save, ref_save,
                                       names=list(TASK_NAMES)) == []

        # the armed seed really did die at post-rollback and restart
        assert kills >= 1

    def test_stall_below_watchdog_deadline_is_absorbed(self, profiled_templates):
        """A dispatch stall shorter than the watchdog deadline costs wall
        clock only — no fault, no restart, jobs complete first try."""
        from saturn_tpu.resilience.faults import FaultEvent, FaultInjector

        import saturn_tpu

        templates, topo, tmp = profiled_templates
        tasks = _clone_tasks(templates, str(tmp / "stall" / "ckpts"))
        injector = FaultInjector(schedule=[
            FaultEvent(0, FaultKind.DISPATCH_STALL, task="chaos-a",
                       stall_s=0.2),
        ])
        out = saturn_tpu.orchestrate(
            tasks, interval=30.0, topology=topo, solver_time_limit=2.0,
            fault_injector=injector,
        )
        assert sorted(out["completed"]) == sorted(TASK_NAMES)
        assert out["failed"] == {}
