"""Crash-safe durability: journal unit tests + the kill-replay harness.

Everything runs hardware-free on the 8 virtual CPU devices from conftest.
The acceptance test at the bottom is the ISSUE's scenario: a 4-job
mixed-priority service run killed at three distinct kill-points
(mid-interval, mid-fsync — with a genuinely torn journal tail — and
post-checkpoint), restarted against the same journal directory each time,
with the asserts that zero admitted jobs are lost, zero durably completed
iterations are re-run (journal sequence numbers are the evidence), and the
corrupt trailing artifacts are quarantined rather than fatal.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from saturn_tpu.core.mesh import SliceTopology
from saturn_tpu.core.strategy import Strategy
from saturn_tpu.core.technique import BaseTechnique
from saturn_tpu.durability import (
    Journal,
    JournalCorruptError,
    build_restore_records,
    recover,
    replay,
    replay_batch_state,
    replay_service_state,
)
from saturn_tpu.resilience import CrashInjector, SimulatedKill, run_to_kill

pytestmark = pytest.mark.crash


class FakeDev:
    pass


def topo(n=8):
    return SliceTopology([FakeDev() for _ in range(n)])


class RecordingTech(BaseTechnique):
    name = "crash-fake"

    def __init__(self, per_batch=0.001):
        self.per_batch = per_batch
        self.calls = []
        self.lock = threading.Lock()
        # Closed by a test that must arm a kill before any batch runs: no
        # execute starts while it is clear, however slow the test's thread.
        self.gate = threading.Event()
        self.gate.set()

    def execute(self, task, devices, tid, override_batch_count=None):
        self.gate.wait()
        with self.lock:
            self.calls.append((task.name, override_batch_count or 1))
        time.sleep(self.per_batch * (override_batch_count or 1))

    def search(self, task, devices, tid):
        return {}, self.per_batch


class FakeTask:
    """Duck-typed pre-profiled task (admission skips the trial sweep)."""

    def __init__(self, name, total_batches, sizes, tech, pbt=0.001):
        self.name = name
        self.total_batches = total_batches
        self.current_batch = 0
        self.epoch_length = 1000
        self.hints = {}
        self.chip_range = None
        self.strategies = {
            g: Strategy(tech, g, {}, pbt * total_batches, pbt) for g in sizes
        }
        self.selected_strategy = None

    def feasible_strategies(self):
        return {g: s for g, s in self.strategies.items() if s.feasible}

    def select_strategy(self, g):
        self.selected_strategy = self.strategies[g]

    def reconfigure(self, n):
        self.current_batch = (self.current_batch + n) % self.epoch_length


# ------------------------------------------------------------------ journal
class TestJournal:
    def test_roundtrip_rotation_and_seq_continuity(self, tmp_path):
        d = str(tmp_path / "wal")
        j = Journal(d, segment_max_bytes=512)
        j.log("job_submitted", job="j0001-a", task="a", total_batches=10)
        for _ in range(20):
            j.append("task_progress", task="a", job="j0001-a", batches=1)
        assert j.pending == 20
        j.commit()
        assert j.pending == 0
        j.close()

        segs = [n for n in os.listdir(d) if n.endswith(".jsonl")]
        assert len(segs) >= 2  # 512-byte cap forced at least one rotation
        recs = replay(d, strict=True)
        seqs = [r["seq"] for r in recs]
        assert seqs == list(range(1, len(recs) + 1))  # strictly monotonic

        # a new incarnation continues the sequence, in a FRESH segment
        # (whose segment_open header consumes the next seq itself)
        j2 = Journal(d, segment_max_bytes=512)
        s = j2.log("recovery")
        assert s == seqs[-1] + 2
        j2.close()
        assert replay(d, strict=True)[-1]["seq"] == s

    def test_uncommitted_records_die_with_the_process(self, tmp_path):
        d = str(tmp_path / "wal")
        j = Journal(d)
        j.log("a")
        j.append("b")  # never committed — "process dies" here
        recs = replay(d, strict=True)
        assert [r["kind"] for r in recs] == ["segment_open", "a"]

    def test_torn_tail_quarantined_and_seq_resumes(self, tmp_path):
        d = str(tmp_path / "wal")
        j = Journal(d)
        j.log("a")
        j.log("b")
        j.close()
        seg = os.path.join(d, "wal-000001.jsonl")
        with open(seg, "ab") as f:
            f.write(b'{"crc":"00000000","data":{},"ki')  # torn append
        with pytest.raises(JournalCorruptError):
            replay(d, strict=True)

        j2 = Journal(d)  # open runs recovery
        assert j2.recovery_report["quarantined"] == [seg + ".corrupt"]
        assert os.path.exists(seg + ".corrupt")
        j2.log("c")
        j2.close()
        recs = replay(d, strict=True)  # strict passes after quarantine
        assert [r["kind"] for r in recs if r["kind"] != "segment_open"] == [
            "a", "b", "c",
        ]

    def test_mid_sequence_corruption_rolls_back_later_segments(self, tmp_path):
        d = str(tmp_path / "wal")
        j = Journal(d, segment_max_bytes=256)
        for i in range(12):
            j.log("rec", i=i)
        j.close()
        segs = sorted(n for n in os.listdir(d) if n.endswith(".jsonl"))
        assert len(segs) >= 3
        # flip bytes in the MIDDLE segment: everything after the durable cut
        # must roll back, including structurally-valid later segments
        victim = os.path.join(d, segs[1])
        raw = open(victim, "rb").read()
        open(victim, "wb").write(raw[: len(raw) // 2] + b"XXXX"
                                 + raw[len(raw) // 2 + 4:])
        report = recover(d)
        assert len(report["quarantined"]) >= 2  # victim tail + later segs
        recs = replay(d, strict=True)
        datas = [r["data"]["i"] for r in recs if r["kind"] == "rec"]
        assert datas == list(range(len(datas)))  # a clean prefix, no gaps

    def test_crc_catches_bit_rot(self, tmp_path):
        d = str(tmp_path / "wal")
        j = Journal(d)
        j.log("x", payload="hello")
        j.close()
        seg = os.path.join(d, "wal-000001.jsonl")
        raw = open(seg, "rb").read()
        open(seg, "wb").write(raw.replace(b"hello", b"jello"))
        recs = replay(d)  # non-strict: stops at the bad record
        assert all(r["kind"] != "x" for r in recs)


# --------------------------------------------------------------- kill points
class TestCrashInjector:
    def test_fires_on_exact_hit_then_goes_inert(self, tmp_path):
        inj = CrashInjector("post-commit", hit=2)
        j = Journal(str(tmp_path / "wal"), barrier=inj.barrier)
        j.log("a")
        with pytest.raises(SimulatedKill):
            j.log("b")
        assert inj.fired.is_set()
        j.log("c")  # inert after firing: the "dead" process's threads unwind
        assert replay(str(tmp_path / "wal"), strict=True)[-1]["kind"] == "c"

    def test_mid_fsync_kill_tears_the_tail(self, tmp_path):
        d = str(tmp_path / "wal")
        inj = CrashInjector("mid-fsync", hit=1, armed=False)
        j = Journal(d, barrier=inj.barrier)
        j.log("a")  # disarmed: setup commits pass through
        inj.arm()
        with pytest.raises(SimulatedKill):
            j.log("b", payload="x" * 64)
        # the un-fsync'd tail was physically torn: recovery must quarantine
        report = recover(d)
        assert report["quarantined"]
        recs = replay(d, strict=True)
        assert [r["kind"] for r in recs if r["kind"] != "segment_open"] == ["a"]

    def test_seeded_is_deterministic(self):
        a = CrashInjector.seeded(1234, armed=False)
        b = CrashInjector.seeded(1234, armed=False)
        assert (a.point, a.hit) == (b.point, b.hit)


# ------------------------------------------------------- checkpoint satellite
class TestCheckpointCorruption:
    def test_corrupt_npz_quarantined_with_typed_error(self, tmp_path):
        from saturn_tpu.utils import checkpoint as ckpt

        path = str(tmp_path / "state.npz")
        good = {"a": np.arange(4, dtype=np.float32)}
        ckpt.save(path, good)
        assert ckpt.verify(path) is True

        with open(path, "wb") as f:
            f.write(b"PK\x03\x04 this is not a checkpoint")
        assert ckpt.verify(path) is False
        with pytest.raises(ckpt.CheckpointCorruptError) as ei:
            ckpt.restore(path, good)
        assert ei.value.quarantined == path + ".corrupt"
        assert os.path.exists(path + ".corrupt")
        assert not os.path.exists(path)  # recovery falls back to previous

    def test_missing_is_not_corrupt(self, tmp_path):
        from saturn_tpu.utils import checkpoint as ckpt

        with pytest.raises(FileNotFoundError):
            ckpt.restore(str(tmp_path / "never.npz"), {"a": np.zeros(1)})

    def test_publish_hook_fires_after_atomic_rename(self, tmp_path):
        from saturn_tpu.utils import checkpoint as ckpt

        seen = []
        hook = lambda stem, path: seen.append((stem, os.path.exists(path)))
        ckpt.add_publish_hook(hook)
        try:
            ckpt.save(str(tmp_path / "t1.npz"), {"a": np.zeros(2)})
        finally:
            ckpt.remove_publish_hook(hook)
        assert seen == [("t1", True)]


class TestStreamedSaveBarriers:
    """PR 27: the save is a pipeline (the writer thread fills the shard file
    while the shards still land), and the commit is what it was: both
    barriers, in order, once each, the publish hook after the manifest."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        from saturn_tpu.utils import checkpoint as ckpt

        yield
        ckpt.set_crash_barrier(None)
        ckpt._FAILED.clear()

    @pytest.mark.parametrize("mode", ["save", "save_async"])
    def test_both_barriers_once_each_in_order(self, tmp_path, mode):
        from saturn_tpu.utils import checkpoint as ckpt

        path = str(tmp_path / "t.npz")
        seen = []

        def barrier(point, ctx):
            # at either crossing the new generation is not yet published
            seen.append((point, os.path.exists(ctx["tmp"]),
                         os.path.exists(path)))

        hook = lambda stem, p: seen.append(("published", True,
                                            os.path.exists(p)))
        ckpt.set_crash_barrier(barrier)
        ckpt.add_publish_hook(hook)
        try:
            getattr(ckpt, mode)(path, {"a": np.arange(6.0), "b": np.ones(3)})
            ckpt.flush()
        finally:
            ckpt.remove_publish_hook(hook)
        assert seen == [("mid-shard-write", True, False),
                        ("pre-manifest-rename", True, False),
                        ("published", True, True)]
        assert ckpt.verify(path)

    @pytest.mark.parametrize("point",
                             ["mid-shard-write", "pre-manifest-rename"])
    def test_kill_in_the_async_writer_keeps_the_previous_generation(
            self, tmp_path, point):
        from saturn_tpu.utils import checkpoint as ckpt

        path = str(tmp_path / "t.npz")
        ckpt.save(path, {"a": np.arange(6.0)})
        before = ckpt.load_arrays(path)["a"].tobytes()
        inj = CrashInjector(point)
        ckpt.set_crash_barrier(inj.barrier)
        ckpt.save_async(path, {"a": np.arange(6.0) + 1})  # returns: parked
        with pytest.raises(RuntimeError) as ei:
            ckpt.flush()
        assert isinstance(ei.value.__cause__, SimulatedKill)
        ckpt.set_crash_barrier(None)
        assert ckpt.verify(path)
        assert ckpt.load_arrays(path)["a"].tobytes() == before
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


# ---------------------------------------------------------- metrics satellite
class TestMetricsTornTail:
    def test_read_events_skips_and_warns_on_torn_line(self, tmp_path, caplog):
        from saturn_tpu.utils.metrics import read_events

        p = str(tmp_path / "m.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "solve"}) + "\n")
            f.write('{"ts": 2.0, "kind": "inter')  # crashed writer's tail
        with caplog.at_level("WARNING", logger="saturn_tpu"):
            evs = read_events(p)
        assert [e["kind"] for e in evs] == ["solve"]
        assert any("torn" in r.message for r in caplog.records)


# --------------------------------------------------------- evaluator satellite
class TestTrialRetry:
    class FlakyTech(BaseTechnique):
        name = "crash-flaky"
        failures_left = 0

        def execute(self, task, devices, tid, override_batch_count=None):
            pass

        def search(self, task, devices, tid):
            cls = type(self)
            if cls.failures_left > 0:
                cls.failures_left -= 1
                raise RuntimeError("transient flake")
            return {}, 0.001

    def _sweep(self, tmp_path, retries):
        from saturn_tpu import library
        from saturn_tpu.trial_runner import evaluator
        from saturn_tpu.utils.metrics import read_events

        mpath = str(tmp_path / "m.jsonl")
        task = FakeTask("flaky", 10, [], None)
        task.strategies = {}
        task.chip_range = (2,)
        library.register("crash-flaky", self.FlakyTech)
        try:
            evaluator.search(
                [task], technique_names=["crash-flaky"], topology=topo(8),
                metrics_path=mpath, profile_cache=False,
                trial_retries=retries, retry_backoff_s=0.001,
            )
        finally:
            library.deregister("crash-flaky")
        return task, read_events(mpath)

    def test_transient_flake_retried_to_success(self, tmp_path):
        self.FlakyTech.failures_left = 2
        task, evs = self._sweep(tmp_path, retries=2)
        assert task.feasible_strategies()  # third attempt succeeded
        retriesv = [e for e in evs if e["kind"] == "trial_retry"]
        assert len(retriesv) == 2
        assert [e["attempt"] for e in retriesv] == [1, 2]
        # exponential backoff: attempt 2's delay window starts above 1's base
        assert retriesv[1]["backoff_s"] > retriesv[0]["backoff_s"]

    def test_budget_exhaustion_is_infeasible_not_fatal(self, tmp_path):
        self.FlakyTech.failures_left = 99
        task, evs = self._sweep(tmp_path, retries=1)
        assert not task.feasible_strategies()  # recorded infeasible
        assert len([e for e in evs if e["kind"] == "trial_retry"]) == 1
        trial = [e for e in evs if e["kind"] == "trial"]
        assert trial and trial[-1]["feasible"] is False


# ------------------------------------------------------------- batch resume
class TestOrchestrateResume:
    def test_resume_runs_only_undurable_batches(self, tmp_path):
        from saturn_tpu import orchestrate

        d = str(tmp_path / "wal")
        # A prior incarnation durably recorded: 30 of a's 50 batches ran,
        # and b completed outright.
        j = Journal(d)
        j.append("task_progress", task="a", batches=30)
        j.append("task_progress", task="b", batches=40)
        j.append("task_completed", task="b")
        j.commit()
        j.close()

        tech = RecordingTech()
        a = FakeTask("a", 50, [2, 4], tech)
        b = FakeTask("b", 40, [2, 4], tech)
        out = orchestrate([a, b], interval=0.2, topology=topo(8),
                          resume_dir=d)
        assert sorted(out["completed"]) == ["a", "b"]
        # b never re-executed; a ran exactly its un-journaled remainder
        ran = {}
        for name, n in tech.calls:
            ran[name] = ran.get(name, 0) + n
        assert "b" not in ran
        assert ran["a"] == 20

        # the journal now accounts for every iteration exactly once
        state = replay_batch_state(d)
        assert state.progress == {"a": 50, "b": 40}
        assert sorted(state.completed) == ["a", "b"]
        replay(d, strict=True)  # seq chain intact across incarnations

    def test_resume_is_idempotent_when_everything_done(self, tmp_path):
        from saturn_tpu import orchestrate

        d = str(tmp_path / "wal")
        tech = RecordingTech()
        out1 = orchestrate([FakeTask("x", 30, [2], tech)], interval=0.2,
                           topology=topo(8), resume_dir=d)
        assert out1["completed"] == ["x"]
        n_calls = len(tech.calls)
        # same batch re-launched after "crash-after-finish": nothing re-runs
        out2 = orchestrate([FakeTask("x", 30, [2], tech)], interval=0.2,
                           topology=topo(8), resume_dir=d)
        assert out2["completed"] == ["x"]
        assert len(tech.calls) == n_calls


# --------------------------------------------------------------- acceptance
class TestKillReplayAcceptance:
    # Sized against the forecast's ceiling of interval / per_batch = 50
    # batches a job an interval (a slower host runs fewer, never more), so
    # that every kill below finds work left whatever the host's speed: at
    # most 50 a job are durable after the first kill (its second interval
    # is lost), so at least 60 remain and the second incarnation needs two
    # intervals, of which the second cannot become durable past a kill at
    # its second fsync; so the third still has an interval to run, whose
    # end is the post-checkpoint crossing.
    TOTALS = {"job-a": 140, "job-b": 140, "job-c": 110, "job-d": 110}
    PRIORITIES = {"job-a": 0.0, "job-b": 1.0, "job-c": 2.0, "job-d": 3.0}

    def _provider(self, tech):
        def provide(spec):
            # remaining_batches is the journal-authoritative budget: durably
            # completed iterations are never re-run
            return FakeTask(spec["task"], spec["remaining_batches"],
                            spec["spec"]["sizes"], tech, pbt=0.004)

        return provide

    def _service(self, wal, tech, barrier=None):
        from saturn_tpu.service import SaturnService

        return SaturnService(
            topology=topo(8), interval=0.2, poll_s=0.02,
            durability_dir=wal, task_provider=self._provider(tech),
            crash_barrier=barrier,
        )

    @staticmethod
    def _kill(inj, svc, tech):
        """Arm the injector, and only then let batches run: the crossings
        that count all come after this call, on a loaded host too."""
        inj.arm()
        tech.gate.set()
        run_to_kill(inj, svc)
        assert svc.killed
        tech.gate.clear()

    def test_kill_replay_no_lost_jobs_no_rerun_iterations(self, tmp_path):
        from saturn_tpu.service import ServiceClient

        wal = str(tmp_path / "wal")
        tech = RecordingTech(per_batch=0.004)
        tech.gate.clear()

        # ---- incarnation 1: submit 4 mixed-priority jobs, kill mid-interval
        inj = CrashInjector("mid-interval", hit=2, armed=False)
        svc = self._service(wal, tech, inj.barrier)
        svc.start()
        client = ServiceClient(svc)
        ids = {}
        for name, total in self.TOTALS.items():
            ids[name] = client.submit(
                FakeTask(name, total, [2], tech, pbt=0.004),
                priority=self.PRIORITIES[name],
                spec={"sizes": [2]},
            )
        self._kill(inj, svc, tech)

        # ---- incarnation 2: recover, kill mid-fsync (tears the journal)
        inj2 = CrashInjector("mid-fsync", hit=2, armed=False)
        svc2 = self._service(wal, tech, inj2.barrier)
        svc2.start()
        self._kill(inj2, svc2, tech)

        # the torn tail is quarantined on the NEXT open, not fatal
        # ---- incarnation 3: recover, kill post-checkpoint (hit 1: what is
        # left may fit one interval)
        inj3 = CrashInjector("post-checkpoint", hit=1, armed=False)
        svc3 = self._service(wal, tech, inj3.barrier)
        assert svc3.journal.recovery_report["quarantined"], (
            "mid-fsync tear must leave a quarantined sidecar"
        )
        svc3.start()
        self._kill(inj3, svc3, tech)

        # ---- final incarnation: no injector, run everything to completion
        tech.gate.set()
        svc4 = self._service(wal, tech)
        svc4.start()
        client4 = ServiceClient(svc4)
        try:
            outs = {n: client4.wait(j, timeout=120) for n, j in ids.items()}
        finally:
            svc4.stop(timeout=60)

        # 1. zero admitted jobs lost: every original job id reaches DONE
        #    under the SAME id it was submitted with
        assert all(o["state"] == "DONE" for o in outs.values()), outs
        assert {o["job_id"] for o in outs.values()} == set(ids.values())

        # 2. journal integrity survives three kills: strict replay verifies
        #    every CRC and that seq is strictly monotonic, gap-free, across
        #    all four incarnations
        recs = replay(wal, strict=True)
        seqs = [r["seq"] for r in recs]
        assert seqs == sorted(set(seqs))

        # 3. zero durably completed iterations re-run: per job, journaled
        #    realized batches sum to EXACTLY the submitted budget — never
        #    more (a double-count would re-run or over-count work)
        progress = {}
        for r in recs:
            if r["kind"] == "task_progress":
                progress[r["data"]["task"]] = (
                    progress.get(r["data"]["task"], 0) + r["data"]["batches"]
                )
        assert progress == self.TOTALS, progress

        # 4. the crashes actually cost something and recovery re-admitted:
        #    at least one incarnation resurrected live jobs
        assert any(r["kind"] == "job_recovered" for r in recs)
        recoveries = [r for r in recs if r["kind"] == "recovery"]
        assert len(recoveries) == 4  # one per incarnation
        assert [r["data"]["incarnation"] for r in recoveries] == [1, 2, 3, 4]

        # 5. corrupt trailing artifacts were quarantined, not fatal
        assert any(n.endswith(".corrupt") or ".corrupt." in n
                   for n in os.listdir(wal))

        # 6. every job's terminal DONE verdict is journaled
        done = {r["data"]["job"] for r in recs
                if r["kind"] == "job_state" and r["data"]["state"] == "DONE"}
        assert done == set(ids.values())

    def test_recovery_without_provider_refuses_to_drop_jobs(self, tmp_path):
        from saturn_tpu.service import ServiceClient

        wal = str(tmp_path / "wal")
        tech = RecordingTech()
        inj = CrashInjector("mid-interval", hit=1, armed=False)
        svc = self._service(wal, tech, inj.barrier)
        svc.start()
        ServiceClient(svc).submit(FakeTask("orphan", 200, [2], tech),
                                  spec={"sizes": [2]})
        run_to_kill(inj, svc)
        from saturn_tpu.service import SaturnService

        with pytest.raises(RuntimeError, match="task_provider"):
            SaturnService(topology=topo(8), durability_dir=wal)

    def test_restore_records_rebuild_remaining_budget(self, tmp_path):
        """Unit-level recovery check: journal says 25 of 60 batches are
        durable -> the restored record re-enters QUEUED with 35 remaining."""
        wal = str(tmp_path / "wal")
        j = Journal(wal)
        j.append("job_submitted", job="j0001-t", task="t", priority=1.0,
                 max_retries=1, total_batches=60, spec={"sizes": [2]})
        j.append("job_state", job="j0001-t", state="PROFILING")
        j.append("job_state", job="j0001-t", state="SCHEDULED")
        j.append("job_state", job="j0001-t", state="RUNNING")
        j.append("task_progress", task="t", job="j0001-t", batches=25)
        j.commit()
        j.close()

        state = replay_service_state(wal)
        assert state.jobs["j0001-t"].realized == 25
        assert state.jobs["j0001-t"].remaining == 35

        tech = RecordingTech()
        recs = build_restore_records(state, self._provider_check(tech))
        (rec,) = recs
        assert rec.job_id == "j0001-t"
        assert rec.state.value == "QUEUED"
        assert rec.requeues == 1  # was RUNNING: counts as a requeue
        assert rec.task.total_batches == 35

    def _provider_check(self, tech):
        def provide(spec):
            assert spec["total_batches"] == 60
            assert spec["remaining_batches"] == 35
            return FakeTask(spec["task"], spec["remaining_batches"],
                            spec["spec"]["sizes"], tech)

        return provide
