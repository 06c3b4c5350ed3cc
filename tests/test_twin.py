"""saturn-twin (round 22): the discrete-event fleet simulator that runs the
REAL control plane — gateway, admission, anytime solver, pressure shed,
elastic replan — against virtual slices on a virtual clock.

The tentpole claims under test:

- **Determinism**: same seed + config (+ trace) ⇒ bit-identical
  ``events.jsonl`` and final verdict ledger across repeated runs — including
  a seeded TopologyChange-storm campaign (preemptions, crashes, stragglers).
- **Replayability**: twin journals are real write-ahead journals; a
  campaign's own journal replays through the twin and lands within the
  documented fidelity band (``trace.DEFAULT_BAND``).
- **Reconciled replay**: ``journal.replay_reconciled`` merges overlapping
  writer incarnations in stable ``(seq, incarnation)`` order where strict
  replay would silently drop the later incarnation.
- **Operator surface**: ``python -m saturn_tpu.analysis twin`` reports
  makespan / tier shares / admission mix / shed counts / fidelity deltas,
  and can run synth, storm, replay and capacity-what-if campaigns itself.

Solver budgets in these tests are deliberately generous (30 real seconds):
the anytime ladder races ``time.perf_counter`` — which the twin leaves
unpatched on purpose — so bit-identity is only guaranteed when every
attempted tier finishes inside its budget on any host.
"""

import json
import os
import time
import timeit
import zlib

import pytest

from saturn_tpu.durability import journal as jmod
from saturn_tpu.twin.arrivals import BURST_EVERY, BURST_LEN, arrival_stream
from saturn_tpu.twin.clock import EventQueue, VirtualClock
from saturn_tpu.twin.runner import CampaignConfig, run_campaign, run_what_if
from saturn_tpu.twin.trace import DEFAULT_BAND, fidelity_compare, load_trace

pytestmark = pytest.mark.twin


@pytest.fixture(autouse=True)
def _small_partitions(monkeypatch):
    # Pin the tier-1 partition width (a documented operator knob) so every
    # MILP instance the campaigns generate proves optimality in milliseconds.
    # A MILP that instead hits its HiGHS time_limit returns a wall-clock-
    # dependent incumbent — on a loaded host that breaks the bit-identity
    # these tests assert (probed: the seed-3 storm's post-grow 24-task solve
    # grinds 48s uncapped at the default width, 1s at width 4).
    monkeypatch.setenv("SATURN_TPU_PARTITION_MAX", "4")

#: Generous real-clock solver budget: every tier the ladder attempts must
#: finish, so tier adoption (and with it the event log) cannot race.
SAFE_SOLVE_S = 30.0

#: The seeded storm campaign (probed: topology changes, transient crashes,
#: preemption requeues AND one retry-budget exhaustion all fire).
STORM_CFG = dict(
    n_jobs=24, n_slices=2, interval_s=12.0, total_batches=6,
    solve_deadline_s=SAFE_SOLVE_S, metrics=False, seed=3, storm=True,
    storm_p_preempt=0.6, storm_p_crash=0.5, storm_p_straggler=0.3,
    outage_intervals=1, max_intervals=80,
)


def _campaign_bytes(out_dir):
    """The determinism contract: the event log and the verdict ledger."""
    out = {}
    for fn in ("events.jsonl", "ledger.json"):
        with open(os.path.join(out_dir, fn), "rb") as fh:
            out[fn] = fh.read()
    return out


# --------------------------------------------------------------------------
# virtual clock + event queue
# --------------------------------------------------------------------------
class TestVirtualClock:
    def test_patch_swaps_and_restores_time_sources(self):
        real_time = time.time
        real_mono = time.monotonic
        with VirtualClock(start=100.0).patch() as clk:
            assert time.time() == 100.0
            assert time.monotonic() == 100.0
            assert timeit.default_timer() == 100.0
            time.sleep(5.5)  # advances instead of blocking
            assert time.time() == 105.5
            assert clk.now() == 105.5
        assert time.time is real_time
        assert time.monotonic is real_mono
        assert time.time() > 1e9  # actually back on the epoch clock

    def test_perf_counter_stays_real_under_patch(self):
        # The solver's deadline race must burn honest CPU time.
        with VirtualClock().patch():
            a = time.perf_counter()
            for _ in range(10_000):
                pass
            assert time.perf_counter() >= a
            assert time.perf_counter() != time.time()

    def test_advance_contract(self):
        clk = VirtualClock(start=10.0)
        with pytest.raises(ValueError):
            clk.advance(-1.0)
        assert clk.advance_to(5.0) == 10.0   # never goes backwards
        assert clk.advance_to(12.0) == 12.0
        clk.sleep(-3.0)                       # clamps like time.sleep
        assert clk.now() == 12.0

    def test_restores_on_exception(self):
        real_time = time.time
        with pytest.raises(RuntimeError):
            with VirtualClock().patch():
                raise RuntimeError("boom")
        assert time.time is real_time

    def test_event_queue_breaks_ties_by_insertion_order(self):
        q = EventQueue()
        q.push(2.0, "b")
        q.push(1.0, "tie-first")
        q.push(1.0, "tie-second")
        assert q.peek_time() == 1.0
        assert len(q) == 3 and not q.empty
        due = q.pop_due(1.0)
        assert [k for _, k, _ in due] == ["tie-first", "tie-second"]
        assert q.pop_due(5.0) == [(2.0, "b", None)]
        assert q.empty


# --------------------------------------------------------------------------
# arrivals (the one seeded generator: the twin's and the live run's below)
# --------------------------------------------------------------------------
class TestArrivals:
    def test_deterministic_across_calls(self):
        a = arrival_stream(200, base_rate_hz=12.0, burst_rate_hz=80.0, seed=7)
        b = arrival_stream(200, base_rate_hz=12.0, burst_rate_hz=80.0, seed=7)
        assert a == b
        assert a != arrival_stream(
            200, base_rate_hz=12.0, burst_rate_hz=80.0, seed=8
        )

    def test_diurnal_burst_shape(self):
        trace = arrival_stream(BURST_EVERY + 5, base_rate_hz=2.0,
                               burst_rate_hz=50.0, seed=1)
        assert all(t.in_burst for t in trace[:BURST_LEN])
        assert not any(t.in_burst for t in trace[BURST_LEN:BURST_EVERY])
        assert all(t.in_burst for t in trace[BURST_EVERY:])
        offsets = [t.at_s for t in trace]
        assert offsets == sorted(offsets)
        assert all(t.priority in (0.0, 1.0, 2.0) for t in trace)

    def test_rejects_nonsense(self):
        with pytest.raises(ValueError):
            arrival_stream(-1, base_rate_hz=1.0, burst_rate_hz=1.0)
        with pytest.raises(ValueError):
            arrival_stream(1, base_rate_hz=0.0, burst_rate_hz=1.0)
        with pytest.raises(ValueError):
            arrival_stream(1, base_rate_hz=1.0, burst_rate_hz=1.0,
                           burst_every=0)


# --------------------------------------------------------------------------
# reconciled journal replay (satellite: stable (seq, incarnation) merge)
# --------------------------------------------------------------------------
def _write_segment(root, index, records):
    """Hand-build a CRC-valid journal segment: records = [(seq, data)]."""
    lines = []
    for seq, data in records:
        body = {"seq": seq, "ts": float(seq), "kind": "job_state",
                "data": data}
        crc = format(
            zlib.crc32(json.dumps(
                body, sort_keys=True, separators=(",", ":"), default=str
            ).encode("utf-8")), "08x")
        body["crc"] = crc
        lines.append(json.dumps(body, sort_keys=True,
                                separators=(",", ":"), default=str))
    path = os.path.join(root, f"wal-{index:06d}.jsonl")
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


class TestReplayReconciled:
    def test_overlapping_incarnations_merge_latest_wins(self, tmp_path):
        root = str(tmp_path)
        # Incarnation 0: seqs 1..6 over two contiguous segments.
        _write_segment(root, 0, [(s, {"inc": 0, "seq": s}) for s in (1, 2, 3)])
        _write_segment(root, 1, [(s, {"inc": 0, "seq": s}) for s in (4, 5, 6)])
        # Incarnation 1 restarted from an OLDER durable cut: its segment
        # re-uses seqs 4..6, then extends the history to 8.
        _write_segment(root, 2,
                       [(s, {"inc": 1, "seq": s}) for s in (4, 5, 6, 7, 8)])

        # Strict single-history replay stops at the discontinuity: the
        # entire later incarnation (including the 7..8 tail) is dropped.
        strict = jmod.replay(root)
        assert [r["seq"] for r in strict] == [1, 2, 3, 4, 5, 6]
        assert all(r["data"]["inc"] == 0 for r in strict)

        # Reconciled replay keeps the union, later incarnation winning
        # where the sequence ranges overlap.
        merged = jmod.replay_reconciled(root)
        assert [r["seq"] for r in merged] == [1, 2, 3, 4, 5, 6, 7, 8]
        by_seq = {r["seq"]: r["data"]["inc"] for r in merged}
        assert by_seq == {1: 0, 2: 0, 3: 0, 4: 1, 5: 1, 6: 1, 7: 1, 8: 1}

    def test_single_incarnation_matches_strict_replay(self, tmp_path):
        root = str(tmp_path)
        _write_segment(root, 0, [(s, {"inc": 0}) for s in (1, 2)])
        _write_segment(root, 1, [(s, {"inc": 0}) for s in (3, 4)])
        assert jmod.replay_reconciled(root) == jmod.replay(root)

    def test_corrupt_record_is_skipped_not_fatal(self, tmp_path):
        root = str(tmp_path)
        _write_segment(root, 0, [(s, {"inc": 0}) for s in (1, 2, 3)])
        with open(os.path.join(root, "wal-000000.jsonl"), "a") as fh:
            fh.write("{torn garbage\n")
        merged = jmod.replay_reconciled(root)
        assert [r["seq"] for r in merged] == [1, 2, 3]


# --------------------------------------------------------------------------
# tentpole: campaign determinism (bit-identical event log + ledger)
# --------------------------------------------------------------------------
class TestCampaignDeterminism:
    def _run_n(self, cfg, tmp_path, n=3):
        outs = []
        for i in range(n):
            d = str(tmp_path / f"run{i}")
            summary = run_campaign(cfg, d)
            outs.append((summary, _campaign_bytes(d)))
        return outs

    def test_synth_campaign_bit_identical_across_3_runs(self, tmp_path):
        cfg = CampaignConfig(n_jobs=30, n_slices=2, interval_s=60.0,
                             solve_deadline_s=SAFE_SOLVE_S, metrics=False,
                             seed=11)
        outs = self._run_n(cfg, tmp_path)
        blobs = [b for _, b in outs]
        assert blobs[0]["events.jsonl"]  # non-trivial log
        assert blobs[0] == blobs[1] == blobs[2]
        summary = outs[0][0]
        assert summary["status"] == "ok"
        assert summary["completed"] == 30
        assert summary["deadline_misses"] == 0
        # The ledger is the deterministic side; wall_s lives only in the
        # summary and is the one intentionally non-deterministic field.
        ledger = json.loads(blobs[0]["ledger.json"])
        assert "wall_s" not in ledger

    def test_storm_campaign_bit_identical_and_chaotic(self, tmp_path):
        cfg = CampaignConfig(**STORM_CFG)
        outs = self._run_n(cfg, tmp_path)
        blobs = [b for _, b in outs]
        assert blobs[0] == blobs[1] == blobs[2]
        summary = outs[0][0]
        assert summary["status"] == "ok"
        assert summary["deadline_misses"] == 0
        # The storm actually stormed — and the control plane rode it out.
        assert summary["topology_changes"] >= 2
        assert summary["preemption_requeues"] > 0
        assert summary["crashes"] > 0
        assert summary["completed"] + summary["failed"] == cfg.n_jobs
        kinds = {json.loads(line)["kind"]
                 for line in blobs[0]["events.jsonl"].decode().splitlines()}
        assert {"topology_change", "task_preempted", "solve",
                "job_completed"} <= kinds

    def test_different_seed_diverges(self, tmp_path):
        base = dict(n_jobs=16, n_slices=2, interval_s=60.0,
                    solve_deadline_s=SAFE_SOLVE_S, metrics=False)
        a = str(tmp_path / "a")
        b = str(tmp_path / "b")
        run_campaign(CampaignConfig(seed=1, **base), a)
        run_campaign(CampaignConfig(seed=2, **base), b)
        assert (_campaign_bytes(a)["events.jsonl"]
                != _campaign_bytes(b)["events.jsonl"])

    def test_dedup_retry_storm_collapses_idempotently(self, tmp_path):
        cfg = CampaignConfig(n_jobs=25, n_slices=2, interval_s=60.0,
                             solve_deadline_s=SAFE_SOLVE_S, metrics=False,
                             seed=5, dedup_every=5)
        summary = run_campaign(cfg, str(tmp_path / "dedup"))
        # Every 5th arrival resubmits its predecessor's idempotency key and
        # must collapse through the real gateway dedup table.
        assert summary["duplicates"] == (cfg.n_jobs - 1) // cfg.dedup_every
        assert summary["submitted"] == cfg.n_jobs - summary["duplicates"]
        assert summary["completed"] == summary["submitted"]


# --------------------------------------------------------------------------
# fidelity: twin journals are replayable traces; replays land in band
# --------------------------------------------------------------------------
class TestReplayFidelity:
    def test_campaign_journal_replays_within_band(self, tmp_path):
        cfg = CampaignConfig(n_jobs=20, n_slices=2, interval_s=30.0,
                             solve_deadline_s=SAFE_SOLVE_S, metrics=False,
                             seed=9)
        a_dir = str(tmp_path / "original")
        a = run_campaign(cfg, a_dir)
        journal_dir = os.path.join(a_dir, "journal")

        trace = load_trace(journal_dir)
        assert len(trace.jobs) == a["submitted"]
        assert set(trace.admission_mix) <= {"admit", "defer", "reject"}
        offsets = [j.at_s for j in trace.jobs]
        assert offsets == sorted(offsets) and offsets[0] == 0.0

        b_cfg = CampaignConfig(trace_dir=journal_dir, n_slices=2,
                               interval_s=30.0,
                               solve_deadline_s=SAFE_SOLVE_S,
                               metrics=False, seed=9)
        b = run_campaign(b_cfg, str(tmp_path / "replay"))
        assert b["status"] == "ok"
        assert b["completed"] == a["completed"]
        cmp = fidelity_compare(
            {"tier_shares": b["tier_shares"],
             "verdict_shares": b["verdict_shares"],
             "makespan_s": b["makespan_s"]},
            {"tier_shares": a["tier_shares"],
             "verdict_shares": a["verdict_shares"],
             "makespan_s": a["makespan_s"]},
        )
        assert cmp["within_band"], cmp

    def test_fidelity_compare_band_edges(self):
        flat = {"tier_shares": {"1": 1.0}, "verdict_shares": {"admit": 1.0},
                "makespan_s": 10.0}
        assert fidelity_compare(flat, dict(flat))["within_band"]
        # A tier distribution further than the band allows.
        drifted = dict(flat, tier_shares={"2": 1.0})
        out = fidelity_compare(drifted, flat)
        assert not out["within_band"]
        assert out["tier_share_deltas"] == {"1": 1.0, "2": 1.0}
        # Makespan ratio outside [0.3, 3.0].
        slow = dict(flat, makespan_s=10.0 * DEFAULT_BAND["makespan_ratio"][1]
                    * 1.5)
        assert not fidelity_compare(slow, flat)["within_band"]
        # Empty-on-both-sides compares equal.
        empty = {"tier_shares": {}, "verdict_shares": {}, "makespan_s": 0.0}
        assert fidelity_compare(empty, dict(empty))["within_band"]


# --------------------------------------------------------------------------
# capacity what-if: base vs +1 slice vs relaxed deadlines, same arrivals
# --------------------------------------------------------------------------
class TestWhatIf:
    def test_relaxing_deadlines_attributably_reduces_evictions(self, tmp_path):
        base = CampaignConfig(n_jobs=24, n_slices=2, interval_s=30.0,
                              deadline_s=35.0,
                              solve_deadline_s=SAFE_SOLVE_S,
                              metrics=False, seed=7)
        verdict = run_what_if(base, str(tmp_path))
        cmp = verdict["comparison"]
        assert set(cmp) == {"base", "add-slice", "relax-deadlines"}
        # Tight deadlines make the pressure projection shed under load;
        # doubling every deadline (same seed, same arrivals) must strictly
        # help, and the delta is attributable to the knob alone.
        assert cmp["base"]["evicted"] > 0
        assert (cmp["relax-deadlines"]["evicted"] < cmp["base"]["evicted"])
        assert (cmp["relax-deadlines"]["completed"]
                > cmp["base"]["completed"])
        assert os.path.exists(os.path.join(str(tmp_path), "whatif.json"))
        with open(os.path.join(str(tmp_path), "whatif.json")) as fh:
            assert json.load(fh)["comparison"] == cmp


# --------------------------------------------------------------------------
# operator surface: python -m saturn_tpu.analysis twin
# --------------------------------------------------------------------------
class TestTwinCLI:
    @pytest.fixture()
    def campaign_dir(self, tmp_path):
        d = str(tmp_path / "campaign")
        run_campaign(
            CampaignConfig(n_jobs=15, n_slices=2, interval_s=30.0,
                           solve_deadline_s=SAFE_SOLVE_S, metrics=False,
                           seed=13),
            d,
        )
        return d

    def test_inspect_human_and_json(self, campaign_dir, capsys):
        from saturn_tpu.analysis.cli import main

        assert main(["twin", campaign_dir]) == 0
        out = capsys.readouterr().out
        assert "twin campaign ok" in out
        assert "admission:" in out and "solver:" in out

        assert main(["--json", "twin", campaign_dir]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["status"] == "ok"
        assert payload["completed"] == 15
        assert payload["deadline_misses"] == 0
        assert payload["tier_counts"]

    def test_fidelity_deltas_against_own_journal(self, campaign_dir, capsys):
        from saturn_tpu.analysis.cli import main

        rc = main(["--json", "twin", campaign_dir,
                   "--trace", os.path.join(campaign_dir, "journal")])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        fid = payload["fidelity"]
        assert fid["within_band"] is True
        assert all(v <= DEFAULT_BAND["verdict_share_delta"]
                   for v in fid["verdict_share_deltas"].values())

    def test_run_synth_through_cli(self, tmp_path, capsys):
        from saturn_tpu.analysis.cli import main

        d = str(tmp_path / "via-cli")
        rc = main(["--json", "twin", d, "--run", "synth",
                   "--jobs", "12", "--slices", "2", "--interval", "30",
                   "--solve-deadline", str(SAFE_SOLVE_S)])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["submitted"] == 12
        for fn in ("events.jsonl", "ledger.json", "summary.json"):
            assert os.path.exists(os.path.join(d, fn))

    def test_run_storm_through_cli_is_deterministic(self, tmp_path, capsys):
        # The acceptance bar verbatim: a seeded preemption-storm campaign
        # run through the twin CLI produces deterministic journaled
        # verdicts — twice through the front door, identical bytes out.
        from saturn_tpu.analysis.cli import main

        dirs = [str(tmp_path / "s1"), str(tmp_path / "s2")]
        payloads = []
        for d in dirs:
            rc = main(["--json", "twin", d, "--run", "storm",
                       "--jobs", "10", "--slices", "2", "--interval", "30",
                       "--seed", "3",
                       "--solve-deadline", str(SAFE_SOLVE_S)])
            assert rc == 0
            payloads.append(json.loads(capsys.readouterr().out))
        assert payloads[0] == payloads[1]
        assert _campaign_bytes(dirs[0]) == _campaign_bytes(dirs[1])
        assert os.path.isdir(os.path.join(dirs[0], "journal"))

    def test_run_whatif_through_cli(self, tmp_path, capsys):
        from saturn_tpu.analysis.cli import main

        d = str(tmp_path / "whatif-cli")
        rc = main(["--json", "twin", d, "--run", "whatif",
                   "--jobs", "12", "--slices", "2", "--interval", "30",
                   "--solve-deadline", str(SAFE_SOLVE_S)])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert set(payload["whatif"]) == {"base", "add-slice",
                                          "relax-deadlines"}
        # Re-inspecting the directory finds whatif.json.
        assert main(["--json", "twin", d]) == 0
        again = json.loads(capsys.readouterr().out)
        assert again["whatif"] == payload["whatif"]

    def test_usage_errors(self, tmp_path, capsys):
        from saturn_tpu.analysis.cli import main

        assert main(["twin", str(tmp_path / "nope")]) == 2
        assert main(["twin", str(tmp_path / "r"), "--run", "replay"]) == 2
        err = capsys.readouterr().err
        assert "requires --trace" in err


# --------------------------------------------------------------------------
# the real-service fidelity regression (sockets + threads: slow tier)
# --------------------------------------------------------------------------
#: The live run's shape, which the twin's replay must mirror exactly: the
#: same 8-chip mesh, 0.2 s interval (solve deadline = interval / 2), gateway
#: window and pre-profiled flat per-batch cost. The window is small on
#: purpose: the bursts overrun it, so the shed path is in the trace.
LIVE_JOBS = 500
LIVE_PER_BATCH_S = 0.004
LIVE_WINDOW = 12
LIVE_INTERVAL_S = 0.2
LIVE_SEED = 7


def _run_live_service(durability_dir, metrics_path):
    """A live ``SaturnService`` behind its gateway, driven over the seeded
    arrival trace (Poisson base rate, diurnal bursts); it leaves its journal
    in ``durability_dir``. The client does not retry: a shed is counted, not
    retried away. Returns (accepted, shed, makespan in seconds)."""
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.service import (
        GatewayClient, GatewayError, GatewayServer, SaturnService,
    )
    from saturn_tpu.service.gateway import protocol
    from tests.test_crash import FakeTask, RecordingTech

    # pre-profiled tasks (strategies filled): admission is the wire and the
    # queue, not a profiling sweep
    tech = RecordingTech(per_batch=LIVE_PER_BATCH_S)
    svc = SaturnService(
        topology=SliceTopology([object() for _ in range(8)]),
        interval=LIVE_INTERVAL_S, poll_s=0.02, health_guardian=False,
        task_provider=lambda p: FakeTask(
            p["task"], p["remaining_batches"], [4, 8], tech,
            pbt=LIVE_PER_BATCH_S),
        metrics_path=metrics_path, durability_dir=durability_dir,
    ).start()
    gw = GatewayServer(svc, max_inflight=LIVE_WINDOW,
                       max_inflight_per_session=16)
    gw.start()
    accepted, shed = [], 0
    t0 = time.monotonic()
    try:
        with GatewayClient(*gw.address, session="live", seed=LIVE_SEED,
                           timeout_s=30.0, max_attempts=1) as client:
            for arr in arrival_stream(LIVE_JOBS, base_rate_hz=12.0,
                                      burst_rate_hz=80.0, seed=LIVE_SEED):
                time.sleep(arr.gap_s)
                try:
                    accepted.append(client.submit(
                        name=f"online-{arr.index}", total_batches=2,
                        priority=arr.priority, spec={"sizes": [4, 8]}))
                except GatewayError as e:
                    if e.code not in (protocol.GW_RETRY_AFTER,
                                      protocol.GW_UNAVAILABLE):
                        raise
                    shed += 1
            for jid in accepted:
                assert client.wait(jid, timeout=300)["state"] == "DONE"
        makespan = time.monotonic() - t0
    finally:
        gw.shutdown(timeout=10, reason="test-complete")
        svc.stop(timeout=60)
    return len(accepted), shed, makespan


@pytest.mark.slow
class TestRealServiceFidelity:
    def test_gateway_bench_journal_replays_within_band(self, tmp_path):
        """The full calibrated-instrument check: a real SaturnService run
        (sockets, threads, real engine stub) journals its arrivals; the twin
        replays that journal; tier shares / verdict mix / makespan agree
        within ``DEFAULT_BAND``."""
        from saturn_tpu.twin.trace import tier_shares

        durability_dir = str(tmp_path / "real-journal")
        metrics_path = str(tmp_path / "real-metrics.jsonl")
        accepted, shed, makespan = _run_live_service(
            durability_dir, metrics_path)
        assert accepted + shed == LIVE_JOBS and accepted > 0
        real = {
            "tier_shares": tier_shares(metrics_path),
            "verdict_shares": load_trace(durability_dir).verdict_shares,
            "makespan_s": makespan,
        }
        twin = run_campaign(
            CampaignConfig(
                trace_dir=durability_dir, n_slices=1, chips_per_slice=8,
                interval_s=LIVE_INTERVAL_S,
                solve_deadline_s=LIVE_INTERVAL_S / 2,
                max_inflight=LIVE_WINDOW,
                flat_per_batch_s=LIVE_PER_BATCH_S, metrics=False,
                seed=LIVE_SEED,
            ),
            str(tmp_path / "twin-replay"),
        )
        cmp = fidelity_compare(
            {k: twin[k] for k in ("tier_shares", "verdict_shares",
                                  "makespan_s")},
            real,
        )
        assert cmp["within_band"], (cmp, real)
        assert twin["deadline_misses"] == 0
