"""Round-19 sharded checkpoint format: manifest structure, zero-gather
save, cross-technique restore, the legacy-npz compat reader, crash
kill-points at the two commit edges, async keep-first error retention,
per-interval MFU telemetry, and the ``analysis ckpt`` CLI summary.

These complement ``test_ckpt_migration.py`` (cross-mesh resharding) by
pinning the FORMAT itself: what is on disk, what survives a torn write,
and what the consumers observe.
"""

import json
import os
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from saturn_tpu.utils import checkpoint as ckpt
from saturn_tpu.utils import metrics

pytestmark = pytest.mark.resilience


def mesh_of(n, axes=("dp",)):
    devs = np.array(jax.devices()[: int(np.prod([n]))])
    return Mesh(devs.reshape(n), axes)


def make_state(mesh):
    """Train-state-shaped tree: 2-d param, 1-d bias, 0-d step counter."""
    sh = NamedSharding(mesh, P("dp"))
    rep = NamedSharding(mesh, P())
    return {
        "params": {
            "w": jax.device_put(
                jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4), sh
            ),
            "b": jax.device_put(jnp.linspace(-1.0, 1.0, 8), sh),
        },
        "step": jax.device_put(jnp.asarray(7, dtype=jnp.int32), rep),
    }


def host_tree(tree):
    return jax.tree_util.tree_map(
        lambda l: np.asarray(jax.device_get(l)), tree
    )


@pytest.fixture(autouse=True)
def _no_leaked_crash_barrier():
    yield
    ckpt.set_crash_barrier(None)


class TestManifestFormat:
    def test_manifest_and_shard_layout(self, tmp_path, devices8):
        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)

        # logical path holds the JSON manifest, not a zip archive
        with open(path, "rb") as f:
            assert f.read(1) == b"{"
        with open(path) as f:
            man = json.load(f)
        assert man["format"] == ckpt.MANIFEST_FORMAT
        assert man["version"] == ckpt.MANIFEST_VERSION
        assert man["pspec_fingerprint"]
        assert set(man["leaves"]) == {"params/w", "params/b", "step"}
        w = man["leaves"]["params/w"]
        assert w["shape"] == [8, 4] and w["dtype"] == "float32"
        # a sharded leaf's shard table covers the full extent
        rows = sum(s["index"][0][1] - s["index"][0][0] for s in w["shards"])
        assert rows == 8
        # shard files sit next to the manifest and match the naming scheme
        shard_files = [
            n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)
        ]
        assert shard_files, "no shard files written"
        for n in shard_files:
            assert n.startswith("t.npz.g")
            assert zipfile.is_zipfile(tmp_path / n)
        assert ckpt.verify(path)

    def test_cross_technique_chain_bit_identical(self, tmp_path, devices8):
        """dp -> fsdp-style resharded save -> tp-style columns: the bytes
        survive two migrations (per-leaf tobytes, the ISSUE acceptance)."""
        path = str(tmp_path / "t.npz")
        dp = make_state(mesh_of(4))
        want = host_tree(dp)
        ckpt.save(path, dp)

        # fsdp-style: shard over all 8 devices
        def fsdp_rule(p, sds):
            m = mesh_of(8)
            if sds.ndim and sds.shape[0] % 8 == 0:
                return NamedSharding(m, P("dp"))
            return NamedSharding(m, P())

        fsdp = ckpt.restore_sharded(path, dp, fsdp_rule)
        ckpt.save(path, fsdp)

        # tp-style: split the trailing axis instead
        def tp_rule(p, sds):
            m = Mesh(np.array(jax.devices()[:4]), ("tp",))
            if sds.ndim == 2 and sds.shape[1] % 4 == 0:
                return NamedSharding(m, P(None, "tp"))
            return NamedSharding(m, P())

        tp = ckpt.restore_sharded(path, dp, tp_rule)
        got = host_tree(tp)
        for key in ("params/w", "params/b", "step"):
            a = want["params"][key.split("/")[1]] if "/" in key else want[key]
            b = got["params"][key.split("/")[1]] if "/" in key else got[key]
            assert a.tobytes() == b.tobytes(), key

    def test_resave_garbage_collects_old_generation(self, tmp_path, devices8):
        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)
        gen1 = {n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)}
        ckpt.save(path, state)
        gen2 = {n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)}
        assert gen1.isdisjoint(gen2), "stale generation not collected"
        assert ckpt.verify(path)

    def test_tampered_manifest_quarantined(self, tmp_path, devices8):
        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)
        with open(path) as f:
            man = json.load(f)
        man["leaves"]["step"]["shape"] = [3]  # checksum now stale
        with open(path, "w") as f:
            json.dump(man, f)
        assert not ckpt.verify(path)
        with pytest.raises(ckpt.CheckpointCorruptError):
            ckpt.load_arrays(path)
        assert os.path.exists(path + ".corrupt")

    def test_missing_shard_file_quarantined(self, tmp_path, devices8):
        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)
        victim = next(
            n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)
        )
        os.unlink(tmp_path / victim)
        assert not ckpt.verify(path)
        with pytest.raises(ckpt.CheckpointCorruptError):
            ckpt.load_arrays(path)


class TestCompatReader:
    def test_legacy_single_file_restores(self, tmp_path, devices8):
        """Checkpoints written by the pre-round-19 allgather writer (one
        npz of full host arrays) must keep restoring."""
        path = str(tmp_path / "old.npz")
        arrays = {
            "params/w": np.arange(32, dtype=np.float32).reshape(8, 4),
            "step": np.asarray(5, dtype=np.int32),
        }
        with open(path, "wb") as f:
            np.savez(f, **arrays)

        loaded = ckpt.load_arrays(path)
        assert loaded["params/w"].tobytes() == arrays["params/w"].tobytes()

        template = {
            "params": {"w": jnp.zeros((8, 4), jnp.float32)},
            "step": jnp.asarray(0, jnp.int32),
        }
        out = ckpt.restore(path, template)
        assert int(out["step"]) == 5

        sh = NamedSharding(mesh_of(4), P())
        placed = ckpt.restore_sharded(path, template, sh)
        got = host_tree(placed)
        assert got["params"]["w"].tobytes() == arrays["params/w"].tobytes()


class TestAsyncErrorRetention:
    def test_keep_first_error_per_path(self, tmp_path, caplog):
        key = os.path.abspath(str(tmp_path / "x.npz"))
        first = RuntimeError("disk full")
        second = RuntimeError("later noise")
        ckpt._record_async_failure(key, key, first)
        with caplog.at_level("WARNING", logger="saturn_tpu.utils.checkpoint"):
            ckpt._record_async_failure(key, key, second)
        assert any("keeping first error" in r.getMessage()
                   for r in caplog.records)
        with pytest.raises(RuntimeError) as ei:
            ckpt.flush()
        assert ei.value.__cause__ is first

    def test_failed_async_write_surfaces_at_flush(self, tmp_path, devices8):
        state = make_state(mesh_of(2))
        # the "parent dir" is a regular file: the background commit's
        # makedirs fails deterministically (snapshot itself touches no disk)
        (tmp_path / "nodir").write_bytes(b"")
        target = str(tmp_path / "nodir" / "t.npz")
        ckpt.save_async(target, state)
        with pytest.raises(RuntimeError, match="async checkpoint write"):
            ckpt.flush()
        ckpt.flush()  # error consumed: the next flush is clean


@pytest.mark.crash
class TestCrashKillPoints:
    def _save_gen(self, path, mesh, fill):
        sh = NamedSharding(mesh, P("dp"))
        state = {"w": jax.device_put(
            jnp.full((8, 4), fill, jnp.float32), sh)}
        ckpt.save(path, state)
        return state

    def test_mid_shard_write_keeps_previous_generation(
            self, tmp_path, devices8):
        from saturn_tpu.resilience.crash import CrashInjector, SimulatedKill

        path = str(tmp_path / "t.npz")
        self._save_gen(path, mesh_of(4), 1.0)
        before = ckpt.load_arrays(path)["w"].tobytes()

        inj = CrashInjector("mid-shard-write")
        ckpt.set_crash_barrier(inj.barrier)
        with pytest.raises(SimulatedKill):
            self._save_gen(path, mesh_of(4), 2.0)
        ckpt.set_crash_barrier(None)

        # previous manifest + shard generation untouched and valid
        assert ckpt.verify(path)
        assert ckpt.load_arrays(path)["w"].tobytes() == before
        # no tmp litter from the torn write
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]

    def test_pre_manifest_rename_keeps_previous_manifest(
            self, tmp_path, devices8):
        from saturn_tpu.resilience.crash import CrashInjector, SimulatedKill

        path = str(tmp_path / "t.npz")
        self._save_gen(path, mesh_of(4), 1.0)
        before = ckpt.load_arrays(path)["w"].tobytes()

        # new-generation shard files may already be durable; the manifest
        # rename is THE commit point, so the old state must still win
        inj = CrashInjector("pre-manifest-rename")
        ckpt.set_crash_barrier(inj.barrier)
        with pytest.raises(SimulatedKill):
            self._save_gen(path, mesh_of(4), 2.0)
        ckpt.set_crash_barrier(None)

        assert ckpt.verify(path)
        assert ckpt.load_arrays(path)["w"].tobytes() == before

    def test_torn_shard_set_reconciles_to_previous_publication(
            self, tmp_path, devices8):
        """recovery.reconcile_checkpoints quarantines a manifest whose
        shard set is torn and falls back to the previous durable one —
        the zero-lost-jobs acceptance from the ISSUE."""
        from saturn_tpu.durability.recovery import reconcile_checkpoints

        old = str(tmp_path / "a" / "t.npz")
        new = str(tmp_path / "b" / "t.npz")
        os.makedirs(os.path.dirname(old))
        os.makedirs(os.path.dirname(new))
        self._save_gen(old, mesh_of(4), 1.0)
        self._save_gen(new, mesh_of(4), 2.0)
        # tear the newer publication: delete its shard file(s)
        for n in os.listdir(tmp_path / "b"):
            if ckpt._SHARD_RE.search(n):
                os.unlink(tmp_path / "b" / n)

        out = reconcile_checkpoints({"job": [old, new]})
        assert out == {"job": old}
        assert os.path.exists(new + ".corrupt")


class TestMfuTelemetry:
    def test_task_interval_reports_tflops_and_mfu(
            self, tiny_task, devices8, tmp_path):
        from saturn_tpu.core.strategy import Strategy
        from saturn_tpu.parallel.dp import DataParallel

        mpath = str(tmp_path / "metrics.jsonl")
        with metrics.scoped(mpath):
            tech = DataParallel()
            params, t = tech.search(tiny_task, devices8[:1], tid=0)
            tiny_task.strategies[1] = Strategy(tech, 1, params, 100.0, t)
            tiny_task.select_strategy(1)
            tech.execute(tiny_task, devices8[:1], tid=0,
                         override_batch_count=2)
        evs = [e for e in metrics.read_events(mpath)
               if e["kind"] == "task_interval"]
        assert evs, "no task_interval events emitted"
        for e in evs:
            assert e["tflops"] > 0
            # the host CPU has no published peak: no MFU is made up for it
            assert "mfu" not in e, e
            assert e["devices"] == [devices8[0].id]
            assert len(e["losses"]) == e["batches"]
            assert e["losses"][-1] == pytest.approx(e["loss"])

    def test_peaks_table_is_keyed_by_device_kind(self):
        from saturn_tpu.utils.peaks import peak_flops

        class Dev:
            device_kind = "TPU v5 lite"

        assert peak_flops(Dev()) == 197e12
        Dev.device_kind = "TPU v9 imaginary"
        with pytest.raises(KeyError, match="no published peak"):
            peak_flops(Dev())


class TestCkptCli:
    def test_ckpt_summary_json(self, tmp_path, devices8, capsys):
        from saturn_tpu.analysis.cli import main

        state = make_state(mesh_of(4))
        ckpt.save(str(tmp_path / "t.npz"), state)
        rc = main(["--json", "ckpt", str(tmp_path)])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert len(out["checkpoints"]) == 1
        row = out["checkpoints"][0]
        assert row["ok"] and row["format"] == "sharded-manifest"
        assert row["leaves"] == 3
        assert out["orphan_shards"] == []

    def test_ckpt_flags_corrupt_dir(self, tmp_path, devices8, capsys):
        from saturn_tpu.analysis.cli import main

        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)
        for n in os.listdir(tmp_path):
            if ckpt._SHARD_RE.search(n):
                os.unlink(tmp_path / n)
        rc = main(["--json", "ckpt", str(tmp_path)])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert not out["checkpoints"][0]["ok"]
        # every shard file is gone but none were orphaned (they belonged
        # to the manifest); a stray unreferenced shard IS flagged
        (tmp_path / "t.npz.gdeadbeef.r9.npz").write_bytes(b"PK\x03\x04")
        main(["--json", "ckpt", str(tmp_path)])
        out2 = json.loads(capsys.readouterr().out)
        assert out2["orphan_shards"]
