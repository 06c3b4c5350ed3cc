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
import re
import threading
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


@pytest.fixture(params=["one_lane", "several_lanes"])
def lanes(request, monkeypatch):
    """PR 46: a save under ``_LANE_MIN_BYTES`` takes one lane and today's
    file name; the tests lower that private constant (no knob reads it) so
    that their small trees take ``_LANES`` lanes, a shard file each."""
    if request.param == "several_lanes":
        monkeypatch.setattr(ckpt, "_LANE_MIN_BYTES", 1)
    return request.param


def n_lanes(lanes, members):
    return 1 if lanes == "one_lane" else min(ckpt._LANES, members)


def ckpt_threads():
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("ckpt-"))


def within(seconds, fn):
    """Run ``fn`` with a time limit of its own: a save that hangs fails
    here in seconds and holds no worker until the suite's clock. Returns
    what ``fn`` returned or raises what it raised."""
    box = []

    def run():
        try:
            box.append((True, fn()))
        except BaseException as e:
            box.append((False, e))

    t = threading.Thread(target=run, daemon=True, name="limited")
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still running after {seconds} s: it hangs"
    ok, what = box[0]
    if not ok:
        raise what
    return what


class TestManifestFormat:
    def test_manifest_and_shard_layout(self, tmp_path, devices8, lanes):
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
        assert len(shard_files) == n_lanes(lanes, 9)  # 4 + 4 + 1 members
        named = {sh["file"] for leaf in man["leaves"].values()
                 for sh in leaf["shards"]}
        assert named == set(shard_files)
        keys = set()
        for n in shard_files:
            assert n.startswith("t.npz.g")
            # one lane: exactly the name every earlier save had
            assert bool(re.fullmatch(r"t\.npz\.g[0-9a-f]+\.r0\.npz", n)) == (
                lanes == "one_lane")
            assert re.fullmatch(
                r"t\.npz\.g[0-9a-f]+\.r0(\.l[0-3])?\.npz", n)
            assert zipfile.is_zipfile(tmp_path / n)
            with zipfile.ZipFile(tmp_path / n) as zf:
                assert zf.testzip() is None
            with np.load(tmp_path / n) as z:  # plain numpy reads a lane
                assert z.files and all(z[k] is not None for k in z.files)
                keys.update(z.files)
        assert len(keys) == 9  # every member in exactly one lane's file
        assert ckpt.verify(path)
        summ = ckpt.summarize(path)
        assert summ["ok"] and summ["shard_files"] == len(shard_files)
        assert summ["shards"] == 9
        inv = ckpt.summarize_dir(str(tmp_path))
        assert inv["orphan_shards"] == [] and len(inv["checkpoints"]) == 1
        ckpt.delete(path)
        assert os.listdir(tmp_path) == []  # every lane's file went with it

    def test_cross_technique_chain_bit_identical(self, tmp_path, devices8,
                                                 lanes):
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

    def test_resave_garbage_collects_old_generation(self, tmp_path, devices8,
                                                    lanes):
        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)
        gen1 = {n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)}
        ckpt.save(path, state)
        gen2 = {n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)}
        assert gen1.isdisjoint(gen2), "stale generation not collected"
        assert len(gen1) == len(gen2) == n_lanes(lanes, 9)
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

    def test_missing_shard_file_quarantined(self, tmp_path, devices8, lanes):
        state = make_state(mesh_of(4))
        path = str(tmp_path / "t.npz")
        ckpt.save(path, state)
        victim = sorted(
            n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)
        )[-1]  # the only file, or the last lane's
        os.unlink(tmp_path / victim)
        assert not ckpt.verify(path)
        with pytest.raises(ckpt.CheckpointCorruptError):
            ckpt.load_arrays(path)
        assert os.path.exists(path + ".corrupt")


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


# ------------------------------------------------- PR 27: the save pipeline
LAYOUTS = ("replicated", "sharded", "host")
A = np.arange(256 * 64, dtype=np.float32).reshape(256, 64)
MODES = ("save", "save_async")


def stream_tree(layout):
    """Four leaves, one of them bf16 (stored widened): every one replicated
    over four devices, the arrays split over them, or plain numpy. The
    first member of the shard file (``params/a``) is larger than a file
    object's buffer, so its bytes reach the file when it is written."""
    host = {
        "params": {
            "a": A.copy(),
            "b": np.linspace(-1.0, 1.0, 8).astype(np.float32),
            "e": np.asarray(jnp.arange(16, dtype=jnp.bfloat16)),
        },
        "step": np.asarray(7, dtype=np.int32),
    }
    if layout == "host":
        return host
    mesh = mesh_of(4)
    split = NamedSharding(mesh, P("dp") if layout == "sharded" else P())
    whole = NamedSharding(mesh, P())
    return {
        "params": {k: jax.device_put(v, split)
                   for k, v in host["params"].items()},
        "step": jax.device_put(host["step"], whole),
    }


def n_members(layout):
    return 3 * 4 + 1 if layout == "sharded" else 4


def run_save(mode, path, tree):
    getattr(ckpt, mode)(path, tree)
    ckpt.flush()


def litter(d):
    return sorted(n for n in os.listdir(d) if n.endswith(".tmp"))


def shard_files(d):
    return sorted(n for n in os.listdir(d) if ckpt._SHARD_RE.search(n))


def zip_directory(path):
    with zipfile.ZipFile(path) as zf:
        return [(i.filename, i.file_size, i.compress_size, i.CRC,
                 i.compress_type, i.flag_bits, i.extract_version,
                 i.header_offset) for i in zf.infolist()]


class Recorder:
    """Order of events in one save: ``("fetched", member)`` on the caller's
    thread, ``("written", member)`` on the writer's. ``hold_last`` keeps the
    fetch of the last member back until the first one is in the temp file
    (an event with a generous timeout, never a sleep)."""

    def __init__(self, monkeypatch, directory, hold_last=False,
                 fail_at=None):
        self.events = []
        self.members = []  # the plan's, in its order
        self.first_written = threading.Event()
        self.seen_in_tmp = None
        self.first_nbytes = None  # of the first member a lane wrote whole
        self.n_fetch = 0
        real_fetch, real_write = ckpt._fetch, ckpt._write_member
        real_plan = ckpt._plan

        def plan(path, tree):
            planned = real_plan(path, tree)
            self.members[:] = [m for m, _ in planned.fetch]
            return planned

        def fetch(source):
            k = self.n_fetch
            self.n_fetch += 1
            if fail_at is not None and k == fail_at:
                raise OSError(f"no shard {k} for you")
            if hold_last and k == len(self.members) - 1:
                assert self.first_written.wait(60), "writer never wrote"
                tmps = litter(directory)  # one a lane
                assert tmps
                self.seen_in_tmp = max(os.path.getsize(
                    os.path.join(directory, tmp)) for tmp in tmps)
            self.events.append(("fetched", self.members[k]))
            return real_fetch(source)

        def write_member(zf, member, arr, piece):
            real_write(zf, member, arr, piece)
            zf.fp.flush()
            self.events.append(("written", member))
            if not self.first_written.is_set():
                self.first_nbytes = arr.nbytes
            self.first_written.set()

        monkeypatch.setattr(ckpt, "_plan", plan)
        monkeypatch.setattr(ckpt, "_fetch", fetch)
        monkeypatch.setattr(ckpt, "_write_member", write_member)


@pytest.fixture
def no_pending():
    """Every case leaves no writer behind and no parked failure."""
    yield
    for t in list(ckpt._PENDING.values()):
        t.join(60)
    assert not ckpt._PENDING
    ckpt._FAILED.clear()


@pytest.mark.usefixtures("devices8", "no_pending")
class TestStreamedSave:
    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("mode", MODES)
    def test_every_reader_reads_it_and_savez_writes_the_same_file(
            self, tmp_path, mode, layout, lanes):
        tree = stream_tree(layout)
        want = {
            "params/a": A,
            "params/b": np.linspace(-1.0, 1.0, 8).astype(np.float32),
            "params/e": np.arange(16, dtype=np.float32),
            "step": np.asarray(7, dtype=np.int32),
        }
        path = str(tmp_path / "t.npz")
        run_save(mode, path, tree)

        def read_all():
            assert ckpt.verify(path)
            got = ckpt.load_arrays(path)
            assert set(got) == set(want)
            for k in want:  # bf16 comes back widened, as before
                assert got[k].dtype == want[k].dtype, k
                assert got[k].tobytes() == want[k].tobytes(), k
            template = stream_tree("host")
            back = ckpt.restore(path, template)
            assert str(back["params"]["e"].dtype) == "bfloat16"
            assert back["params"]["a"].tobytes() == want["params/a"].tobytes()
            placed = ckpt.restore_sharded(
                path, template, NamedSharding(mesh_of(2), P()))
            assert (np.asarray(placed["params"]["b"]).tobytes()
                    == want["params/b"].tobytes())
            assert int(placed["step"]) == 7
            summ = ckpt.summarize(path)
            assert summ["ok"] and summ["format"] == "sharded-manifest"
            assert summ["leaves"] == 4 and summ["shards"] == n_members(layout)

        read_all()
        assert ckpt_threads() == []  # the flush joined every lane
        assert len(shard_files(tmp_path)) == n_lanes(lanes, n_members(layout))
        with open(path) as f:
            assert json.load(f)["version"] == 1
        # the same members through plain np.savez, under the same manifest:
        # the zip's directory is the same, entry for entry, and every
        # reader reads the same arrays — the format did not move
        for name in shard_files(tmp_path):
            full = str(tmp_path / name)
            streamed = zip_directory(full)
            with np.load(full) as z:
                members = {k: z[k] for k in z.files}
            with open(full, "wb") as f:
                np.savez(f, **members)
            assert zip_directory(full) == streamed
        read_all()

    def test_members_of_every_kind_are_the_bytes_savez_writes(
            self, tmp_path, monkeypatch, lanes):
        """A lane hands a member to its file through a reused piece (PR 46);
        what is not plain C-ordered numbers goes through ``write_array``
        as before. Either way the file is the one ``np.savez`` writes."""
        monkeypatch.setattr(ckpt, "_PIECE_BYTES", 1 << 10)  # many pieces
        tree = {
            "big": np.arange(5000, dtype=np.float64).reshape(50, 100),
            "fortran": np.asfortranarray(np.arange(12.0).reshape(3, 4)),
            "strided": np.arange(40, dtype=np.int16)[::3],
            "flags": np.array([True, False, True]),
            "z": np.array([1 + 2j, 3 - 4j], dtype=np.complex64),
            "words": np.array(["ab", "c"]),
            "none": np.zeros((0, 7), np.float32),
            "point": np.asarray(2.5, np.float32),
        }
        path = str(tmp_path / "t.npz")
        ckpt.save(path, tree)
        assert ckpt.verify(path)
        got = ckpt.load_arrays(path)
        for k, v in tree.items():
            assert got[k].dtype == v.dtype and got[k].shape == v.shape, k
            assert got[k].tobytes() == np.ascontiguousarray(v).reshape(
                v.shape).tobytes(), k
        for name in shard_files(tmp_path):
            full = str(tmp_path / name)
            streamed = zip_directory(full)
            with np.load(full) as z:
                members = {k: z[k] for k in z.files}
            with open(full, "wb") as f:
                np.savez(f, **members)
            assert zip_directory(full) == streamed

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("mode", MODES)
    def test_first_member_is_on_disk_before_the_last_is_fetched(
            self, tmp_path, monkeypatch, mode, layout, lanes):
        rec = Recorder(monkeypatch, str(tmp_path), hold_last=True)
        path = str(tmp_path / "t.npz")
        run_save(mode, path, stream_tree(layout))
        members = rec.members
        assert len(members) == n_members(layout)
        fetched = [e for e in rec.events if e[0] == "fetched"]
        assert [m for _, m in fetched] == members  # plan order:
        assert members[0].startswith("params/a#")  # the largest first,
        assert members[-1] == "step#s0"  # the smallest last
        first_write = next(i for i, e in enumerate(rec.events)
                           if e[0] == "written")
        assert first_write < rec.events.index(("fetched", members[-1]))
        # ... and its bytes were in the shard file's temp file by then
        first = A.nbytes // (4 if layout == "sharded" else 1)
        if lanes == "several_lanes":  # whichever lane ended a member first
            first = rec.first_nbytes
        assert rec.seen_in_tmp is not None and rec.seen_in_tmp >= first > 0
        assert not litter(tmp_path) and ckpt.verify(path)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_save_async_returns_with_every_shard_on_the_host(
            self, tmp_path, monkeypatch, layout, lanes):
        rec = Recorder(monkeypatch, str(tmp_path))
        tree = stream_tree(layout)
        path = str(tmp_path / "t.npz")
        ckpt.save_async(path, tree)
        # every member was fetched before the call returned ...
        assert ([m for k, m in rec.events if k == "fetched"]
                == rec.members)
        # ... so the source may go (the engine donates it into the next
        # step); a host leaf is the caller's to keep until the join
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "delete"):
                leaf.delete()
        del tree
        ckpt.flush()
        got = ckpt.load_arrays(path)
        assert got["params/a"].tobytes() == A.tobytes()
        assert got["params/e"].tobytes() == np.arange(
            16, dtype=np.float32).tobytes()
        assert int(got["step"]) == 7

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("fail_at", [0, 2, 3])  # 3: the last, if 4
    def test_fetch_that_raises_commits_nothing(
            self, tmp_path, monkeypatch, mode, layout, lanes, fail_at):
        path = str(tmp_path / "t.npz")
        ckpt.save(path, stream_tree(layout))
        before = sorted(os.listdir(tmp_path))
        prev = ckpt.load_arrays(path)["params/a"].tobytes()
        Recorder(monkeypatch, str(tmp_path), fail_at=fail_at)
        bad = stream_tree(layout)
        with pytest.raises(OSError, match=f"no shard {fail_at}"):
            # on this thread, and soon
            within(60, lambda: getattr(ckpt, mode)(path, bad))
        assert not ckpt._PENDING  # the writer was stopped and joined,
        assert ckpt_threads() == []  # and every lane with it
        ckpt.flush()  # ... and parked nothing
        assert sorted(os.listdir(tmp_path)) == before  # no tmp, no new gen
        assert ckpt.verify(path)
        assert ckpt.load_arrays(path)["params/a"].tobytes() == prev

    @pytest.mark.parametrize("join", ["flush", "next_save_async", "save"])
    def test_writer_that_dies_surfaces_and_hangs_nobody(self, tmp_path, join,
                                                        lanes):
        (tmp_path / "nodir").write_bytes(b"")  # the "directory" is a file

        target = str(tmp_path / "nodir" / "t.npz")

        def go():
            if join == "save":
                ckpt.save(target, stream_tree("sharded"))
                return
            ckpt.save_async(target, stream_tree("sharded"))
            if join == "flush":
                ckpt.flush()
            else:
                ckpt.save_async(target, stream_tree("sharded"))

        with pytest.raises((OSError, RuntimeError)) as ei:
            within(60, go)  # the caller's thread hangs on no dead writer
        err = ei.value
        if join == "save":  # the writer's own error, on the caller's thread
            assert isinstance(err, OSError)
        else:
            assert isinstance(err, RuntimeError)
            assert "async checkpoint write" in str(err)
            assert isinstance(err.__cause__, OSError)
        assert ckpt_threads() == []  # it died before it started a lane
        ckpt.flush()  # consumed: the next join point is clean

    @pytest.mark.parametrize("mode", MODES)
    def test_write_span_is_its_snapshots_sibling_and_overlaps_it(
            self, tmp_path, monkeypatch, mode, lanes):
        Recorder(monkeypatch, str(tmp_path), hold_last=True)
        ev = str(tmp_path / "ev.jsonl")
        with metrics.scoped(ev):
            with metrics.span("task_interval_like"):
                getattr(ckpt, mode)(str(tmp_path / "t.npz"),
                                    stream_tree("sharded"))
            ckpt.flush()
        by = {}
        for e in metrics.read_events(ev):
            by.setdefault(e["kind"], []).append(e)
        (outer,), (snap,), (write,) = (
            by["task_interval_like"], by["ckpt.snapshot"], by["ckpt.write"])
        assert snap["parent"] == write["parent"] == outer["id"]
        assert write["root"] == outer["id"]
        assert write["thread"].startswith("ckpt-")
        assert write["thread"] != snap["thread"]
        assert write["ts_start"] < snap["ts"]  # began under its snapshot
        assert snap["n_streamed"] == n_members("sharded") == write["n_shards"]
        assert snap["bytes"] == write["bytes"] == A.nbytes + 8 * 4 + 16 * 4 + 4
        assert 0 < write["overlap_s"] <= write["dur_s"] + 1e-6
        assert 0 <= write["starved_s"] <= write["dur_s"] + 1e-6
        # one ckpt.write a save whatever its lanes (the benchmark divides
        # the bytes by the seconds of the spans of that name); a lane's
        # span has a name of its own and is the write's sibling
        assert write["lanes"] == len(by["ckpt.lane"]) == n_lanes(lanes, 13)
        assert len({e["thread"] for e in by["ckpt.lane"]}) == write["lanes"]
        for e in by["ckpt.lane"]:
            assert e["parent"] == outer["id"] and e["root"] == outer["id"]
            assert e["thread"].startswith(write["thread"] + ".l")
            assert 0 <= e["starved_s"] <= write["starved_s"] + 1e-9
            assert write["ts_start"] <= e["ts_start"] + 0.005
            assert e["ts"] <= write["ts"] + 0.005
        assert sum(e["bytes"] for e in by["ckpt.lane"]) == write["bytes"]
        assert sum(e["n_members"] for e in by["ckpt.lane"]) == 13
        # the join of a synchronous save is a ckpt.flush of its own
        assert len(by["ckpt.flush"]) == (2 if mode == "save" else 1)


@pytest.mark.usefixtures("devices8", "no_pending")
class TestLaneEndings:
    """PR 46: a save over the size threshold writes its members on several
    lanes, a daemon thread and a shard file each, started by the save's one
    writer thread (``ckpt-<base>``, the one in ``_PENDING``) and ended
    before that one returns, on every path. Each case has a time limit of
    its own (``within``) and ends with no ``ckpt-`` thread alive."""

    @pytest.fixture(autouse=True)
    def _several(self, monkeypatch):
        monkeypatch.setattr(ckpt, "_LANE_MIN_BYTES", 1)
        assert ckpt_threads() == []
        yield
        for t in threading.enumerate():
            if t.name.startswith("ckpt-"):
                t.join(30)
        assert ckpt_threads() == []

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("mode", MODES)
    def test_a_lane_that_dies_stops_the_others_and_commits_nothing(
            self, tmp_path, monkeypatch, mode, layout):
        """Lane 1's write raises while the other lanes are inside a member:
        the error is that lane's, the others stop, no temp file and no file
        of the new generation is left, the previous generation verifies."""
        path = str(tmp_path / "t.npz")
        ckpt.save(path, stream_tree(layout))
        before = sorted(os.listdir(tmp_path))
        prev = ckpt.load_arrays(path)["params/a"].tobytes()
        real, real_fail = ckpt._write_member, ckpt._Stream.fail
        inside = threading.Semaphore(0)
        failed = threading.Event()
        after = []

        def fail(stream, err):
            real_fail(stream, err)
            failed.set()

        def write_member(zf, member, arr, piece):
            if threading.current_thread().name.endswith(".l1"):
                for _ in range(ckpt._LANES - 1):  # the others are mid-member
                    assert inside.acquire(timeout=30)
                raise OSError("disk full on lane 1")
            if failed.is_set():
                after.append(member)  # a member begun after the stop
            inside.release()
            assert failed.wait(30), "the failed lane told nobody"
            real(zf, member, arr, piece)

        monkeypatch.setattr(ckpt._Stream, "fail", fail)
        monkeypatch.setattr(ckpt, "_write_member", write_member)

        def go():
            getattr(ckpt, mode)(path, stream_tree(layout))
            ckpt.flush()

        with pytest.raises((OSError, RuntimeError)) as ei:
            within(60, go)
        err = ei.value
        if mode == "save_async":  # parked, raised at the join point
            assert "async checkpoint write" in str(err)
            err = err.__cause__
        assert isinstance(err, OSError) and "lane 1" in str(err)
        assert after == []  # a lane ends the member it is in, and no more
        assert ckpt_threads() == [] and not ckpt._PENDING
        assert sorted(os.listdir(tmp_path)) == before  # no tmp, no new gen
        assert ckpt.verify(path)
        assert ckpt.load_arrays(path)["params/a"].tobytes() == prev
        ckpt.flush()  # consumed: the next join point is clean

    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("n", [0, 1, 2, 3])
    def test_fewer_members_than_lanes(self, tmp_path, mode, n):
        """A lane for every member and no lane without one; a tree with no
        member at all starts no lane and still commits its manifest."""
        tree = {f"m{i}": np.full((3, 5), i, np.float32) for i in range(n)}
        path = str(tmp_path / "t.npz")
        within(60, lambda: run_save(mode, path, tree))
        assert ckpt_threads() == [] and not litter(tmp_path)
        assert len(shard_files(tmp_path)) == n
        assert ckpt.verify(path)
        got = ckpt.load_arrays(path)
        assert set(got) == set(tree)
        for k in tree:
            assert got[k].tobytes() == tree[k].tobytes()

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("point",
                             ["mid-shard-write", "pre-manifest-rename"])
    def test_kill_at_a_barrier_ends_every_lane(self, tmp_path, mode, layout,
                                               point):
        from saturn_tpu.resilience.crash import CrashInjector, SimulatedKill

        path = str(tmp_path / "t.npz")
        ckpt.save(path, stream_tree(layout))
        before = sorted(os.listdir(tmp_path))
        prev = ckpt.load_arrays(path)["params/a"].tobytes()
        seen = []
        inj = CrashInjector(point)

        def barrier(at, ctx):
            seen.append((at, threading.current_thread().name,
                         os.path.exists(ctx["tmp"])))
            inj.barrier(at, ctx)

        ckpt.set_crash_barrier(barrier)

        def go():
            getattr(ckpt, mode)(path, stream_tree(layout))
            ckpt.flush()

        with pytest.raises((SimulatedKill, RuntimeError)) as ei:
            within(60, go)
        ckpt.set_crash_barrier(None)
        err = ei.value if mode == "save" else ei.value.__cause__
        assert isinstance(err, SimulatedKill)
        # every lane crosses mid-shard-write with its own staged file, on
        # its own thread; the writer thread crosses pre-manifest-rename
        mid = [s for s in seen if s[0] == "mid-shard-write"]
        assert all(name.startswith("ckpt-t.npz.l") and staged
                   for _, name, staged in mid)
        if point == "pre-manifest-rename":
            assert len({name for _, name, _ in mid}) == ckpt._LANES
            assert seen[-1] == (point, "ckpt-t.npz", True)
        assert ckpt_threads() == [] and not ckpt._PENDING
        assert sorted(os.listdir(tmp_path)) == before
        assert ckpt.verify(path)
        assert ckpt.load_arrays(path)["params/a"].tobytes() == prev

    def test_lanes_come_from_the_plan_alone(self, monkeypatch):
        """Bytes and members decide, the largest member first, each to the
        lane with the fewest bytes: every process of a multi-process save
        names the same files for every rank."""
        mb = 1 << 20
        monkeypatch.setattr(ckpt, "_LANE_MIN_BYTES", 256 * mb)
        sizes = [10 * mb, 300 * mb, 20 * mb, 300 * mb, 150 * mb, 150 * mb,
                 4, 90 * mb]
        shards = [(n, {"key": f"k{i}", "file": None})
                  for i, n in enumerate(sizes)]
        files, order, lane_of = ckpt._assign_lanes("t.npz.g1f.r2", shards)
        assert files == [f"t.npz.g1f.r2.l{k}.npz" for k in range(ckpt._LANES)]
        assert [sizes[i] for i in order] == sorted(sizes, reverse=True)
        assert order[:2] == [1, 3]  # equal sizes keep the tree's order
        held = [0] * len(files)
        for i, k in zip(order, lane_of):
            assert held[k] == min(held)  # the emptiest lane at its turn
            held[k] += sizes[i]
            assert shards[i][1]["file"] == files[k]
        assert max(held) - min(held) <= max(sizes)  # they end together
        assert all(ckpt._SHARD_RE.search(f).groups() == ("1f", "2")
                   for f in files)
        # under the threshold: one lane under the name it always had
        small = [(n, {"key": f"k{i}", "file": None})
                 for i, n in enumerate([100 * mb, 100 * mb, 55 * mb])]
        files, order, lane_of = ckpt._assign_lanes("t.npz.g1f.r2", small)
        assert files == ["t.npz.g1f.r2.npz"] and lane_of == [0, 0, 0]
        assert ckpt._SHARD_RE.search(files[0]).groups() == ("1f", "2")
        # over it with two members: two lanes, never an empty one
        two = [(200 * mb, {"key": "a", "file": None}),
               (200 * mb, {"key": "b", "file": None})]
        files, _, lane_of = ckpt._assign_lanes("t.npz.g1f.r0", two)
        assert len(files) == 2 and sorted(lane_of) == [0, 1]


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
