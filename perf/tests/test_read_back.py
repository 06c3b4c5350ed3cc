"""The checkpoint read back (``refcheck.read_back``, ``harness.check_window``):
a sound one reads 0, and each way a save can go wrong -- a shard file gone,
a member torn, values altered under a valid CRC, two chips' shards swapped --
reads at least 1, whose limit is 0. Then a whole rehearsal run with the save
broken underneath (the copy off the chips alters a value) comes out not
correct by that number alone."""

import json
import os
import zipfile

import numpy as np
import pytest

from perf.lib import refcheck


def _saved(tmp_path):
    """A small train state sharded over the (virtual) devices, saved by the
    package; what ``refcheck.live_state`` would hand ``read_back`` of it."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from saturn_tpu.utils import checkpoint
    from saturn_tpu.utils.treepath import path_str

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("chips",))
    rng = np.random.default_rng(7)
    rows = NamedSharding(mesh, P("chips", None))
    whole = NamedSharding(mesh, P())

    def leaf(shape, sharding):
        return jax.device_put(rng.standard_normal(shape).astype(np.float32), sharding)

    n = 8 * len(devices)
    state = {"params": {"wte": leaf((n, 16), rows), "ln_f": {"scale": leaf((16,), whole)}},
             "opt": {"mu": {"wte": leaf((n, 16), rows), "ln_f": {"scale": leaf((16,), whole)}}},
             "step": jax.device_put(np.int32(3), whole)}
    path = str(tmp_path / "job.npz")
    checkpoint.save(path, state)
    arrays, shardings = {}, {}
    for p, x in jax.tree_util.tree_flatten_with_path(state)[0]:
        arrays[path_str(p)], shardings[path_str(p)] = np.asarray(x), x.sharding
    return path, arrays, shardings


def _shard_file(path):
    folder = os.path.dirname(path)
    (name,) = [n for n in os.listdir(folder) if n.endswith(".npz") and ".g" in n]
    return os.path.join(folder, name)


def _rewrite(shard, change):
    """The shard file written anew (valid CRCs) with ``change(members)``
    applied to its arrays by member name."""
    with np.load(shard) as z:
        members = {k: z[k] for k in z.files}
    change(members)
    np.savez(shard[:-len(".npz")], **members)


def test_a_sound_checkpoint_reads_nothing_that_differs(tmp_path, capsys):
    path, arrays, shardings = _saved(tmp_path)
    assert refcheck.read_back(path, arrays, shardings, 3200000611) == {
        "ckpt_leaves_differ": 0.0}
    assert f"{len(arrays)} of {len(arrays)} leaves" in capsys.readouterr().out
    # a budget smaller than the state: a sample drawn from the seed, never
    # more bytes than the budget, another seed another sample
    picked = []
    for seed in (1, 2, 3, 4):
        assert refcheck.read_back(path, arrays, shardings, seed,
                                  budget=arrays["params/wte"].nbytes + 80) == {
            "ckpt_leaves_differ": 0.0}
        picked.append(capsys.readouterr().out)
    assert all(" 1 of 5 leaves" not in p and " 5 of 5 leaves" not in p for p in picked)


def _shard_file_gone(path):
    os.remove(_shard_file(path))


def _member_torn(path):
    shard = _shard_file(path)
    with zipfile.ZipFile(shard) as zf:
        info = max(zf.infolist(), key=lambda i: i.file_size)
    with open(shard, "r+b") as f:
        f.seek(info.header_offset + 200)
        byte = f.read(1)
        f.seek(info.header_offset + 200)
        f.write(bytes([byte[0] ^ 0xFF]))


def _value_altered_under_a_valid_crc(path):
    def change(members):
        k = max(members, key=lambda k: members[k].size)
        members[k] = members[k].copy()
        members[k].flat[5] += 1.0
    _rewrite(_shard_file(path), change)


def _two_chips_shards_swapped(path):
    def change(members):
        big = sorted(k for k in members if members[k].ndim == 2)
        same = [k for k in big if members[k].shape == members[big[0]].shape]
        members[same[0]], members[same[1]] = members[same[1]], members[same[0]]
    _rewrite(_shard_file(path), change)


@pytest.mark.parametrize("fault", [_shard_file_gone, _member_torn,
                                   _value_altered_under_a_valid_crc,
                                   _two_chips_shards_swapped],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_checkpoint_is_not_correct(tmp_path, fault):
    path, arrays, shardings = _saved(tmp_path)
    fault(path)
    numbers = refcheck.read_back(path, arrays, shardings, 3200000617)
    assert numbers["ckpt_leaves_differ"] >= 1
    said = []
    sound = {"logits_rel_rms": 0.0, "grad_rel_rms": 0.0, "update_rel_rms": 0.0,
             "loss_max_rel": 0.0, "loss_drop_rel": 0.0}
    assert refcheck.verdict({**sound, **numbers}, refcheck.load_limits(),
                            said.append, fault.__name__) is False
    assert sum("NOT OK" in s for s in said) == 1
    assert refcheck.verdict({**sound, "ckpt_leaves_differ": 0.0},
                            refcheck.load_limits(), said.append, "sound") is True


def test_the_windows_checkpoint_has_to_verify(tmp_path, monkeypatch):
    """``check_window`` on a window whose checkpoint lost a byte."""
    from perf.lib import harness
    from saturn_tpu.utils import checkpoint

    path, _, _ = _saved(tmp_path)
    assert checkpoint.verify(path) and harness.saved_step(path) == 3
    _member_torn(path)
    assert not checkpoint.verify(path)

    class Window:  # what check_window reads of a run, one job
        window = {"result": {"failed": [], "completed": ["job"]}}
        tasks = [type("T", (), {"name": "job", "ckpt_path": path})()]

        def events(self, phase, kind):
            return [{"ts": 0.0, "plan": {"assignments": {}}}] if kind == "solve" else []

        def job(self, name):
            return type("J", (), {"batch_count": 3, "tokens_per_step": 8})()

    monkeypatch.setattr(harness, "topology", lambda run: None)
    with pytest.raises(harness.NotCorrect, match="does not verify"):
        harness.check_window(Window())


def test_a_run_whose_save_alters_a_value_is_not_correct(tmp_path, monkeypatch, capfd):
    """The rest of a run past the look for a chip, at tiny size, with the
    timed path broken underneath: the copy off the chips that the save
    streams to its writer alters one value of every matrix. Steps, losses
    and the state on the chips are sound, the files verify (the CRC is taken
    of the altered bytes): only the leaves read back say so."""
    from perf.lib import harness
    from perf.tests import tinyroot
    from saturn_tpu.utils import checkpoint

    fetch = checkpoint._fetch

    def altered(source):
        out = fetch(source)
        if out.ndim >= 2:
            out = out.copy()
            out.flat[0] += 1.0
        return out

    monkeypatch.setattr(checkpoint, "_fetch", altered)
    monkeypatch.setenv(harness.REHEARSAL_ENV, "cpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    one_job = dict(tinyroot.TINY_TRAFFIC, interval={"window_fraction": 100.0},
                   jobs=tinyroot.TINY_TRAFFIC["jobs"][:1])
    name = tinyroot.write(str(tmp_path), one_job)
    import time
    rc = harness.main_run(name, 3200000623, 2.0, False, time.time(),
                          root=str(tmp_path))
    out, err = capfd.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is False
    bad = {k.split(".")[-1] for k, c in result["compared"].items() if not c["ok"]}
    assert bad == {"ckpt_leaves_differ"}
    assert "NOT OK" in err.strip().splitlines()[-1] or any(
        "ckpt_leaves_differ" in l and "NOT OK" in l for l in err.splitlines())
