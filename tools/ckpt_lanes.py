"""What a save of a train state of GPT-J's size costs, by the number of lanes
(PR 46): the measurement that fixes ``utils/checkpoint.py::_LANES``.

    chiprun -- python3 tools/ckpt_lanes.py [--lanes 1,2,3,4,6] [--gb 7.3] [--copy] [--buffer]

Chip or fail (``PERF_REHEARSAL_PLATFORM``, the benchmark's own name for a
rehearsal on the CPU, lets a small ``--gb`` through). Puts float32 arrays
of the sizes the GPT-J cell's state has (six of 825 MB: embedding, head and
Adam's moments of each; the block's matrices at 268 and 67 MB) on the chip,
and saves them with ``checkpoint.save`` once for every lane count,
``--reps`` times over, each save from device arrays no earlier save has
copied and after an ``os.sync()``, into a directory under
``tempfile`` (where the benchmark's checkpoints go). One line a save: the
caller's ``ckpt.snapshot`` (the device->host copies), the writer's
``ckpt.write``, the ``ckpt.flush`` that is left when the snapshot ends, the
longest lane's wait for the link (``starved_s``) and each lane's seconds.
The writer is the module's (each member through the lane's one reused
piece of ``_PIECE_BYTES``); ``--copy`` also saves through ``np.lib.format.
write_array`` (the parent's writer: a fresh 16 MiB copy of every piece),
``--buffer`` from the arrays' own buffers (ISSUE 46's point 4, measured and
not taken) on one lane and on ``_LANES``, ``--piece-mb 1,8`` through pieces
of other sizes. Before the saves, what one member costs alone: its copy to
the host, its CRC-32, its ``write``. The last save is verified. Saves that
follow each other within seconds can meet a stretch in which the host
gives every variant a third of its rate (PR 46 saw two in seven calls):
``--pause 6`` rests before each save, ``--reps`` repeats the round.
The lane count is set by assignment to the module's constant: no option of
the program reads it.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MB = 1 << 20
SIZES_MB = [825] * 6 + [268] * 8 + [67] * 3  # 7.3 GB, as GPT-J's cell holds


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--lanes", default="1,2,3,4,6")
    p.add_argument("--gb", type=float, default=7.3)
    p.add_argument("--copy", action="store_true")
    p.add_argument("--buffer", action="store_true")
    p.add_argument("--piece-mb", default="",
                   help="also: through a piece of this size, not _PIECE_BYTES")
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--pause", type=float, default=0.0,
                   help="seconds of rest before each save")
    args = p.parse_args()

    import jax
    import numpy as np

    from saturn_tpu.utils import checkpoint as ckpt
    from saturn_tpu.utils import metrics

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not os.environ.get("PERF_REHEARSAL_PLATFORM"):
        raise SystemExit("ckpt_lanes: no TPU")
    scale = args.gb / (sum(SIZES_MB) * MB / 1e9)
    key = jax.random.PRNGKey(0)
    tree = {}
    for i, mb in enumerate(SIZES_MB):
        n = int(mb * MB * scale) // 4
        tree[f"m{i:02d}"] = jax.random.normal(
            jax.random.fold_in(key, i), (n,), dtype=np.float32)
    tree["step"] = jax.device_put(np.asarray(7, np.int32), dev)
    jax.block_until_ready(tree)
    total = sum(x.nbytes for x in tree.values())
    root = tempfile.mkdtemp(prefix="ckpt-lanes-")
    print(f"ckpt_lanes: {total / 1e9:.2f} GB in {len(tree)} members on "
          f"{dev.device_kind}, into {root}, host cores {os.cpu_count()}",
          flush=True)
    plain = ckpt._write_member

    def with_copy(zf, member, arr, piece):
        # the parent's writer: write_array allocates a copy of every piece
        with zf.open(member + ".npy", "w", force_zip64=True) as fid:
            np.lib.format.write_array(fid, arr, allow_pickle=False)

    def from_buffer(zf, member, arr, piece):
        # the pass a lane could lose: header, then the array's own bytes
        with zf.open(member + ".npy", "w", force_zip64=True) as fid:
            np.lib.format.write_array_header_1_0(
                fid, np.lib.format.header_data_from_array_1_0(arr))
            raw = arr.reshape(-1).view(np.uint8)
            for at in range(0, raw.size, 64 * MB):
                fid.write(raw[at:at + 64 * MB])

    def through_piece(mb):
        def write(zf, member, arr, piece):
            plain(zf, member, arr, np.empty(mb * MB, np.uint8))
        return write

    fresh = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x + 0, t),
                    donate_argnums=0)  # in place: a state of 10 GB fits

    def meminfo(*names):  # GB; a sandboxed kernel may not say
        try:
            with open("/proc/meminfo") as f:
                rows = dict(line.split(":") for line in f)
        except OSError:
            return {}
        return {n: int(rows[n].split()[0]) / 1e6 for n in names if n in rows}

    vm = {}
    for name in ("dirty_ratio", "dirty_background_ratio"):
        try:
            with open("/proc/sys/vm/" + name) as f:
                vm[name] = int(f.read())
        except OSError:
            vm[name] = None
    try:
        with open("/proc/mounts") as f:
            mounts = [line.split()[:3] for line in f]
        mount = max((m for m in mounts if root.startswith(m[1])),
                    key=lambda m: len(m[1]), default=None)
    except OSError:
        mount = None
    print(f"ckpt_lanes: vm {vm}; {meminfo('MemTotal', 'MemAvailable')} GB; "
          f"mount: {mount}", flush=True)

    lanes = [int(n) for n in args.lanes.split(",")]
    cases = [("piece", n) for n in lanes]
    writers = {"piece": plain, "copy": with_copy, "buffer": from_buffer}
    if args.copy:
        cases += [("copy", n) for n in lanes]
    if args.buffer:
        cases += [("buffer", 1), ("buffer", ckpt._LANES)]
    for mb in filter(None, args.piece_mb.split(",")):
        writers[f"p{mb}"] = through_piece(int(mb))
        cases += [(f"p{mb}", n) for n in lanes]
    real_fetch = ckpt._fetch
    fetch_s = []

    def fetch(source):
        t0 = time.perf_counter()
        arr = real_fetch(source)
        fetch_s.append((time.perf_counter() - t0, arr.nbytes))
        return arr

    ckpt._fetch = fetch
    # what one member costs alone, nothing else running: the copy to the
    # host, its CRC-32, its write and giving its memory back
    import zlib

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    tree = fresh(tree)
    big = tree["m00"]
    probe = os.path.join(root, "probe")
    arr, fetch_one = timed(lambda: np.asarray(big))
    _, crc_one = timed(lambda: zlib.crc32(arr.view(np.uint8)))
    with open(probe, "wb") as f:
        _, write_one = timed(lambda: f.write(arr.view(np.uint8)))
    _, unlink_one = timed(lambda: os.unlink(probe))
    print(f"ckpt_lanes: one member of {big.nbytes / 1e9:.2f} GB alone: fetch "
          f"{fetch_one:.3f}s crc {crc_one:.3f}s write {write_one:.3f}s "
          f"unlink {unlink_one:.3f}s", flush=True)
    del arr
    del big
    path = os.path.join(root, "t.npz")
    events = os.path.join(root, "ev.jsonl")
    try:
        ckpt.save(path, {"warm": np.zeros(8)})  # the first save's imports
        for rep in range(args.reps):
            for how, lanes in cases:
                ckpt._LANES = lanes
                ckpt._write_member = writers[how]
                del fetch_s[:]
                ckpt.delete(path)
                if os.path.exists(events):
                    os.unlink(events)
                # a run of the benchmark saves once: start every save from
                # a clean page cache, and from device arrays no earlier
                # save has copied (a jax.Array keeps its host copy)
                os.sync()
                time.sleep(args.pause)
                tree = jax.block_until_ready(fresh(tree))
                with metrics.scoped(events):
                    t0 = time.perf_counter()
                    ckpt.save(path, tree)
                    wall = time.perf_counter() - t0
                by = {}
                for e in metrics.read_events(events):
                    by.setdefault(e["kind"], []).append(e)
                (snap,), (write,) = by["ckpt.snapshot"], by["ckpt.write"]
                flush = sum(e["dur_s"] for e in by["ckpt.flush"])
                lane_s = sorted(round(e["dur_s"], 2) for e in by["ckpt.lane"])
                print(f"{how:6s} lanes={write['lanes']} wall={wall:.2f}s "
                      f"snapshot={snap['dur_s']:.2f}s "
                      f"({total / snap['dur_s'] / 1e9:.2f} GB/s) "
                      f"write={write['dur_s']:.2f}s "
                      f"({total / write['dur_s'] / 1e9:.2f} GB/s) "
                      f"flush={flush:.2f}s starved={write['starved_s']:.2f}s "
                      f"lane_s={lane_s} fetch_gb_s="
                      f"{[round(n / s / 1e9, 1) for s, n in fetch_s[:12]]} "
                      f"after: "
                      f"{meminfo('Dirty', 'Writeback', 'MemAvailable')} GB",
                      flush=True)
        t0 = time.perf_counter()
        ok = ckpt.verify(path)
        print(f"verify: {ok} in {time.perf_counter() - t0:.2f}s; threads left: "
              f"{[t.name for t in threading.enumerate() if t.name.startswith('ckpt-')]}")
        return 0 if ok else 1
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
