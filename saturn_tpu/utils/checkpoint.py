"""Path-keyed pytree checkpointing with cross-technique resharding.

The reference checkpoints model state only, via ``torch.save`` of a state dict
(``Task.py:150-169``), and silently drops optimizer state between intervals
(``FSDP.py:220``, ``DDP.py:163``) — a wart SURVEY.md §5 flags to fix. Here we
save the **full train state** (params + optimizer state + step) keyed by tree
path; the data cursor is derived from ``step`` on restore, making resume
restart-safe.

Format (round 19, ROADMAP item 6): **sharded manifest**. The logical
checkpoint path holds a checksummed JSON manifest (tree structure, leaf
dtypes/shapes, per-leaf shard index→file map, PartitionSpec fingerprint);
the array bytes live beside it in per-rank ``.npz`` shard files named
``<path>.g<GEN>.r<RANK>.npz`` (a rank whose members are 256 MB or more
spreads them over up to four, ``...r<RANK>.l<LANE>.npz``: the manifest names
each member's file, so every reader follows it). Each process writes only its
locally-addressable shards — the device→host copy is a pure local transfer,
with **no allgather and no replication funnel** (the SAT-X002 anti-pattern
the previous single-writer format needed two sanction markers for). The
global shard layout is computed from sharding *metadata* alone
(``Sharding.devices_indices_map`` is the same on every process), so the
manifest needs no communication either. ``GEN`` is a per-save generation id:
a crashed save can never tear the previously committed generation's files,
and the manifest rename is the single atomic commit point (stale generations
are garbage-collected only after it lands).

A save is a **pipeline** (PR 27): the plan (manifest body and the ordered
list of members this process fetches) comes from metadata alone; then the
device→host copies run back to back while the shards that have landed are
already being put into the shard file. The caller is held until its last
shard is on the host, the commit order is what it was, and ``save`` is
``save_async`` joined. The disk half runs on **lanes** (PR 46): the plan
gives every member to a lane, largest first and each to the lane with the
fewest bytes, and fetches in that order; a lane is a daemon thread and a
shard file of its own; the save's one writer thread (``ckpt-<base>``, the
only one the join points know) starts them, returns only when each has
ended, and then renames their files and the manifest. How many lanes comes
from the plan's bytes and members alone (``_LANE_MIN_BYTES``, ``_LANES``):
one, and the file name and layout of every earlier save, under 256 MB.

Saving by *path* rather than pickling tree structure is what makes
interval-boundary **technique switching** work (the reference's central
trick, ``executor.py:65`` kill-and-respawn + state-dict reload): any
technique can restore the same arrays under a *different* mesh/sharding,
because ``restore_sharded`` maps saved shards onto the destination
technique's shardings leaf by leaf — assembling only the blocks each
destination device needs, so no host materializes the full replicated tree.
A compatibility reader keeps pre-round-19 single-file ``.npz`` checkpoints
restorable (readers sniff JSON-vs-zip on the first byte).
"""

from __future__ import annotations

import glob
import hashlib
import json
import logging
import math
import os
import queue
import re
import tempfile
import threading
import time
import zipfile
import zlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from saturn_tpu.utils import metrics
from saturn_tpu.utils.treepath import path_str as _path_str

log = logging.getLogger("saturn_tpu")

#: Manifest self-identification; readers sniff the first byte (``{`` vs
#: zip's ``PK``) and then check this field.
MANIFEST_FORMAT = "saturn-ckpt-manifest"
MANIFEST_VERSION = 1

#: Shard files committed beside a manifest: ``<path>.g<GEN>.r<RANK>.npz``,
#: or ``<path>.g<GEN>.r<RANK>.l<LANE>.npz`` for a rank whose save took
#: several lanes. Two groups either way: generation and rank.
_SHARD_RE = re.compile(r"\.g([0-9a-f]+)\.r(\d+)(?:\.l\d+)?\.npz$")

#: A rank's members go into one shard file (today's name, one lane) while
#: they are fewer bytes than this, and into ``_LANES`` files (fewer where
#: the rank has fewer members), each written on a thread of its own, from
#: this size on. Both are constants of the format's writer, read from the
#: plan's metadata alone: the manifest's writer names the other ranks' files
#: without talking to them, so nothing of the host (its cores) may enter.
#: Measured on the benchmark's host (PR 46, ``tools/ckpt_lanes.py``, 7.3 GB
#: from one chip, through ``write_array``): one lane 5.2-6.1 s a save, two
#: 3.6-4.6, three 3.5-3.7, four 3.6-3.7, six 3.6-3.7: from three on the
#: copies to the host and the writes share what that host gives both, and
#: more lanes buy nothing.
_LANE_MIN_BYTES = 256 << 20
_LANES = 4
#: A lane hands its members to its file through one buffer of this size
#: (``_write_member``; 1 MiB pieces kept no pace, 8 and 16 MiB read slower).
_PIECE_BYTES = 4 << 20


class CheckpointCorruptError(RuntimeError):
    """A checkpoint exists on disk but cannot be read back (truncated write,
    bit rot, torn page, missing/corrupt shard file). The unreadable artifact
    has already been quarantined to a ``*.corrupt`` sidecar by the time this
    raises, so crash recovery can fall back to the *previous* published
    checkpoint instead of dying on the newest one."""

    def __init__(self, path: str, quarantined: str, cause: str):
        self.path = path
        self.quarantined = quarantined
        super().__init__(
            f"checkpoint {path} is corrupt ({cause}); quarantined to "
            f"{quarantined}"
        )


def quarantine(path: str) -> str:
    """Rename an unreadable artifact to a ``*.corrupt`` sidecar (never
    overwrite an earlier quarantine: ``.corrupt``, ``.corrupt.1``, ...).
    Returns the sidecar path; if the rename itself fails the original path
    is returned and the file is left in place (recovery treats both the
    same — the path is not a usable checkpoint)."""
    sidecar = path + ".corrupt"
    n = 0
    while os.path.exists(sidecar):
        n += 1
        sidecar = f"{path}.corrupt.{n}"
    try:
        os.replace(path, sidecar)
    except OSError:
        log.exception("failed to quarantine %s", path)
        return path
    return sidecar


# ------------------------------------------------------------ crash barriers
# The resilience crash harness installs a callback here to simulate SIGKILL
# at the two commit-critical crossings of a sharded save: ``mid-shard-write``
# (shard bytes staged, shard rename not yet done) and ``pre-manifest-rename``
# (all shards durable, manifest — the commit point — not yet renamed). A
# kill at either leaves the previous generation fully intact.
_CRASH_BARRIER: Optional[Callable[[str, Dict[str, Any]], None]] = None


def set_crash_barrier(cb: Optional[Callable[[str, Dict[str, Any]], None]]) -> None:
    """Install (None to clear) the crash-harness barrier callback; called as
    ``cb(point, ctx)`` from whichever thread performs the write."""
    global _CRASH_BARRIER
    _CRASH_BARRIER = cb


def _barrier(point: str, **ctx: Any) -> None:
    cb = _CRASH_BARRIER
    if cb is not None:
        cb(point, ctx)


# ---------------------------------------------------------------- sniff/read
def _is_manifest_file(path: str) -> bool:
    """Format sniff: a round-19 manifest is JSON (first byte ``{``); the
    legacy single-file format is a zip (``PK``). Raises OSError for a path
    that cannot be opened — callers decide how missing files surface."""
    with open(path, "rb") as f:
        return f.read(1) == b"{"


def _manifest_checksum(body: Dict[str, Any]) -> str:
    """CRC-32 of the canonical (sorted-key, no-whitespace) JSON body with the
    ``checksum`` field absent — a torn or hand-edited manifest fails closed."""
    scrubbed = {k: v for k, v in body.items() if k != "checksum"}
    canon = json.dumps(scrubbed, sort_keys=True, separators=(",", ":"))
    return f"{zlib.crc32(canon.encode('utf-8')) & 0xFFFFFFFF:08x}"


def _read_manifest(path: str) -> Dict[str, Any]:
    """Parse + integrity-check a manifest. Raises ``ValueError`` on any
    structural or checksum mismatch (callers wrap into quarantine)."""
    with open(path, "r", encoding="utf-8") as f:
        body = json.load(f)
    if body.get("format") != MANIFEST_FORMAT:
        raise ValueError(f"not a {MANIFEST_FORMAT} file")
    if int(body.get("version", -1)) > MANIFEST_VERSION:
        raise ValueError(f"manifest version {body['version']} is newer than "
                         f"this reader ({MANIFEST_VERSION})")
    want = body.get("checksum")
    got = _manifest_checksum(body)
    if want != got:
        raise ValueError(f"manifest checksum mismatch ({want} != {got})")
    return body


def verify(path: str) -> bool:
    """Integrity-check a published checkpoint without loading it into
    memory. Manifest format: the JSON body must checksum, every referenced
    shard file must exist, parse as a zip with every member CRC intact, and
    contain the referenced member keys; every leaf's shard extents must
    cover its full shape. Legacy ``.npz``: the zip central directory must
    parse and every member CRC must match. False for missing, truncated,
    partial or corrupt checkpoints — never raises."""
    try:
        if _is_manifest_file(path):
            m = _read_manifest(path)
            d = os.path.dirname(os.path.abspath(path))
            members: Dict[str, set] = {}
            for entry in m["leaves"].values():
                covered = 0
                for sh in entry["shards"]:
                    members.setdefault(sh["file"], set()).add(sh["key"])
                    n = 1
                    for start, stop in sh["index"]:
                        n *= max(int(stop) - int(start), 0)
                    covered += n
                total = 1
                for dim in entry["shape"]:
                    total *= int(dim)
                if covered != total:
                    return False  # partial shard set (torn save)
            for fname, keys in members.items():
                fpath = os.path.join(d, fname)
                with zipfile.ZipFile(fpath) as zf:
                    if zf.testzip() is not None:
                        return False
                    have = {os.path.splitext(n)[0] for n in zf.namelist()}
                    if not keys <= have:
                        return False
            return True
        with zipfile.ZipFile(path) as zf:
            return zf.testzip() is None
    except Exception:
        return False


# Publication hooks: called as ``hook(task_or_stem, path)`` after the atomic
# manifest rename lands a checkpoint, from whichever thread performed the
# write (the async writer thread for ``save_async``). The durability layer
# registers one to journal every publication; hooks must be cheap and must
# not raise.
_PUBLISH_HOOKS: list = []


def add_publish_hook(hook) -> None:
    _PUBLISH_HOOKS.append(hook)


def remove_publish_hook(hook) -> None:
    try:
        _PUBLISH_HOOKS.remove(hook)
    except ValueError:
        pass


def _notify_published(path: str) -> None:
    if not _PUBLISH_HOOKS:
        return
    stem = os.path.splitext(os.path.basename(path))[0]
    for hook in list(_PUBLISH_HOOKS):
        try:
            hook(stem, os.path.abspath(path))
        except Exception:
            log.exception("checkpoint publish hook failed for %s", path)


def _writer_rank(tree: Any) -> int:
    """The process that writes this tree's *manifest*: the lowest process
    index that addresses its arrays. For a cross-host sharded/replicated
    state that is the coordinator; for a state living entirely on one host's
    devices it is that host (the coordinator never even sees the tree — the
    multi-host engine only calls execute() on processes local to the task's
    block). Host-only trees (plain numpy) default to rank 0."""
    for leaf in jax.tree_util.tree_leaves(tree):
        ds = getattr(getattr(leaf, "sharding", None), "device_set", None)
        if ds:
            return min(getattr(d, "process_index", 0) for d in ds)
    return 0


def _should_write(tree: Any) -> bool:
    from saturn_tpu.core import distributed

    if not distributed.is_multihost():
        return True
    return distributed.process_index() == _writer_rank(tree)


def _my_rank() -> int:
    from saturn_tpu.core import distributed

    return distributed.process_index() if distributed.is_multihost() else 0


def _widens(dtype: Any) -> bool:
    # npz can't round-trip ml_dtypes (bfloat16/fp8); such a leaf is stored
    # as float32 — restore() narrows back to the template's dtype.
    return (np.dtype(dtype).kind == "V" or "bfloat16" in str(dtype)
            or "float8" in str(dtype))


def _stored_dtype(dtype: Any) -> np.dtype:
    """The dtype a leaf of ``dtype`` has on disk — from metadata alone, so
    the manifest is whole before a byte has left the device."""
    return np.dtype(np.float32) if _widens(dtype) else np.dtype(dtype)


def _stored(arr: np.ndarray) -> np.ndarray:
    return arr.astype(np.float32) if _widens(arr.dtype) else arr


def _norm_index(index: Tuple, shape: Tuple[int, ...]) -> Tuple[Tuple[int, int], ...]:
    """Resolve a ``devices_indices_map`` slice tuple against ``shape`` into
    concrete ``(start, stop)`` extents — the manifest's shard coordinates."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _pspec_fingerprint(tree: Any) -> str:
    """Stable digest of the tree's per-leaf partition specs — lets restore
    and the ``analysis ckpt`` CLI tell at a glance whether a checkpoint was
    written under the same layout (purely informational: restore reshards
    onto the destination regardless)."""
    items = []
    for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        items.append([_path_str(p), "host" if spec is None else str(spec)])
    canon = json.dumps(sorted(items), separators=(",", ":"))
    return hashlib.sha1(canon.encode("utf-8")).hexdigest()[:12]


class _Plan(NamedTuple):
    """The first stage of a save, from metadata alone (no device is touched
    and no array is copied): the global shard plan — the manifest body —
    and, in the order they are fetched (the largest first, so that what is
    left to write when the last one lands is small), the ``(member,
    source)`` pairs this process must fetch, each with the lane that writes
    it. A source is a single-device ``jax.Array`` (a shard this process
    owns; fetched by a pure local device→host copy, never a gather) or a
    host ``ndarray`` (written by the tree's writer rank)."""

    manifest: Dict[str, Any]
    fetch: List[Tuple[str, Any]]
    lane_of: List[int]  #: parallel to ``fetch``: index into ``files``
    files: List[str]  #: this rank's shard files (base names), one a lane
    rank: int
    gen: str
    writes_manifest: bool
    nbytes: int  #: bytes this process copies to the host and writes


def _assign_lanes(stem: str, shards: List[Tuple[int, Dict[str, Any]]]
                  ) -> Tuple[List[str], List[int], List[int]]:
    """Spread one rank's members over its lanes and name each one's file in
    its manifest entry. ``shards`` is ``(bytes, manifest shard entry)`` in
    tree order; the result is the rank's file names, one a lane, then the
    order to fetch in (indices into ``shards``, the largest first) and each
    fetched member's lane. Largest first, each to the lane with the fewest
    bytes so far, so that the lanes end together. Every process computes
    the same answer for every rank: nothing but the plan enters."""
    total = sum(n for n, _ in shards)
    lanes = 1 if total < _LANE_MIN_BYTES else min(_LANES, len(shards))
    files = ([f"{stem}.npz"] if lanes == 1
             else [f"{stem}.l{k}.npz" for k in range(lanes)])
    order = sorted(range(len(shards)), key=lambda i: -shards[i][0])  # stable
    held = [0] * lanes
    lane_of = []
    for i in order:
        k = held.index(min(held))
        held[k] += shards[i][0]
        shards[i][1]["file"] = files[k]
        lane_of.append(k)
    return files, order, lane_of


def _plan(path: str, tree: Any) -> _Plan:
    gen = f"{time.time_ns():x}"
    rank = _my_rank()
    wrank = _writer_rank(tree)
    base = os.path.basename(path)
    leaves: Dict[str, Any] = {}
    # every rank's members, as (bytes, manifest shard entry), in tree order;
    # the sources of this rank's, in the same order
    owned: Dict[int, List[Tuple[int, Dict[str, Any]]]] = {}
    sources: List[Any] = []
    for tpath, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _path_str(tpath)
        if key in leaves:
            raise ValueError(f"duplicate tree path key: {key!r}")
        sharding = getattr(leaf, "sharding", None)
        on_device = (sharding is not None
                     and hasattr(sharding, "devices_indices_map"))
        if not on_device:
            leaf = np.asarray(leaf)  # python scalar -> 0-d; ndarray: no copy
        shape = tuple(int(s) for s in leaf.shape)
        stored_dtype = _stored_dtype(leaf.dtype)
        shards: List[Dict[str, Any]] = []
        if on_device:
            # Global layout from metadata alone: devices_indices_map is
            # identical on every process, so each rank derives the same
            # plan with zero communication. Replicas dedupe to one owner
            # (lowest (process, device id)) so each block is written once.
            groups: Dict[Tuple, list] = {}
            for dev, index in sharding.devices_indices_map(shape).items():
                groups.setdefault(_norm_index(index, shape), []).append(dev)
            by_dev_id = {
                s.device.id: s for s in getattr(leaf, "addressable_shards", [])
            }
            for i, extent in enumerate(sorted(groups)):
                owner = min(
                    groups[extent],
                    key=lambda d: (getattr(d, "process_index", 0),
                                   getattr(d, "id", 0)),
                )
                orank = getattr(owner, "process_index", 0)
                shards.append({
                    "index": [[a, b] for a, b in extent],
                    "file": None,  # its rank's lane: _assign_lanes
                    "key": f"{key}#s{i}",
                })
                owned.setdefault(orank, []).append((
                    stored_dtype.itemsize * math.prod(
                        b - a for a, b in extent), shards[-1]))
                if orank == rank:
                    sources.append(by_dev_id[getattr(owner, "id", 0)].data)
        else:
            # Host (plain numpy / python scalar) leaf: one full-extent
            # shard, written by the tree's writer rank.
            shards.append({
                "index": [[0, d] for d in shape],
                "file": None,
                "key": f"{key}#s0",
            })
            owned.setdefault(wrank, []).append(
                (leaf.size * stored_dtype.itemsize, shards[-1]))
            if rank == wrank:
                sources.append(leaf)
        leaves[key] = {
            "shape": list(shape),
            "dtype": str(leaf.dtype),
            "stored_dtype": str(stored_dtype),
            "shards": shards,
        }
    files: List[str] = []
    fetch: List[Tuple[str, Any]] = []
    lane_of: List[int] = []
    for r, mine in owned.items():
        r_files, order, r_lane_of = _assign_lanes(f"{base}.g{gen}.r{r}", mine)
        if r == rank:
            files, lane_of = r_files, r_lane_of
            fetch = [(mine[i][1]["key"], sources[i]) for i in order]
    manifest = {
        "format": MANIFEST_FORMAT,
        "version": MANIFEST_VERSION,
        "generation": gen,
        "pspec_fingerprint": _pspec_fingerprint(tree),
        "leaves": leaves,
    }
    nbytes = sum(n for n, _ in owned.get(rank, ()))
    return _Plan(manifest, fetch, lane_of, files, rank, gen, rank == wrank,
                 nbytes)


def _gc_stale_generations(path: str, keep_gen: str) -> None:
    """After the manifest rename lands, older generations' shard files are
    unreachable — remove them (best-effort; a crash here only leaks disk,
    never correctness)."""
    for f in glob.glob(glob.escape(path) + ".g*.npz"):
        m = _SHARD_RE.search(f)
        if m and m.group(1) != keep_gen:
            try:
                os.unlink(f)
            except OSError:
                log.warning("could not GC stale checkpoint shard %s", f)


# ------------------------------------------------------------- the pipeline
# A save is three stages. (1) ``_plan``: the manifest, the list of members to
# fetch (the largest first) and each one's lane, from metadata. (2) The
# caller's thread copies the shards to the host one after another, in plan
# order, and hands each to its lane as it lands. (3) The lanes, a thread and
# a shard file each, started before the first shard is waited for, put each
# member into their file as it arrives; when every lane has ended, the
# save's one writer thread commits exactly as before: shard renames, then
# (manifest writer only) manifest rename, publication, GC. End-of-interval
# checkpoints are GB-scale (full train state incl. optimizer): a save that
# is joined right away — the last interval of any job, every interval of a
# multi-host one — costs the longer of copy and write, not their sum, and
# with lanes enough (``_LANES``) the write is the shorter of the two.
#
# The caller's thread is held exactly until its last local shard is on the
# host (the engine may donate the buffers into the next interval's first
# step), and never by a lane: the hand-off queues are unbounded. One
# writer thread per path (``ckpt-<base>``, the one in ``_PENDING``);
# restore() and a second save to the same path wait for the in-flight write
# first. A failed write is recorded per path and re-raised at the next join
# point (exists/restore/save_async/flush) — a checkpoint that never hit
# disk must not be silently reported as saved.
#
# Who ends whom. The writer thread starts the lanes (daemon threads named
# ``ckpt-<base>.l<k>``) and returns only when every one has: joining it is
# joining the whole save, and no thread, pool or process outlives a save.
# A lane blocks in one place, its inbox, and a mark is certain to come
# there: ``_LANDED_ALL`` or ``_FETCH_FAILED`` from the caller's thread on
# every way out of its loop, ``_STOPPED`` from whichever thread failed first
# (another lane, the writer thread). The first error is the save's error;
# the others stop at their next member, every temp file and every
# file of the new generation is removed, and nothing is committed.
_PENDING: Dict[str, threading.Thread] = {}
_FAILED: Dict[str, BaseException] = {}
_PENDING_LOCK = threading.Lock()

_LANDED_ALL = object()  # hand-off marks: every member is in the queues /
_FETCH_FAILED = object()  # a fetch raised, commit nothing /
_STOPPED = object()  # another thread of the save failed, commit nothing


class _SaveAborted(Exception):
    """Raised on a lane or the writer thread when the save was given up
    elsewhere: the thread that gave it up raises what stopped it."""


class _Lane:
    """One shard file of a save in flight and the queue that feeds it:
    ``inbox`` carries ``(member, ndarray)`` in plan order and then a mark."""

    __slots__ = ("file", "inbox", "starved_s", "tmp", "nbytes", "n_members")
    # ``nbytes`` / ``n_members``: what the lane has put into its file so far

    def __init__(self, file: str):
        self.file = file
        self.inbox: "queue.SimpleQueue" = queue.SimpleQueue()
        self.starved_s = 0.0
        self.tmp: Optional[str] = None  # the staged file, until its rename
        self.nbytes = 0
        self.n_members = 0


class _Stream:
    """One save in flight: what the caller's thread, the writer thread and
    the lanes share. ``snapshot_end`` (``perf_counter``) is stamped before
    the marks are put; ``stop`` is set once nothing will be committed;
    ``error`` is what the save died of (the first failure, kept)."""

    __slots__ = ("lanes", "thread", "error", "stop", "snapshot_end", "_lock")

    def __init__(self, files: List[str]):
        self.lanes = [_Lane(f) for f in files]
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        self.stop = threading.Event()
        self.snapshot_end: Optional[float] = None
        self._lock = threading.Lock()

    def landed(self, lane: _Lane):
        """Lane side: its members as they arrive, until the mark."""
        while True:
            t0 = time.perf_counter()
            item = lane.inbox.get()
            lane.starved_s += time.perf_counter() - t0
            if self.stop.is_set() or item is _FETCH_FAILED:
                raise _SaveAborted()
            if item is _LANDED_ALL:
                return
            yield item

    def _mark(self, mark: object) -> None:
        for lane in self.lanes:
            lane.inbox.put(mark)

    def end_snapshot(self, mark: object) -> None:
        """Caller's side, on every way out of its loop."""
        self.snapshot_end = time.perf_counter()
        if mark is not _LANDED_ALL:
            self.stop.set()
        self._mark(mark)

    def fail(self, err: BaseException) -> None:
        """A lane or the writer thread died of ``err``: keep the first
        error, stop the save, wake every lane that waits for a member."""
        with self._lock:
            if self.error is None:
                self.error = err
        self.stop.set()
        self._mark(_STOPPED)


def _write_member(zf: zipfile.ZipFile, member: str, arr: np.ndarray,
                  piece: np.ndarray) -> None:
    """One member of a shard file, as ``np.savez`` writes it (the same
    ``.npy`` header, the same bytes, the zip's CRC-32 over them), handed to
    the file through ``piece``, the lane's one reused buffer. Both other
    ways were measured on the benchmark's host (PR 46, ``tools/
    ckpt_lanes.py``, 7.3 GB on four lanes): ``write_array`` allocates a
    16 MiB copy of every piece it writes, and the save is 3.6-3.7 s where
    it is 3.1-3.2 through a reused one (fresh pages are what copies and
    writes contend for there); a ``write`` straight from the array's own
    buffer, the memory the device→host copy has just filled, holds the
    next copy back (8-10 s)."""
    with zf.open(member + ".npy", "w", force_zip64=True) as fid:
        if not arr.flags.c_contiguous or arr.dtype.kind not in "biufc":
            np.lib.format.write_array(fid, arr, allow_pickle=False)
            return
        np.lib.format.write_array_header_1_0(
            fid, np.lib.format.header_data_from_array_1_0(arr))
        raw = arr.reshape(-1).view(np.uint8)
        for at in range(0, raw.size, piece.size):
            n = min(piece.size, raw.size - at)
            np.copyto(piece[:n], raw[at:at + n])
            fid.write(piece[:n])


def _run_lane(d: str, plan: _Plan, stream: _Stream, lane: _Lane,
              above: Optional[metrics.span]) -> None:
    """A lane's thread: stage its shard file member by member as its shards
    land, and cross ``mid-shard-write`` once the bytes are staged. The
    rename is the writer thread's. Never raises: what it died of stops the
    save (``stream.fail``)."""
    try:
        with metrics.span("ckpt.lane", parent=above,
                          file=lane.file) as wrote:
            try:
                fd, lane.tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
                # what np.savez does per member, one member at a time: the
                # file is the same .npz
                piece = np.empty(_PIECE_BYTES, np.uint8)
                with os.fdopen(fd, "wb") as f, \
                        zipfile.ZipFile(f, "w", allowZip64=True) as zf:
                    for member, arr in stream.landed(lane):
                        _write_member(zf, member, arr, piece)
                        lane.nbytes += arr.nbytes
                        lane.n_members += 1
                _barrier("mid-shard-write", path=os.path.join(d, lane.file),
                         tmp=lane.tmp, gen=plan.gen)
            finally:
                wrote.set(bytes=lane.nbytes, n_members=lane.n_members,
                          starved_s=lane.starved_s)
    except _SaveAborted:
        pass
    except BaseException as e:  # SimulatedKill included
        stream.fail(e)


def _commit_streamed(path: str, plan: _Plan, stream: _Stream,
                     above: Optional[metrics.span]) -> None:
    """The disk half of a save, on the writer thread: run this rank's lanes
    to their end, rename every lane's shard file, then (manifest writer
    only) stage + rename the manifest — the atomic commit point — and
    notify publication. Crash-barrier crossings precede both kinds of
    rename; a kill at either leaves the previous generation untouched.
    Returns, or raises, only after every lane it started has ended."""
    d = os.path.dirname(os.path.abspath(path))
    base = os.path.basename(path)
    lanes = [threading.Thread(target=_run_lane, name=f"ckpt-{base}.l{k}",
                              args=(d, plan, stream, lane, above),
                              daemon=True)
             for k, lane in enumerate(stream.lanes)]
    started: List[threading.Thread] = []
    renamed: List[str] = []
    committed = False
    try:
        try:
            os.makedirs(d, exist_ok=True)
            for t in lanes:
                t.start()
                started.append(t)
        except BaseException as e:
            stream.fail(e)
        for t in started:
            t.join()
        if stream.error is not None:
            raise stream.error
        if stream.stop.is_set():
            raise _SaveAborted()
        for lane in stream.lanes:
            fname = os.path.join(d, lane.file)
            os.replace(lane.tmp, fname)
            renamed.append(fname)
        if not plan.writes_manifest:
            committed = True
            return
        body = dict(plan.manifest)
        body["checksum"] = _manifest_checksum(body)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(body, f, separators=(",", ":"))
            _barrier("pre-manifest-rename", path=path, tmp=tmp, gen=plan.gen)
            os.replace(tmp, path)  # atomic: no torn checkpoints on crash
            committed = True
            _notify_published(path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    finally:
        doomed = [lane.tmp for lane in stream.lanes if lane.tmp]
        if not committed:
            doomed += renamed  # of a generation no manifest will name
        for f in doomed:
            try:
                os.unlink(f)
            except OSError:
                pass  # renamed away, or never made
    _gc_stale_generations(path, plan.gen)


def _wait_pending(path: str) -> None:
    key = os.path.abspath(path)
    with _PENDING_LOCK:
        t = _PENDING.get(key)
    if t is not None:
        t.join()
    with _PENDING_LOCK:
        err = _FAILED.pop(key, None)
    if err is not None:
        raise RuntimeError(f"async checkpoint write to {path} failed") from err


def _record_async_failure(key: str, path: str, err: BaseException) -> None:
    """Park a background-write failure for the next join point. Keep-first:
    if an earlier failure for this path is still unconsumed, the new one is
    logged and dropped — the first error is the root cause a join point
    must surface (the engine's ``_record_error(keep_first)`` convention)."""
    with _PENDING_LOCK:
        prev = _FAILED.get(key)
        if prev is not None:
            log.warning(
                "async checkpoint write to %s failed again (%r); keeping "
                "first error %r", path, err, prev,
            )
        else:
            _FAILED[key] = err


def _fetch(source: Any) -> np.ndarray:
    """One planned source as the host array the shard file stores: a device
    shard's pure local device→host copy, waited for (a host leaf is there
    already)."""
    return _stored(np.asarray(source))


def _start_save(path: str, tree: Any, park_failure: bool) -> _Stream:
    """Run the pipeline up to the moment the last local shard is on the
    host and handed to its lane; the writer thread and its lanes are still
    running on return. A fetch that raises stops them (nothing is
    committed, the temp files are removed) and propagates from here. What
    the *writer* or a lane dies of is left in ``stream.error`` and, with
    ``park_failure``, parked for the path's next join point."""
    base = os.path.basename(path)
    key = os.path.abspath(path)
    # ``ckpt.write`` is its snapshot's sibling, not its child: the two
    # overlap, and a span's self time is its duration minus its children.
    # The lanes' ``ckpt.lane`` are its siblings for the same reason.
    above = metrics.current_span()
    # Both stretches are on the caller's thread — the gang's: training is
    # stalled for as long as they last (``ckpt_stall`` reads them).
    with metrics.span("ckpt.wait_pending", path=base):
        _wait_pending(path)  # at most one in-flight write per path
    with metrics.span("ckpt.snapshot", path=base) as snapped:
        plan = _plan(path, tree)
        stream = _Stream(plan.files)

        def write():
            try:
                with metrics.span("ckpt.write", parent=above, path=base,
                                  bytes=plan.nbytes,
                                  n_shards=len(plan.fetch),
                                  lanes=len(stream.lanes)) as wrote:
                    t0 = time.perf_counter()
                    try:
                        _commit_streamed(path, plan, stream, above)
                    finally:
                        t1 = time.perf_counter()
                        end = stream.snapshot_end  # None: still snapshotting
                        under = t1 if end is None else min(t1, end)
                        wrote.set(overlap_s=max(under - t0, 0.0),
                                  starved_s=max(
                                      (l.starved_s for l in stream.lanes),
                                      default=0.0))
            except _SaveAborted:
                pass  # the caller's thread raises what stopped it
            except BaseException as e:  # re-raised at a join point
                stream.fail(e)
                if park_failure:
                    log.exception("async checkpoint write to %s failed", path)
                    _record_async_failure(key, path, stream.error)
            finally:
                with _PENDING_LOCK:
                    if _PENDING.get(key) is threading.current_thread():
                        del _PENDING[key]

        stream.thread = threading.Thread(target=write, name=f"ckpt-{base}",
                                         daemon=True)
        n_streamed = 0
        try:
            with _PENDING_LOCK:
                _PENDING[key] = stream.thread
            stream.thread.start()
            for (member, source), k in zip(plan.fetch, plan.lane_of):
                if stream.stop.is_set():
                    break  # the write failed: nothing left to feed
                stream.lanes[k].inbox.put((member, _fetch(source)))
                n_streamed += 1
        except BaseException:
            stream.end_snapshot(_FETCH_FAILED)
            if stream.thread.ident is not None:  # it was started
                stream.thread.join()
            with _PENDING_LOCK:
                if _PENDING.get(key) is stream.thread:
                    del _PENDING[key]
            raise
        stream.end_snapshot(_LANDED_ALL)
        snapped.set(bytes=plan.nbytes, n_streamed=n_streamed)
    return stream


def save(path: str, tree: Any) -> None:
    """Atomically write a sharded pytree checkpoint rooted at ``path``.

    Each process pulls only its locally-addressable shards to host (no
    collective of any kind) and writes them to its own generation-tagged
    shard file; the tree's writer rank additionally commits the manifest.
    The manifest rename is the commit point — a crash at any earlier moment
    leaves the previously published checkpoint fully readable.

    The same pipeline as ``save_async``, joined before it returns; what
    the writer thread raised (a crash barrier's kill included) is raised
    here."""
    stream = _start_save(path, tree, park_failure=False)
    with metrics.span("ckpt.flush", path=os.path.basename(path), n_pending=1):
        stream.thread.join()
    if stream.error is not None:
        raise stream.error


def save_async(path: str, tree: Any) -> None:
    """``save`` with the end of the disk write off the critical path.

    Blocks until the last local shard is on the host — the device→host
    copies back to back, the writer thread already putting the shards that
    have landed into the shard file — and no longer; the rest of the file,
    the manifest and the atomic renames happen in the writer thread. A
    crash mid-write leaves the previous checkpoint intact (same commit
    discipline as ``save``). ``flush()`` joins all outstanding writes; a
    failed write re-raises from the next join point on the same path (or
    ``flush``). A host leaf is written from the caller's own array: it must
    not change before the write is joined.

    Multi-host: every participating process streams its OWN shards (pure
    local copies — the sharded format removed the old collective gather)
    into its own shard file; only the tree's writer rank
    (``_writer_rank`` — lowest process addressing it) commits the manifest,
    after its own shard file. The multi-host engine flushes + barriers at
    interval end so readers never race the write (``engine.py``)."""
    _start_save(path, tree, park_failure=True)


def flush() -> None:
    """Join every outstanding async write; re-raise the first failure."""
    with _PENDING_LOCK:
        threads = list(_PENDING.values())
    with metrics.span("ckpt.flush", n_pending=len(threads)):
        for t in threads:
            t.join()
    with _PENDING_LOCK:
        errs = dict(_FAILED)
        _FAILED.clear()
    for path, err in errs.items():
        raise RuntimeError(f"async checkpoint write to {path} failed") from err


# -------------------------------------------------------------------- restore
class _ShardReader:
    """Lazily-opened shard files for one manifest; at most one ``NpzFile``
    per shard file stays open, so assembly is O(one leaf) of extra host
    memory, never the full tree."""

    def __init__(self, path: str):
        self._dir = os.path.dirname(os.path.abspath(path))
        self._open: Dict[str, Any] = {}

    def member(self, fname: str, key: str) -> np.ndarray:
        npz = self._open.get(fname)
        if npz is None:
            npz = np.load(os.path.join(self._dir, fname))
            self._open[fname] = npz
        return npz[key]

    def close(self) -> None:
        for npz in self._open.values():
            try:
                npz.close()
            except Exception:
                pass
        self._open.clear()


def _assemble_block(entry: Dict[str, Any], reader: _ShardReader,
                    block: Tuple[Tuple[int, int], ...],
                    dtype: Any) -> np.ndarray:
    """Materialize one hyper-rectangular block of a leaf from its shards
    (the lazy per-shard assembly ``restore_sharded`` builds device arrays
    from). ``block`` is concrete ``(start, stop)`` extents; a block exactly
    matching one source shard is returned without a copy beyond the dtype
    cast."""
    shape = tuple(bl[1] - bl[0] for bl in block)
    for sh in entry["shards"]:
        if tuple((int(a), int(b)) for a, b in sh["index"]) == block:
            arr = reader.member(sh["file"], sh["key"]).astype(dtype, copy=False)
            # NOT ascontiguousarray: that helper promotes 0-d to 1-d,
            # breaking scalar leaves like ``step``; npz members are
            # already contiguous.
            return arr
    out = np.empty(shape, dtype=dtype)
    covered = 0
    for sh in entry["shards"]:
        src_sel, dst_sel, n = [], [], 1
        for (bs, be), (ss, se) in zip(block, sh["index"]):
            lo, hi = max(bs, int(ss)), min(be, int(se))
            if lo >= hi:
                n = 0
                break
            src_sel.append(slice(lo - int(ss), hi - int(ss)))
            dst_sel.append(slice(lo - bs, hi - bs))
            n *= hi - lo
        if n == 0:
            continue
        data = reader.member(sh["file"], sh["key"])
        out[tuple(dst_sel)] = data[tuple(src_sel)].astype(dtype, copy=False)
        covered += n
    total = 1
    for dim in shape:
        total *= dim
    if covered < total:
        raise ValueError(
            f"shard set does not cover requested block {block} "
            f"({covered}/{total} elements)"
        )
    return out


def _full_extent(shape) -> Tuple[Tuple[int, int], ...]:
    return tuple((0, int(d)) for d in shape)


def _load_manifest_arrays(path: str,
                          manifest: Dict[str, Any]) -> Dict[str, np.ndarray]:
    reader = _ShardReader(path)
    try:
        out: Dict[str, np.ndarray] = {}
        for key, entry in manifest["leaves"].items():
            out[key] = _assemble_block(
                entry, reader, _full_extent(entry["shape"]),
                np.dtype(entry["stored_dtype"]),
            )
        return out
    finally:
        reader.close()


def load_arrays(path: str) -> Dict[str, np.ndarray]:
    """Read a checkpoint (either format) as a flat ``{tree/path: ndarray}``
    dict of full host arrays in *stored* dtype (bf16/fp8 leaves come back
    float32-widened, exactly as the legacy ``np.load`` view did) — the
    drop-in replacement for code that used to ``np.load`` the checkpoint
    file directly. Joins any in-flight async write; quarantines + raises
    :class:`CheckpointCorruptError` on unreadable/partial checkpoints."""
    _wait_pending(path)
    # Absent is not corrupt: callers branch on exists(). Only the *root*
    # file's absence means absent — a missing shard file below IS corruption
    # (partial shard set) and takes the quarantine path.
    is_manifest = _is_manifest_file(path)
    try:
        if is_manifest:
            return _load_manifest_arrays(path, _read_manifest(path))
        with np.load(path) as data:
            return {k: data[k] for k in data.files}
    except Exception as e:
        # Truncated / torn / bit-rotted manifest or shard set: quarantine
        # the checkpoint so the next reader (and crash recovery) falls back
        # to the previous one instead of re-hitting the same unreadable
        # file. Shard files of the quarantined generation are swept by the
        # next successful save's GC.
        sidecar = quarantine(path)
        log.warning("checkpoint %s unreadable (%r); quarantined to %s",
                    path, e, sidecar)
        raise CheckpointCorruptError(path, sidecar, repr(e)) from e


def restore(path: str, template: Any) -> Any:
    """Map saved arrays onto ``template``'s structure (host numpy leaves).

    ``template`` is a freshly-initialized train state (any technique's); leaves
    are replaced by the saved arrays with dtype preserved from the template so
    a bf16 param set restores as bf16 even though numpy stored it widened.

    Multi-host: the writer rank's _wait_pending joins its own in-flight
    write; OTHER ranks rely on the engine's interval-end flush+barrier
    (``engine._execute_multihost``) having run before any cross-rank read —
    no collective here, because a task local to one host restores on that
    host alone and a cluster-wide barrier would deadlock.
    """
    saved = load_arrays(path)

    def replace(tree_path, leaf):
        key = _path_str(tree_path)
        if key not in saved:
            raise KeyError(
                f"checkpoint at {path!r} missing array for tree path {key!r}"
            )
        arr = saved[key]
        want_dtype = getattr(leaf, "dtype", arr.dtype)
        want_shape = getattr(leaf, "shape", arr.shape)
        if tuple(arr.shape) != tuple(want_shape):
            raise ValueError(
                f"shape mismatch at {key!r}: saved {arr.shape} vs template {want_shape}"
            )
        return arr.astype(want_dtype)

    return jax.tree_util.tree_map_with_path(replace, template)


def _resolve_sharding(sharding: Any, template: Any):
    """Normalize the three ``restore_sharded`` sharding forms into a
    per-leaf callable ``(tree_path, shape_dtype) -> Sharding``."""
    if isinstance(sharding, jax.sharding.Sharding):
        # isinstance check FIRST: Sharding subclasses may be callable.
        return lambda p, sds: sharding
    if callable(sharding):
        return sharding
    by_key = {
        _path_str(p): s
        for p, s in jax.tree_util.tree_flatten_with_path(sharding)[0]
    }
    return lambda p, sds: by_key[_path_str(p)]


def _place_leaf(entry: Dict[str, Any], reader: _ShardReader,
                dst_sharding: Any, dtype: Any) -> Any:
    """Build one destination device array from source shards, assembling
    only the block each destination device actually needs. Falls back to
    full-leaf host assembly + ``device_put`` for non-device memory kinds
    (offloaded ``pinned_host`` state), where callback-placement support
    varies by backend."""
    shape = tuple(int(d) for d in entry["shape"])
    mk = getattr(dst_sharding, "memory_kind", None)
    if mk not in (None, "device"):
        full = _assemble_block(entry, reader, _full_extent(shape), dtype)
        return jax.device_put(full, dst_sharding)

    def cb(index):
        return _assemble_block(entry, reader, _norm_index(index, shape), dtype)

    return jax.make_array_from_callback(shape, dst_sharding, cb)


def restore_sharded(path: str, template: Any, sharding: Any) -> Any:
    """``restore`` + place every leaf on devices under ``sharding``.

    This is the cross-mesh migration primitive: a checkpoint written on one
    mesh shape restores onto a *different* one (half the devices after a
    slice preemption, twice after a grow), because the manifest holds
    mesh-agnostic ``(start, stop)`` extents keyed by tree path — nothing
    about the old mesh constrains the destination. For manifest checkpoints
    each leaf is assembled lazily per destination shard
    (``jax.make_array_from_callback``), so no host materializes the full
    replicated tree; legacy single-file checkpoints take the compat
    full-host path. ``sharding`` is one of:

    - a single ``jax.sharding.Sharding`` applied to every leaf (the common
      fully-replicated / uniform case),
    - a pytree of shardings matching ``template``'s structure,
    - a callable ``(tree_path, leaf_like) -> Sharding`` for per-leaf rules
      (``leaf_like`` has ``shape``/``dtype``/``ndim``).
    """
    _wait_pending(path)
    try:
        is_manifest = _is_manifest_file(path)
    except FileNotFoundError:
        raise
    if not is_manifest:
        host = restore(path, template)
        rule = _resolve_sharding(sharding, template)
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: jax.device_put(leaf, rule(p, leaf)), host
        )

    try:
        manifest = _read_manifest(path)
    except Exception as e:
        sidecar = quarantine(path)
        log.warning("checkpoint %s unreadable (%r); quarantined to %s",
                    path, e, sidecar)
        raise CheckpointCorruptError(path, sidecar, repr(e)) from e

    rule = _resolve_sharding(sharding, template)
    leaves = manifest["leaves"]
    reader = _ShardReader(path)
    try:

        def place(tree_path, tleaf):
            key = _path_str(tree_path)
            if key not in leaves:
                raise KeyError(
                    f"checkpoint at {path!r} missing array for tree path "
                    f"{key!r}"
                )
            entry = leaves[key]
            want_shape = tuple(getattr(tleaf, "shape", entry["shape"]))
            if tuple(entry["shape"]) != want_shape:
                raise ValueError(
                    f"shape mismatch at {key!r}: saved "
                    f"{tuple(entry['shape'])} vs template {want_shape}"
                )
            dtype = getattr(tleaf, "dtype", np.dtype(entry["stored_dtype"]))
            sds = jax.ShapeDtypeStruct(want_shape, dtype)
            return _place_leaf(entry, reader, rule(tree_path, sds), dtype)

        return jax.tree_util.tree_map_with_path(place, template)
    except (CheckpointCorruptError, KeyError, ValueError):
        raise
    except Exception as e:
        # A manifest that parsed but whose shard set is missing/torn on
        # read: quarantine so recovery falls back, same as load_arrays.
        sidecar = quarantine(path)
        log.warning("checkpoint %s shard set unreadable (%r); quarantined "
                    "to %s", path, e, sidecar)
        raise CheckpointCorruptError(path, sidecar, repr(e)) from e
    finally:
        reader.close()


def exists(path: str) -> bool:
    """True if a checkpoint exists (joining any in-flight async write first,
    so a just-scheduled save counts).

    Multi-host: consistency across ranks comes from the engine's
    interval-end flush+barrier — by the time any rank asks, the shared-FS
    file is durable, so every rank reads the same answer with no
    collective (which would deadlock for host-local tasks)."""
    _wait_pending(path)
    return os.path.exists(path)


def delete(path: str) -> None:
    """Remove a checkpoint: the manifest (or legacy single file) plus every
    generation's shard files. Quarantine sidecars are kept (they are
    evidence, not state). Missing paths are fine; joins any in-flight
    async write first so a just-scheduled save doesn't resurrect files."""
    try:
        _wait_pending(path)
    except RuntimeError:
        pass  # a failed write is moot — we are deleting the target
    try:
        os.unlink(path)
    except FileNotFoundError:
        pass
    for f in glob.glob(glob.escape(path) + ".g*.npz"):
        if _SHARD_RE.search(f):
            try:
                os.unlink(f)
            except OSError:
                log.warning("could not remove checkpoint shard %s", f)


# ------------------------------------------------------------------ CLI views
def summarize(path: str) -> Dict[str, Any]:
    """One checkpoint's manifest summary for ``python -m saturn_tpu.analysis
    ckpt``: format, shard/leaf counts, on-disk bytes, pspec fingerprint and
    verification verdict. Never raises — unreadable checkpoints report
    ``ok: False``."""
    out: Dict[str, Any] = {"path": path, "ok": False, "format": None,
                           "leaves": 0, "shards": 0, "shard_files": 0,
                           "bytes": 0, "pspec_fingerprint": None,
                           "generation": None}
    try:
        out["bytes"] = os.path.getsize(path)
        if _is_manifest_file(path):
            out["format"] = "sharded-manifest"
            m = _read_manifest(path)
            out["generation"] = m.get("generation")
            out["pspec_fingerprint"] = m.get("pspec_fingerprint")
            out["leaves"] = len(m["leaves"])
            d = os.path.dirname(os.path.abspath(path))
            files = set()
            for entry in m["leaves"].values():
                out["shards"] += len(entry["shards"])
                files.update(sh["file"] for sh in entry["shards"])
            out["shard_files"] = len(files)
            for fname in files:
                fpath = os.path.join(d, fname)
                if os.path.exists(fpath):
                    out["bytes"] += os.path.getsize(fpath)
        else:
            out["format"] = "legacy-npz"
            with np.load(path) as data:
                out["leaves"] = len(data.files)
                out["shards"] = len(data.files)
            out["shard_files"] = 1
        out["ok"] = verify(path)
    except Exception as e:
        out["error"] = repr(e)
    return out


def summarize_dir(directory: str) -> Dict[str, Any]:
    """Directory-level checkpoint inventory: every checkpoint (manifest or
    legacy), corrupt sidecars, and orphan shard files no manifest owns."""
    directory = os.path.abspath(directory)
    checkpoints, sidecars, shard_files = [], [], set()
    referenced = set()
    for name in sorted(os.listdir(directory)):
        full = os.path.join(directory, name)
        if not os.path.isfile(full):
            continue
        if ".corrupt" in name:
            sidecars.append(name)
            continue
        if _SHARD_RE.search(name):
            shard_files.add(name)
            continue
        if name.endswith(".npz"):
            summ = summarize(full)
            checkpoints.append(summ)
            if summ.get("format") == "sharded-manifest" and summ.get("ok"):
                try:
                    m = _read_manifest(full)
                    for entry in m["leaves"].values():
                        referenced.update(sh["file"] for sh in entry["shards"])
                except Exception:
                    pass
    return {
        "dir": directory,
        "checkpoints": checkpoints,
        "corrupt_sidecars": sidecars,
        "orphan_shards": sorted(shard_files - referenced),
        "total_bytes": sum(c.get("bytes", 0) for c in checkpoints),
    }
