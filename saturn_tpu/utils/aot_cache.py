"""On-disk cache of AOT-compiled XLA executables (serialized, reloadable).

Opt-in companion of JAX's persistent *compilation* cache
(``profile_cache.maybe_enable_persistent_compile_cache``) for the programs
saturn_tpu itself builds: each
``jit(...).lower(...)`` result is keyed by a content hash of its OWN HLO
text plus the runtime identity (jax version, backend, device kinds/count,
machine), and the compiled executable is serialized with
``jax.experimental.serialize_executable`` into a subdirectory of the
persistent profile-cache directory. On restart — the recovery replay path,
or an online admission re-building a previously-seen program — the
executable is deserialized instead of recompiled, cutting the cold-start
compile tax that dominates both paths.

Every failure mode (missing file, pickle/deserialize error, device-set
mismatch, API drift) degrades to a recompile, never an error: a wrong or
unloadable entry costs exactly what not having the cache costs. Entries are
plain pickle files in a local trusted cache directory — the same trust
domain as the profile entries beside them; delete the directory to
invalidate everything.

Environment:

- ``SATURN_TPU_AOT_CACHE=1`` turns the on-disk cache on; unset or ``=0`` it
  is off on every backend. It was once on by default on a TPU backend only,
  a default no test ever ran; JAX's own persistent compilation cache
  (``profile_cache.maybe_enable_persistent_compile_cache``) is what a chip
  run gets by default now. On XLA:CPU the hazard of a shared cache stands —
  AOT-loaded machine code from an execution context with different CPU
  feature detection executes anyway ("machine type doesn't match" is a
  warning, not an error) and silently wedges collective programs.
- ``SATURN_TPU_PROFILE_CACHE=0`` (the global profile-cache kill switch)
  disables it too, since it lives inside that directory.
- ``SATURN_TPU_PROFILE_CACHE_DIR`` moves the root (the ``aot/`` subdir).

**Refusal records** (PR 29). A program the chip's compiler refuses for
memory (``RESOURCE_EXHAUSTED``: HBM over capacity, an allocation over
memory, a Mosaic kernel over its scoped VMEM) is the one compile outcome
JAX's persistent compilation cache does not keep, so every search paid for
it again in full. :func:`load_or_compile` keeps it: one small JSON file
``<compile cache directory>/saturn-refused/<key>.json`` with the compiler's
message, and the next compile of the same program raises
:class:`CompileRefused` with that message without calling the compiler.

- *Where*: inside JAX's persistent compilation cache directory, and on
  exactly when that cache is on
  (``profile_cache.maybe_enable_persistent_compile_cache()`` returns the
  directory; no switch of its own, independent of ``SATURN_TPU_AOT_CACHE``).
  Deleting the compile cache deletes the refusals.
- *Key*: the content hash of the program's own text, as for the executables,
  **without** the block's device ids (the verdict does not depend on which
  chips) and **with** the backend's ``platform_version`` (libtpu),
  ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``: a repaired kernel, a new block
  size, a new JAX or libtpu all miss, so a refusal cannot outlive its cause.
- *What is kept*: only what ``lowered.compile()`` itself raised. A
  ``RESOURCE_EXHAUSTED`` from *running* a program (an init, a step) depends
  on what else is on the chip and is never recorded; no other exception is.
  An unreadable or malformed record is a miss, never an error.
- *The program's own memory rule* (PR 42). A program the compiler accepts
  and ``SPMDTechnique._fits_compiled`` then rejects (over 0.92 x HBM) is a
  memory verdict of the same kind, and was the one that still cost a compile
  or a cache read of 50 MB in every search: :func:`reject` records it as a
  refusal (the message says whose it is) and takes the executable that
  compile left in JAX's cache out again, so that a cell's cache holds the
  programs it runs and not those it only weighs.
- *Point records* (PR 47). The key above exists only once a grid point has
  been built, traced and lowered, which is most of what a refused point
  still costs a search. ``utils/point_records`` keeps a second record beside
  these (``saturn-refused/point-<key>.json``), keyed by what the point is
  made from and guarded by a manifest of source files, that remembers the
  verdict reached here; ``SPMDTechnique.search`` asks it before it builds.
  This record stays the authority: the other is written only after the full
  path ended in a verdict, and a miss there leads back here.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import platform
import re
import threading
import weakref
from typing import Any, List, NamedTuple, Optional, Tuple

log = logging.getLogger("saturn_tpu")

_ENV_TOGGLE = "SATURN_TPU_AOT_CACHE"
_SUBDIR = "aot"
_REFUSED_SUBDIR = "saturn-refused"
#: What the compiler's three memory refusals have in common (HBM over
#: capacity, an allocation over memory, a kernel over its scoped VMEM).
_REFUSAL_MARK = "RESOURCE_EXHAUSTED"
_MESSAGE_CAP = 8192

#: Bump when the payload layout changes meaning — old entries then miss.
SCHEMA_VERSION = 1

_stats_lock = threading.Lock()
_stats = {"hits": 0, "misses": 0, "stores": 0, "errors": 0,
          "prewarms": 0, "warm_hits": 0,
          "refusals_fresh": 0, "refusals_replayed": 0,
          # grid points ended on their point record (``utils/point_records``)
          "refusals_unbuilt": 0}

# In-process warm pool fed by the compile-ahead service
# (``tenancy.compile_ahead``): executables compiled in the background
# between admission and first dispatch. Same-process, so none of the
# cross-context serialize/deserialize hazards apply — the warm pool is
# consulted even when the on-disk cache is disabled (CPU default).
_warm_lock = threading.Lock()
_warm: dict = {}


class CompileRefused(RuntimeError):
    """The chip's compiler refused the program for memory.

    Carries the compiler's own message (so text that looks for
    ``RESOURCE_EXHAUSTED`` still finds it). ``refusal`` says whether the
    compiler said so just now (``"fresh"``) or a record of an earlier
    compile of the same program did (``"recorded"``); ``program`` is the
    module's name (``jit_saturn_window``).
    """

    def __init__(self, message: str, refusal: str,
                 program: Optional[str] = None):
        super().__init__(message)
        self.refusal = refusal
        self.program = program

    @property
    def first_line(self) -> str:
        """The message's first line that says something, shortened."""
        for line in str(self).splitlines():
            if line.strip():
                return line.strip()[:300]
        return ""


def stats() -> dict:
    """Copy of the process-lifetime hit/miss counters (telemetry, tests)."""
    with _stats_lock:
        return dict(_stats)


def _bump(k: str) -> None:
    with _stats_lock:
        _stats[k] += 1


def enabled() -> bool:
    from saturn_tpu.utils import profile_cache as _pc

    raw = os.environ.get(_ENV_TOGGLE)
    if raw is None or raw.lower() in _pc._FALSEY:
        return False
    # riding inside the profile-cache directory means riding its kill switch
    return _pc.default_cache() is not None


def cache_dir() -> str:
    from saturn_tpu.utils import profile_cache as _pc

    return os.path.join(_pc.default_dir(), _SUBDIR)


def _fusion_version() -> int:
    """Fused-stacking machinery version (lazy: utils must not import
    parallel at module level; 0 = fusion unavailable)."""
    try:
        from saturn_tpu.parallel.fused import FUSION_SET_VERSION

        return int(FUSION_SET_VERSION)
    except Exception:
        return 0


def _runtime_identity() -> str:
    """Everything about the process that makes a serialized executable
    loadable: a hit compiled under a different jax, backend, device set or
    machine must miss (and would fail loudly at deserialize time anyway —
    the key check just makes the common case cheap)."""
    import jax

    from saturn_tpu.analysis import SCHEMA_VERSION as _ANALYSIS_SCHEMA
    from saturn_tpu.analysis.memlens import PASS_VERSION as _MEMLENS_PASS
    from saturn_tpu.analysis.shardflow import PASS_VERSION as _SHARDFLOW_PASS

    devs = jax.devices()
    return ";".join(
        [
            f"schema{SCHEMA_VERSION}",
            # analyzer rule-set version: diagnostics-driven plan repairs
            # must never deserialize executables cached under older rules
            f"lint{_ANALYSIS_SCHEMA}",
            # shardflow rule-set version: sharding findings gate what gets
            # compiled, so an executable cached under one rule set must
            # miss under another
            f"shardflow{_SHARDFLOW_PASS}",
            # memlens liveness-model version: static feasibility verdicts
            # gate what lowers at all, so executables cached under one
            # liveness model must miss under another
            f"memlens{_MEMLENS_PASS}",
            # fused-stacking version: the stacked step's HLO depends on the
            # fusion machinery, so executables cached under one stacked
            # program must miss when FUSION_SET_VERSION bumps
            f"fusion{_fusion_version()}",
            f"jax:{jax.__version__}",
            f"backend:{jax.default_backend()}",
            f"machine:{platform.machine()}",
            f"devices:{len(devs)}",
            "kinds:" + ",".join(sorted({getattr(d, "device_kind", "?") for d in devs})),
        ]
    )


class _Program(NamedTuple):
    """One pass over a lowered program's text: its content hash and name."""

    digest: bytes
    name: Optional[str]


_MODULE_NAME = re.compile(r"\s*module\s+@([\w.$-]+)")


def _program(lowered: Any) -> Optional[_Program]:
    """The text of ``lowered`` hashed once (None = it has no text)."""
    try:
        text = lowered.as_text()
    except Exception:
        return None
    m = _MODULE_NAME.match(text)
    return _Program(hashlib.sha256(text.encode()).digest(),
                    m.group(1) if m else None)


def _keyed(prog: _Program, *parts: str) -> str:
    """``prog``'s content hash under ``parts`` (what else the entry depends
    on), as a file name."""
    h = hashlib.sha256()
    for part in parts:
        h.update(part.encode())
        h.update(b"\x00")
    h.update(prog.digest)
    return h.hexdigest()


def _executable_key(prog: _Program, devices: Any) -> str:
    if devices is None:
        return _keyed(prog, _runtime_identity())
    ids = ",".join(str(getattr(d, "id", i)) for i, d in enumerate(devices))
    return _keyed(prog, _runtime_identity(), f"block:{ids}")


def cache_key(lowered: Any, devices: Any = None) -> Optional[str]:
    """Content key for a ``jit(...).lower(...)`` result; None = uncacheable.

    The HLO text pins the program (shapes, dtypes, shardings, donation all
    lower into it); the runtime identity pins where it can load. ``devices``
    (the concrete device block the program was lowered for) MUST be part of
    the key whenever the caller compiles the same program for different
    blocks: GSPMD sharding annotations use logical device indices, so the
    physical assignment lives only in the executable — loading a twin
    program pinned to a different block would silently run on the wrong
    chips.
    """
    prog = _program(lowered)
    return None if prog is None else _executable_key(prog, devices)


# ------------------------------------------------------------ refusal records
def _compiler_identity() -> List[str]:
    """What can change the compiler's verdict on one program text and is not
    in :func:`_runtime_identity`: the backend's own version (libtpu) and the
    two variables that hand flags to XLA and to libtpu."""
    import jax

    try:
        version = str(jax.devices()[0].client.platform_version)
    except Exception:
        version = "?"
    return [
        f"platform_version:{version}",
        "XLA_FLAGS:" + os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS:" + os.environ.get("LIBTPU_INIT_ARGS", ""),
    ]


def _refusal_path(prog: Optional[_Program]) -> Optional[str]:
    """Where the refusal of ``prog`` is or would be recorded: inside JAX's
    persistent compilation cache, so None wherever that cache is off."""
    from saturn_tpu.utils import profile_cache as _pc

    if prog is None:
        return None
    root = _pc.maybe_enable_persistent_compile_cache()
    if not root:
        return None
    key = _keyed(prog, _runtime_identity(), *_compiler_identity())
    return os.path.join(root, _REFUSED_SUBDIR, f"{key}.json")


def _read_refusal(path: str) -> Optional[str]:
    """The recorded message, or None: no record, or one that cannot be
    read as a refusal (a miss, never an error: the compiler is asked)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            record = json.load(f)
        message = record["message"]
    except (OSError, ValueError, KeyError, TypeError):
        return None
    if not isinstance(message, str) or _REFUSAL_MARK not in message:
        return None
    return message


def _write_refusal(path: str, prog: _Program, message: str) -> None:
    import jax

    record = {
        "program": prog.name,
        "message": message[:_MESSAGE_CAP],
        "jax": jax.__version__,
        "compiler": _compiler_identity(),
    }
    if not _write_atomic(path, json.dumps(record).encode()):
        log.info("compile refusal of %s not recorded at %s", prog.name, path)


#: compiled executable -> (its program, where its refusal would be recorded,
#: the files its compile added to JAX's cache): what :func:`reject` needs
_compiled_as: "weakref.WeakKeyDictionary[Any, Tuple[_Program, str, List[str]]]" = \
    weakref.WeakKeyDictionary()


def _entry_files(root: str, prog: _Program) -> set:
    """The files of JAX's cache that are entries of a program of this name."""
    try:
        return {n for n in os.listdir(root) if n.startswith(f"{prog.name}-")}
    except OSError:
        return set()


def _compile(lowered: Any, prog: Optional[_Program]) -> Any:
    """``lowered.compile()``, behind the refusal records."""
    path = _refusal_path(prog)
    if path is not None:
        recorded = _read_refusal(path)
        if recorded is not None:
            _bump("refusals_replayed")
            log.info("%s: the compiler refused this program before (%s); "
                     "not compiled again", prog.name, path)
            raise CompileRefused(recorded, "recorded", prog.name)
    try:
        if path is None:
            return lowered.compile()
        root = os.path.dirname(os.path.dirname(path))
        before = _entry_files(root, prog)
        compiled = lowered.compile()
        try:
            _compiled_as[compiled] = (
                prog, path, sorted(_entry_files(root, prog) - before))
        except TypeError:   # an executable that takes no weak reference
            pass
        return compiled
    except Exception as e:
        message = str(e)
        if _REFUSAL_MARK not in message:
            raise
        _bump("refusals_fresh")
        if path is not None:
            _write_refusal(path, prog, message)
        raise CompileRefused(
            message, "fresh", prog.name if prog else None) from e


def reject(compiled: Any, need_bytes: int, limit_bytes: int) -> bool:
    """The program's own memory rule refused ``compiled`` (a program of
    :func:`load_or_compile` that the compiler accepted): record it as a
    refusal, so that the next search raises :class:`CompileRefused` where it
    would have compiled or read 50 MB to weigh it again, and take the
    executable that compile wrote to JAX's cache out (it will not be run).
    False where nothing was recorded: the compile cache is off, or the
    executable is not one of this process's compiles."""
    try:
        prog, path, files = _compiled_as.pop(compiled)
    except (KeyError, TypeError):
        return False
    _write_refusal(path, prog, (
        f"{_REFUSAL_MARK}: the program's memory rule: compiled, "
        f"{prog.name} needs {need_bytes / 2 ** 30:.3f} GiB, over 0.92 x "
        f"{limit_bytes / 2 ** 30:.3f} GiB of HBM (the compiler accepted it; "
        f"recorded by saturn_tpu's memory check)"))
    root = os.path.dirname(os.path.dirname(path))
    for name in files:
        try:
            os.unlink(os.path.join(root, name))
        except OSError:
            pass
    return True


def _path(key: str) -> str:
    return os.path.join(cache_dir(), f"{key}.jaxexec")


def _load(key: str, devices: Any = None) -> Optional[Any]:
    try:
        with open(_path(key), "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        import jax
        from jax.experimental.serialize_executable import deserialize_and_load

        # Load onto the block the program was lowered for. Left to its
        # default, deserialize_and_load takes EVERY device of the backend and
        # the executable then wants one shard per device. A caller that names
        # no block lowered for the default device.
        block = list(devices) if devices is not None else jax.devices()[:1]
        return deserialize_and_load(
            payload, in_tree, out_tree, execution_devices=block
        )
    except FileNotFoundError:
        return None
    except Exception as e:
        # corrupt / stale / cross-context entry: a miss, never an error
        _bump("errors")
        log.info("aot cache entry %s unloadable (%r) — recompiling", key[:12], e)
        try:
            os.unlink(_path(key))
        except OSError:
            pass
        return None


def _write_atomic(path: str, blob: bytes) -> bool:
    """``blob`` whole under ``path`` or not there (temp file + rename: trial
    threads run side by side, and so may processes); False = not written."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    return True


def _store(key: str, compiled: Any) -> bool:
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
    except Exception as e:
        _bump("errors")
        log.info("aot executable not serializable (%r) — caching skipped", e)
        return False
    if not _write_atomic(_path(key), blob):
        return False
    _bump("stores")
    return True


def load_or_compile(lowered: Any, devices: Any = None) -> Any:
    """The compiled executable for ``lowered``, via the on-disk cache.

    Cache hit: deserialize and skip XLA compilation entirely. Miss (or the
    cache is disabled/unwritable/unloadable): ``lowered.compile()`` as
    before, then serialize the result for the next process. The deserialized
    executable runs the identical machine code a fresh compile would
    produce, so results — including donation/aliasing behavior — are
    unchanged. One caveat: ``memory_analysis()`` may be unavailable on a
    deserialized executable; ``utils.timing.hbm_bytes_required`` already
    degrades that to "feasible, with a warning".

    A compile the compiler refuses for memory raises :class:`CompileRefused`
    and, where JAX's persistent compilation cache is on, is recorded beside
    it; the next call for the same program raises from the record without
    compiling (module docstring, "Refusal records").
    """
    prog = _program(lowered)
    key = None if prog is None else _executable_key(prog, devices)
    if key is not None:
        # Compile-ahead warm pool first: same process, no load hazard,
        # works even where the disk cache is off (CPU default).
        with _warm_lock:
            warm = _warm.get(key)
        if warm is not None:
            _bump("warm_hits")
            return warm
    if not enabled() or key is None:
        return _compile(lowered, prog)
    hit = _load(key, devices)
    if hit is not None:
        _bump("hits")
        return hit
    _bump("misses")
    compiled = _compile(lowered, prog)
    _store(key, compiled)
    return compiled


def prewarm(lowered: Any, devices: Any = None) -> Any:
    """Compile ``lowered`` now and park the executable in the warm pool.

    Called from compile-ahead worker threads. The executable goes two
    places: the in-process warm pool (always — that is what makes the
    admitted job's first ``load_or_compile`` free), and the on-disk
    cache via the normal :func:`load_or_compile` path when enabled (so
    the prewarm also survives a restart).
    """
    compiled = load_or_compile(lowered, devices)
    key = cache_key(lowered, devices)
    if key is not None:
        with _warm_lock:
            _warm[key] = compiled
        _bump("prewarms")
    return compiled


def clear_warm() -> None:
    """Drop the warm pool (tests; bounded-memory resets)."""
    with _warm_lock:
        _warm.clear()
