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
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import platform
import threading
from typing import Any, Optional

log = logging.getLogger("saturn_tpu")

_ENV_TOGGLE = "SATURN_TPU_AOT_CACHE"
_SUBDIR = "aot"

#: Bump when the payload layout changes meaning — old entries then miss.
SCHEMA_VERSION = 1

_stats_lock = threading.Lock()
_stats = {"hits": 0, "misses": 0, "stores": 0, "errors": 0,
          "prewarms": 0, "warm_hits": 0}

# In-process warm pool fed by the compile-ahead service
# (``tenancy.compile_ahead``): executables compiled in the background
# between admission and first dispatch. Same-process, so none of the
# cross-context serialize/deserialize hazards apply — the warm pool is
# consulted even when the on-disk cache is disabled (CPU default).
_warm_lock = threading.Lock()
_warm: dict = {}


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
    try:
        text = lowered.as_text()
    except Exception:
        return None
    h = hashlib.sha256()
    h.update(_runtime_identity().encode())
    h.update(b"\x00")
    if devices is not None:
        ids = ",".join(
            str(getattr(d, "id", i)) for i, d in enumerate(devices)
        )
        h.update(f"block:{ids}".encode())
        h.update(b"\x00")
    h.update(text.encode())
    return h.hexdigest()


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


def _store(key: str, compiled: Any) -> bool:
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
    except Exception as e:
        _bump("errors")
        log.info("aot executable not serializable (%r) — caching skipped", e)
        return False
    path = _path(key)
    tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        os.makedirs(cache_dir(), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
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
    """
    key = cache_key(lowered, devices)
    if key is not None:
        # Compile-ahead warm pool first: same process, no load hazard,
        # works even where the disk cache is off (CPU default).
        with _warm_lock:
            warm = _warm.get(key)
        if warm is not None:
            _bump("warm_hits")
            return warm
    if not enabled():
        return lowered.compile()
    if key is None:
        return lowered.compile()
    hit = _load(key, devices)
    if hit is not None:
        _bump("hits")
        return hit
    _bump("misses")
    compiled = lowered.compile()
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
