"""Persistent profile cache: trial results that outlive the driver process.

Profiling is the single most expensive phase of the pipeline — compile
dominates a trial (~1 min upper bound each, ``trial_runner/evaluator.py``) and
the grid is (task × sub-mesh size × technique). The Saturn paper notes this
cost is amortizable: a profile depends only on *what* is being timed (model,
data shape, optimizer, technique, sub-mesh size, accelerator topology, XLA
version), none of which changes between back-to-back sweeps. So every trial
outcome — feasible (params + per-batch seconds) or infeasible — is keyed on a
content fingerprint of exactly those inputs and written to one JSON file per
key. A repeated ``search()`` over an unchanged task list then performs zero
trial compiles.

Entries are upgraded in place by the orchestrator's realized-feedback loop
(``executor/orchestrator.py``): once a task actually runs, its measured
per-batch time replaces the trial estimate (``source="realized"``), so the
next process's sweep starts from production numbers, not solo-trial ones.

Corrupt, stale or partially-written files are treated as misses, never
errors: writes go through an atomic ``os.replace`` and reads re-validate the
embedded key and field types. Delete the cache directory to invalidate
everything.

Environment:

- ``SATURN_TPU_PROFILE_CACHE_DIR``: cache directory (default
  ``~/.cache/saturn_tpu/profiles``).
- ``SATURN_TPU_PROFILE_CACHE=0``: disable the default cache entirely.

JAX's persistent *compilation* cache is placed from outside, not by an
option of this package: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX keeps
its cache there and this package sets no directory; where it is unset the
cache is on by default on a TPU backend, at the fixed path
``<checkout>/.jax_compile_cache`` (the path is part of the cache key, so a
directory that moves never hits), and off elsewhere — see
:func:`maybe_enable_persistent_compile_cache`.
"""

from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

log = logging.getLogger("saturn_tpu")

#: Bump when the fingerprint payload or entry schema changes meaning —
#: old entries then miss instead of being misread.
SCHEMA_VERSION = 1

_ENV_DIR = "SATURN_TPU_PROFILE_CACHE_DIR"
_ENV_TOGGLE = "SATURN_TPU_PROFILE_CACHE"

_FALSEY = ("0", "false", "off", "no")


# --------------------------------------------------------------- fingerprints
def _model_signature(task: Any) -> str:
    """Content signature of the task's model: config + abstract param tree.

    Uses ``jax.eval_shape`` via ``ModelSpec.abstract_init`` so no weights are
    materialized (the reference's lazy-instantiation rule, ``Task.py:92-97``).
    Factories that fail or specs without the ModelSpec surface degrade to
    whatever stable repr is available — a narrower key, never a wrong hit.
    """
    try:
        spec = task.get_model()
    except Exception:
        return f"factory:{type(task).__name__}"
    parts = [repr(getattr(spec, "config", type(spec).__name__))]
    abstract = getattr(spec, "abstract_init", None)
    if callable(abstract):
        try:
            import jax

            leaves, _ = jax.tree_util.tree_flatten_with_path(abstract())
            parts += [
                f"{jax.tree_util.keystr(path)}:{tuple(leaf.shape)}:{leaf.dtype}"
                for path, leaf in leaves
            ]
        except Exception:
            pass
    return ";".join(parts)


def _data_signature(task: Any) -> str:
    """Batch shape/dtype + batch size: what actually drives step time (token
    *values* don't — synthetic vs real corpora profile identically)."""
    try:
        ds = task.get_dataset()
    except Exception:
        return "none"
    parts = [type(ds).__name__, str(getattr(ds, "batch_size", None))]
    eb = getattr(ds, "example_batch", None)
    if callable(eb):
        try:
            b = eb()
            parts += [str(tuple(getattr(b, "shape", ()))), str(getattr(b, "dtype", ""))]
        except Exception:
            pass
    return ";".join(parts)


def _optimizer_signature(task: Any) -> str:
    opt = getattr(getattr(task, "hparams", None), "optimizer", None)
    if isinstance(opt, str) or opt is None:
        return str(opt)
    # a custom optax factory: the qualname is the best stable handle (repr
    # would embed a memory address and never match across processes)
    return f"custom:{getattr(opt, '__qualname__', type(opt).__name__)}"


def task_signature(task: Any) -> str:
    """Everything about a *task* that a per-batch profile depends on.

    Excludes lr, total batch count and the task name: the reference cloned
    searched tasks across learning rates precisely because lr doesn't change
    step time (``WikiText103.py:87-99``), and runtime is re-derived as
    ``per_batch_time * total_batches`` at use time. Scheduling-only hints
    (``priority``, ``deadline`` — written by the online job service for the
    replanner's eviction ordering) are likewise excluded: they never touch
    the compiled program, and the same model submitted at a different
    priority must stay a warm cache hit.
    """
    hp = getattr(task, "hparams", None)
    kwargs = dict(getattr(hp, "kwargs", {}) or {})
    hints = {
        k: v
        for k, v in dict(getattr(task, "hints", {}) or {}).items()
        if k not in ("priority", "deadline")
    }
    return json.dumps(
        {
            "model": _model_signature(task),
            "data": _data_signature(task),
            "optimizer": _optimizer_signature(task),
            "kwargs": kwargs,
            "hints": hints,
        },
        sort_keys=True,
        default=repr,
    )


def topology_signature(topo: Any) -> str:
    sig = getattr(topo, "signature", None)
    return sig() if callable(sig) else repr(topo)


def dispatch_signature() -> str:
    """How execute() dispatches batches — part of every fingerprint.

    Trials profile the dispatch mode execution will use (fused K-step scan
    windows vs per-step calls), and the two modes have genuinely different
    per-batch times — amortized dispatch/readback overhead is the point of
    fusing. A stale per-step profile warm-starting a fused sweep (or vice
    versa) would hand the MILP numbers execution never exhibits, so the
    mode (and its window cap) keys the cache. Imported lazily: utils must
    not import parallel at module level.
    """
    try:
        from saturn_tpu.parallel.spmd_base import dispatch_signature as _ds

        return _ds()
    except Exception:
        return "per-step"


def schedule_signature() -> str:
    """Version of the pipeline schedule set — part of every fingerprint.

    The pipeline executor's candidate grid carries the schedule kind
    (GPipe vs 1F1B) in each config, and the trial runner times both; a
    profile recorded before a schedule existed (or after one's program
    changed) describes a grid the sweep no longer runs, so stale entries
    must MISS rather than warm-start the solver with configs execution
    would route differently. Imported lazily like ``dispatch_signature``:
    utils must not import ops at module level.
    """
    try:
        from saturn_tpu.ops.pipeline import schedule_signature as _ss

        return _ss()
    except Exception:
        return "gpipe-only"


def fusion_signature() -> str:
    """Version of the fused-stacking machinery — part of every fingerprint.

    A profile's ``fused_per_batch_time`` (and the solver decisions priced on
    it) describes the stacked program of a specific fusion version; when the
    stacked step's semantics change (``parallel/fused.FUSION_SET_VERSION``)
    stale entries must MISS so groups re-trial instead of fusing on a
    measurement of a program that no longer exists. Lazy import like
    ``schedule_signature``: utils must not import parallel at module level.
    """
    try:
        from saturn_tpu.parallel.fused import fusion_signature as _fs

        return _fs()
    except Exception:
        return "no-fusion"


def overlap_signature() -> str:
    """Overlap lowering version + active per-op-class factor set.

    Two reasons an entry must MISS: (1) the overlapped programs changed
    shape (``ops/collective_matmul.OVERLAP_SET_VERSION`` — a serial profile
    must never price an overlapped lowering, and vice versa); (2) the
    overlap factors the prior priced it under moved (calibration or an env
    pin), so a plan warm-started from the entry would disagree with what
    admission and the solver now compute. Lazy imports like the signatures
    above: utils must not import ops/analysis at module level.
    """
    try:
        from saturn_tpu.ops.collective_matmul import overlap_signature as _os

        lowering = _os()
    except Exception:
        lowering = "no-overlap"
    try:
        from saturn_tpu.analysis.shardflow.prior import (
            overlap_factor_signature as _ofs,
        )

        factors = _ofs()
    except Exception:
        factors = "no-factors"
    return f"{lowering};{factors}"


def fingerprint(
    task_sig: str, technique: str, size: int, topo_sig: str,
    dispatch: Optional[str] = None,
) -> str:
    """Cache key for one (task, technique, sub-mesh size) grid point under
    one execution dispatch mode (``dispatch_signature()`` when None)."""
    try:
        import jax

        jax_version = jax.__version__
    except Exception:
        jax_version = "none"
    from saturn_tpu.analysis import SCHEMA_VERSION as _ANALYSIS_SCHEMA
    from saturn_tpu.analysis.memlens import PASS_VERSION as _MEMLENS_PASS
    from saturn_tpu.analysis.shardflow import PASS_VERSION as _SHARDFLOW_PASS

    payload = json.dumps(
        {
            "schema": SCHEMA_VERSION,
            # Analyzer rule-set version: a plan repaired under one
            # diagnostic schema must never warm-start from profiles
            # recorded under another (saturn-lint round 12).
            "analysis": _ANALYSIS_SCHEMA,
            # Shardflow propagation-rule version: static priors recorded
            # under one cost model must miss cleanly under another.
            "shardflow": _SHARDFLOW_PASS,
            # Memlens liveness-model version: memory-infeasibility entries
            # (including statically pruned points) recorded under one
            # liveness model must miss cleanly under another.
            "memlens": _MEMLENS_PASS,
            "task": task_sig,
            "technique": technique,
            "size": int(size),
            "topology": topo_sig,
            "jax": jax_version,
            "dispatch": dispatch_signature() if dispatch is None else dispatch,
            # Pipeline schedule-set version: a GPipe-only profile recorded
            # before 1F1B landed must miss — its cached params lack the
            # schedule key and its timing raced a narrower grid.
            "schedules": schedule_signature(),
            # Fusion-set version: entries recorded before cross-job stacking
            # existed (or under a different stacked-step program) must miss.
            "fusion": fusion_signature(),
            # Overlap lowering version + active overlap-factor set: serial
            # profiles must not price overlapped programs, and recalibrated
            # factors must invalidate plans priced under the old set.
            "overlap": overlap_signature(),
        },
        sort_keys=True,
    )
    return hashlib.sha256(payload.encode()).hexdigest()


# --------------------------------------------------------------------- store
class ProfileCache:
    """Directory of one-JSON-file-per-key trial outcomes."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.json")

    def get(self, key: Optional[str]) -> Optional[Dict[str, Any]]:
        """Validated entry dict, or None for missing/corrupt/foreign files."""
        if not key:
            return None
        try:
            with open(self._path(key)) as f:
                e = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(e, dict) or e.get("key") != key:
            return None  # stale schema or hash collision artifact: miss
        if not isinstance(e.get("feasible"), bool):
            return None
        if e["feasible"]:
            pbt = e.get("per_batch_time")
            if not isinstance(pbt, (int, float)) or pbt <= 0.0:
                return None
            if not isinstance(e.get("params"), dict):
                return None
        return e

    def put(
        self,
        key: Optional[str],
        *,
        technique: str,
        size: int,
        feasible: bool,
        params: Optional[Dict[str, Any]] = None,
        per_batch_time: Optional[float] = None,
        source: str = "trial",
        memory_infeasible: bool = False,
        host_fraction: float = 0.0,
    ) -> bool:
        """Atomically write one entry; False if the key or params aren't
        cacheable (non-JSON params from a plugin technique).

        ``host_fraction`` is the trial-measured staging-vs-compute split the
        solver's co-location term consumes; pre-existing entries without the
        field read back as 0.0 (never co-scheduled) via ``get``'s tolerance
        for missing fields."""
        if not key:
            return False
        entry = {
            "key": key,
            "schema": SCHEMA_VERSION,
            "technique": technique,
            "size": int(size),
            "feasible": bool(feasible),
            "params": params,
            "per_batch_time": per_batch_time,
            "source": source,
            "memory_infeasible": bool(memory_infeasible),
            "host_fraction": float(host_fraction),
            "written": time.time(),
        }
        try:
            blob = json.dumps(entry)
        except (TypeError, ValueError):
            return False
        tmp = self._path(key) + f".tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "w") as f:
                f.write(blob)
            os.replace(tmp, self._path(key))
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        return True

    def note_realized(
        self,
        key: Optional[str],
        per_batch_time: float,
        params: Optional[Dict[str, Any]],
        technique: str,
        size: int,
    ) -> bool:
        """Upgrade (or create) an entry from a *realized* interval measurement.

        Realized numbers supersede both trial profiles and interpolated
        estimates: they average a whole interval of production batches under
        real contention, which is exactly what the next sweep should predict.
        """
        if not key or per_batch_time <= 0.0:
            return False
        prev = self.get(key)
        if prev is not None and prev.get("feasible") and params is None:
            params = prev.get("params")
        # The realized interval measures wall time, not the staging split —
        # carry the trial's host fraction forward so an upgraded entry stays
        # co-schedulable.
        hf = prev.get("host_fraction", 0.0) if prev is not None else 0.0
        return self.put(
            key,
            technique=technique,
            size=size,
            feasible=True,
            params=params if isinstance(params, dict) else {},
            per_batch_time=float(per_batch_time),
            source="realized",
            host_fraction=float(hf) if isinstance(hf, (int, float)) else 0.0,
        )

    def __len__(self) -> int:
        try:
            return sum(1 for fn in os.listdir(self.root) if fn.endswith(".json"))
        except OSError:
            return 0


# ------------------------------------------------------------- default cache
_DEFAULT: Optional[ProfileCache] = None
_DEFAULT_LOCK = threading.Lock()


def default_dir() -> str:
    return os.environ.get(
        _ENV_DIR, os.path.join(os.path.expanduser("~"), ".cache", "saturn_tpu", "profiles")
    )


def default_cache() -> Optional[ProfileCache]:
    """Process-wide cache honoring the env toggles; None when disabled."""
    if os.environ.get(_ENV_TOGGLE, "1").lower() in _FALSEY:
        return None
    global _DEFAULT
    with _DEFAULT_LOCK:
        d = default_dir()
        if _DEFAULT is None or _DEFAULT.root != d:
            try:
                _DEFAULT = ProfileCache(d)
            except OSError:
                log.warning("profile cache dir %s not writable — caching off", d)
                return None
        return _DEFAULT


def resolve(spec: Any = None) -> Optional[ProfileCache]:
    """Map a ``search(profile_cache=...)`` argument to a cache instance.

    ``None`` -> the env-configured default (on unless disabled); ``False`` ->
    off for this sweep; a path string -> that directory; a ``ProfileCache``
    -> itself.
    """
    if spec is None:
        return default_cache()
    if spec is False:
        return None
    if isinstance(spec, ProfileCache):
        return spec
    if isinstance(spec, (str, os.PathLike)):
        try:
            return ProfileCache(os.fspath(spec))
        except OSError:
            log.warning("profile cache dir %s not writable — caching off", spec)
            return None
    raise TypeError(
        f"profile_cache must be None, False, a directory path or a "
        f"ProfileCache, got {type(spec).__name__}"
    )


# -------------------------------------------------- JAX compilation cache
_ENV_JAX_COMPILE_DIR = "JAX_COMPILATION_CACHE_DIR"


def default_compile_cache_dir() -> str:
    """``<checkout>/.jax_compile_cache``: beside the package, fixed."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_compile_cache")


@functools.cache
def maybe_enable_persistent_compile_cache() -> Optional[str]:
    """Decide, once per process, where JAX's persistent compilation cache
    lives, and return that directory (None = off).

    The only place in the repo that touches ``jax_compilation_cache_dir``.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and no
    directory is set here. Unset, a TPU backend gets the fixed
    :func:`default_compile_cache_dir`; any other backend gets no cache
    (XLA:CPU loads entries written under different CPU feature detection
    and runs them anyway — ``tests/conftest.py``). Either way the size and
    time thresholds drop, because a sweep compiles many programs that the
    defaults would not keep.

    Cached after the first call, so the build hot path
    (``SPMDTechnique._build_uncached``) calls it unconditionally.
    """
    import jax

    path = os.environ.get(_ENV_JAX_COMPILE_DIR) or None
    if path is None and jax.default_backend() == "tpu":
        path = default_compile_cache_dir()
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    if path is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # A Pallas kernel's payload carries the source locations of its
        # trace, ten frames of traceback each by default: a backward kernel
        # is traced four frames under ``_build_uncached``, so the same step
        # program traced under ``search`` and under an interval's launch (or
        # a reference check's ``execute``) had two texts and two cache
        # entries of 47-59 MB (PR 36's second ``jit_saturn_window`` entry,
        # "not found why"; found in PR 42: ``tests/test_tpu_compile.py``).
        # Four frames keep the callers out, and the kernels their names in a
        # device trace (one frame a location, which
        # ``jax_include_full_tracebacks_in_locations`` offers, renames every
        # kernel ``tpu_custom_call.N`` there: my chip run, PR 42).
        jax.config.update("jax_traceback_in_locations_limit", 4)
        log.info("jax persistent compilation cache at %s", path)
    return path
