"""Point records (PR 47): a grid point's memory verdict, found by what the
point is made from and not by the text a trace and a lowering produce.

The refusal records of :mod:`saturn_tpu.utils.aot_cache` are keyed by the
lowered program's text, so a search has to build, trace and lower a point
(7-19 s of the chip's host at the benchmark's widths) to learn that its
verdict is on record. A point record is a second way to the same verdict, by
an identity that exists before anything is built. It only *remembers*, for
one point, that the full path ended ``refused`` or ``memory_rejected``; the
text-keyed record stays the authority that writes verdicts.

A refusal must not outlive its cause. The text guarantees that for the first
kind of record; this one earns it the way a compiler cache's direct mode
does:

- *The key*: the data the program is a function of (:func:`_key`): technique
  class, the grid config, the profiled window, the block's device count,
  kinds and the HBM limit the memory rule reads, the runtime's and the
  compiler's identity (``aot_cache``), every ``SATURN_TPU_*`` variable, the
  versions of the distributions the trace runs through, the ``ModelSpec``
  the point's overrides give (every field but ``init_fn``: its config, its
  hints, its functions by qualified name and by what they close over), the
  parameter tree's shapes and dtypes (``init_fn`` traced abstractly, once a
  task), the batch's shape and dtype, ``HParams`` (optimizer, lr, kwargs),
  the loss function's qualified name, and where the package and the caller's
  script lie (two checkouts that share a compile cache share no record).
  **Not** the task's name, the data's seed, the device ids or a save
  directory. A part that cannot be written down canonically (an optimizer
  or loss that is no module-level function, an array or an object without
  fields somewhere in the spec, a function of a module whose source neither
  a version string nor the manifest vouches for) means *no identity*, and
  the point takes the full path unchanged.
- *The manifest*: the code the program is a function of. Written into the
  record at the end of the full path, when everything the trace imported is
  loaded: path and content hash of every loaded module's file that lies
  outside the installed distributions (the ``saturn_tpu`` package wherever
  it lies, the caller's script and its modules). A read is a hit only if
  every file named still hashes the same and is the file this process loaded
  under that module name; hashes are computed once a process. A mismatch, a
  missing file or an unreadable record is a miss, never an error: the full
  path runs and rewrites the record.

Records are ``saturn-refused/point-<key>.json`` beside the text-keyed
``<key>.json``: on exactly when the compile cache is on, gone with it, no
switch of their own. Only the two outcomes a search files under
``over_memory`` are kept; a point whose full path ends any other way takes
its record away (:meth:`PointRecord.note`).
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import hashlib
import json
import logging
import os
import sys
import threading
import types
import weakref
from typing import Any, Dict, Optional, Sequence

import numpy as np

from saturn_tpu.utils import aot_cache
from saturn_tpu.utils import metrics as _metrics
from saturn_tpu.utils.timing import hbm_limit

log = logging.getLogger("saturn_tpu")

#: Bump when the key or the record changes meaning: old records then miss.
SCHEMA_VERSION = 1
_PREFIX = "point-"
#: the outcomes of ``SPMDTechnique.search`` that are memory verdicts
VERDICTS = ("refused", "memory_rejected")
#: distributions a step's trace runs through (their files are not hashed)
_DISTRIBUTIONS = ("jax", "jaxlib", "libtpu", "optax", "numpy", "flax",
                  "ml_dtypes", "chex")
#: jax settings that change what a trace or a lowering produces
_JAX_FLAGS = ("jax_enable_x64", "jax_default_matmul_precision",
              "jax_default_prng_impl", "jax_threefry_partitionable",
              "jax_numpy_dtype_promotion", "jax_numpy_rank_promotion")
_MAX_NODES = 200_000


class _NoIdentity(Exception):
    """Something the program may depend on has no canonical form."""


# ----------------------------------------------------------- canonical form
@functools.lru_cache(maxsize=None)
def _vouched(module: Optional[str]) -> Optional[str]:
    """``module`` again if something vouches for what it holds: a version
    string (a built-in or installed module) or the manifest (a source file
    that can be hashed). A name defined where neither does (a notebook's or
    a ``python -c``'s ``__main__``) can change without a trace: no identity."""
    path = getattr(sys.modules.get(module), "__file__", None)
    if os.path.isfile(path) if isinstance(path, str) \
            else module in sys.builtin_module_names:
        return module
    raise _NoIdentity(f"nothing vouches for the source of module {module!r}")


def _named(fn: Any) -> str:
    """``module:qualname`` of a module-level function or class, and only of
    one: looked up again by that name it has to be the same object."""
    module, name = getattr(fn, "__module__", None), getattr(fn, "__qualname__", None)
    found = sys.modules.get(module) if isinstance(module, str) else None
    for part in (name or "").split("."):
        found = getattr(found, part, None)
    if found is None or found is not fn:
        raise _NoIdentity(f"{fn!r} has no module-level name")
    return f"{_vouched(module)}:{name}"


class _Canon:
    """One walk over a value: plain data as itself, a dataclass or an object
    of fields by its type and fields, a function by its name and what it
    closes over. Anything else (an array, a lock, a cycle) is no identity."""

    def __init__(self) -> None:
        self.path: set = set()   # ids on the way down: a cycle is refused
        self.nodes = 0

    def of(self, x: Any) -> Any:
        self.nodes += 1
        if self.nodes > _MAX_NODES:
            raise _NoIdentity("too large to write down")
        if x is None or isinstance(x, (bool, int, str)):
            return x
        if isinstance(x, float):
            return ["float", repr(x)]
        if isinstance(x, enum.Enum):
            return ["enum", _named(type(x)), x.name]
        if isinstance(x, np.dtype):
            return ["dtype", x.name]
        if isinstance(x, np.generic):
            return ["scalar", x.dtype.name, repr(x.item())]
        if isinstance(x, type):
            # a scalar type (``jnp.bfloat16``) by its dtype, a class by name
            dtype = getattr(x, "dtype", None)
            if isinstance(dtype, np.dtype):
                return ["dtype", dtype.name]
            if issubclass(x, np.generic):
                return ["dtype", np.dtype(x).name]
            return ["type", _named(x)]
        if isinstance(x, types.ModuleType):
            return ["module", x.__name__]
        if id(x) in self.path:
            raise _NoIdentity("a cycle")
        self.path.add(id(x))
        try:
            return self._inside(x)
        finally:
            self.path.discard(id(x))

    def _inside(self, x: Any) -> Any:
        if isinstance(x, (list, tuple)):
            return [type(x).__name__, [self.of(v) for v in x]]
        if isinstance(x, (set, frozenset)):
            return ["set", sorted((self.of(v) for v in x), key=json.dumps)]
        if isinstance(x, dict):
            items = [[self.of(k), self.of(v)] for k, v in x.items()]
            return ["dict", sorted(items, key=lambda kv: json.dumps(kv[0]))]
        if isinstance(x, functools.partial):
            return ["partial", self.of(x.func), self.of(list(x.args)),
                    self.of(x.keywords)]
        if isinstance(x, types.FunctionType):
            cells = []
            for name, cell in zip(x.__code__.co_freevars, x.__closure__ or ()):
                try:
                    cells.append([name, self.of(cell.cell_contents)])
                except ValueError:   # a cell not filled yet
                    cells.append([name, ["empty"]])
            return ["fn", f"{_vouched(x.__module__)}:{x.__qualname__}", cells,
                    self.of(x.__defaults__), self.of(x.__kwdefaults__)]
        if dataclasses.is_dataclass(x):
            return ["dataclass", _named(type(x)),
                    [[f.name, self.of(getattr(x, f.name))]
                     for f in dataclasses.fields(x)]]
        fields = getattr(x, "__dict__", None)
        if isinstance(fields, dict) and not hasattr(type(x), "__slots__") \
                and not hasattr(x, "shape"):
            return ["object", _named(type(x)), self.of(fields)]
        raise _NoIdentity(f"a {type(x).__name__} has no canonical form")


# ------------------------------------------------------------ what is keyed
@functools.lru_cache(maxsize=1)
def _installed() -> tuple:
    """Where the installed distributions and the standard library lie."""
    import site
    import sysconfig

    roots = {sysconfig.get_paths().get(k) for k in
             ("stdlib", "platstdlib", "purelib", "platlib")}
    try:
        roots.update(site.getsitepackages())
        roots.add(site.getusersitepackages())
    except Exception:
        pass
    return tuple(sorted(os.path.realpath(r) + os.sep for r in roots if r))


_realpath = functools.lru_cache(maxsize=None)(os.path.realpath)


def _own_source(name: str, module: Any) -> Optional[str]:
    """The file of a loaded module whose content no version string covers:
    one outside the installed distributions, and the package's wherever it
    lies. None for every other module."""
    path = getattr(module, "__file__", None)
    if not isinstance(path, str):
        return None
    path = _realpath(path)   # once a path: a manifest names some 120 files
    mine = name == "saturn_tpu" or name.startswith("saturn_tpu.")
    if not mine and path.startswith(_installed()):
        return None
    return path


@functools.lru_cache(maxsize=1)
def _versions() -> tuple:
    from importlib import metadata

    out = ["python:" + ".".join(map(str, sys.version_info[:3]))]
    for dist in _DISTRIBUTIONS:
        try:
            out.append(f"{dist}:{metadata.version(dist)}")
        except Exception:
            out.append(f"{dist}:-")
    return tuple(out)


def _where() -> list:
    """The package's directory and the caller's script: two checkouts that
    share a compile cache directory share no record."""
    import saturn_tpu

    main = _own_source("__main__", sys.modules.get("__main__"))
    return [os.path.realpath(os.path.dirname(saturn_tpu.__file__)), main]


def _settings() -> list:
    import jax

    flags = [[f, repr(getattr(jax.config, f, None))] for f in _JAX_FLAGS]
    env = sorted((k, v) for k, v in os.environ.items()
                 if k.startswith("SATURN_TPU_"))
    return [flags, [list(kv) for kv in env]]


#: task -> (its model kwargs as written down, its parameter tree's shapes)
_param_shapes: "weakref.WeakKeyDictionary[Any, tuple]" = weakref.WeakKeyDictionary()


def _parameter_shapes(task: Any) -> list:
    """Shapes and dtypes of the task's parameter tree (``init_fn`` traced
    abstractly: nothing is allocated), once a task and not once a point."""
    import jax

    kwargs = json.dumps(_Canon().of(dict(task.hparams.kwargs)))
    try:
        said, shapes = _param_shapes[task]
        if said == kwargs:
            return shapes
    except (KeyError, TypeError):
        pass
    tree = task.get_model().abstract_init()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    shapes = [[jax.tree_util.keystr(path), list(leaf.shape), str(leaf.dtype)]
              for path, leaf in leaves]
    try:
        _param_shapes[task] = (kwargs, shapes)
    except TypeError:   # a task that takes no weak reference
        pass
    return shapes


def _key(technique: Any, task: Any, devices: Sequence[Any],
         config: Dict[str, Any], k: int) -> str:
    """The point's identity as a file name; raises where it has none."""
    spec = task.get_model(**technique._model_overrides(config))
    if not dataclasses.is_dataclass(spec):
        raise _NoIdentity("the model is no ModelSpec")
    canon = _Canon()
    model = [[f.name, canon.of(getattr(spec, f.name))]
             for f in dataclasses.fields(spec) if f.name != "init_fn"]
    batch = task.get_dataset().example_batch()
    hp = task.hparams
    optimizer = hp.optimizer if isinstance(hp.optimizer, str) \
        else ["fn", _named(hp.optimizer)]
    loss = task.loss_fn
    parts = [
        f"schema{SCHEMA_VERSION}",
        [_named(type(technique)), getattr(technique, "name", None)],
        canon.of(dict(config)), int(k),
        [len(devices), sorted({str(getattr(d, "device_kind", "?"))
                               for d in devices}), hbm_limit(devices[0])],
        aot_cache._runtime_identity(), aot_cache._compiler_identity(),
        list(_versions()), _settings(), _where(),
        model, _parameter_shapes(task),
        [list(batch.shape), str(batch.dtype)],
        [optimizer, canon.of(hp.lr), canon.of(dict(hp.kwargs)),
         canon.of(hp.batch_size)],
        [_named(loss), canon.of(getattr(loss, "supports_fused_head", None))],
        canon.of(dict(getattr(task, "hints", None) or {})),
    ]
    blob = json.dumps(parts, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


# ------------------------------------------------------------- the manifest
_hash_lock = threading.Lock()
_hashes: Dict[str, Optional[str]] = {}   # path -> content hash, once a process


def _file_hash(path: str) -> Optional[str]:
    """The content hash of ``path``, computed once a process (None = gone
    or unreadable)."""
    with _hash_lock:
        if path in _hashes:
            return _hashes[path]
    try:
        with open(path, "rb") as f:
            digest: Optional[str] = hashlib.sha256(f.read()).hexdigest()
    except OSError:
        digest = None
    with _hash_lock:
        return _hashes.setdefault(path, digest)


def _manifest() -> Dict[str, list]:
    """Module name -> [file, content hash] of everything loaded now whose
    source no version string covers."""
    out = {}
    for name, module in list(sys.modules.items()):
        path = _own_source(name, module)
        if path is not None:
            out[name] = [path, _file_hash(path)]
    return out


def _manifest_holds(manifest: Any) -> bool:
    if not isinstance(manifest, dict) or not manifest:
        return False
    for name, entry in manifest.items():
        path, digest = entry   # a malformed entry raises: the caller's miss
        if not isinstance(digest, str) or _file_hash(path) != digest:
            return False
        loaded = sys.modules.get(name)
        if loaded is not None and _own_source(name, loaded) != path:
            return False
    return True


# --------------------------------------------------------------- the record
class PointRecord:
    """Where one grid point's verdict is or would be on record (``path`` is
    None where the point has no identity or the compile cache is off:
    ``note`` is then a no-op and the point takes the full path).
    ``verdict`` is what :func:`of` read there: the ``outcome`` (and the
    ``compiler``'s line, of a refusal) of a record that stands, else None."""

    def __init__(self, path: Optional[str], about: Dict[str, Any]):
        self.path = path
        self.about = about
        self.verdict: Optional[Dict[str, Any]] = None

    def _read(self) -> Optional[Dict[str, Any]]:
        """None: no record, one that cannot be read as a verdict, or one
        whose manifest no longer holds (a miss, never an error)."""
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                record = json.load(f)
            if record["schema"] != SCHEMA_VERSION or \
                    record["outcome"] not in VERDICTS or \
                    not _manifest_holds(record["manifest"]):
                return None
            compiler = record.get("compiler")
        except (OSError, ValueError, KeyError, TypeError):
            return None
        aot_cache._bump("refusals_unbuilt")
        log.info("%s %s: the grid point's memory verdict is on record (%s); "
                 "not built", self.about["technique"], self.about["config"],
                 self.path)
        said = {"compiler": compiler} if isinstance(compiler, str) else {}
        return dict(said, outcome=record["outcome"])

    def note(self, outcome: Optional[str], compiler: Optional[str] = None) -> None:
        """How the point's full path ended: a memory verdict is recorded
        (with the manifest of what is loaded now), anything else (it fits,
        it is infeasible, it raised) takes a standing record away."""
        if self.path is None:
            return
        if outcome not in VERDICTS:
            try:
                os.unlink(self.path)
            except OSError:
                pass
            return
        record = dict(self.about, schema=SCHEMA_VERSION, outcome=outcome,
                      compiler=compiler, manifest=_manifest())
        if not aot_cache._write_atomic(self.path, json.dumps(record).encode()):
            log.info("point record not written at %s", self.path)


def of(technique: Any, task: Any, devices: Sequence[Any],
       config: Dict[str, Any], k: int, parent: Any = None,
       read: bool = True) -> PointRecord:
    """The record of this grid point and, with ``read``, the verdict that
    stands there, under a ``trial.identity`` span: the seconds of the key
    and of the manifest's check (``identity``: whether the point has one;
    ``hit``: whether a verdict stands). Never raises: whatever goes wrong,
    the point has no identity."""
    from saturn_tpu.utils import profile_cache as _pc

    about = {"technique": getattr(technique, "name", None),
             "config": {str(name): repr(v) for name, v in config.items()},
             "size": len(devices), "k": int(k)}
    record = PointRecord(None, about)
    with _metrics.span("trial.identity", parent=parent) as sp:
        try:
            root = _pc.maybe_enable_persistent_compile_cache()
            if root:
                key = _key(technique, task, devices, config, k)
                record.path = os.path.join(root, aot_cache._REFUSED_SUBDIR,
                                           f"{_PREFIX}{key}.json")
                if read:
                    record.verdict = record._read()
        except Exception as e:
            log.debug("grid point %s has no identity: %r", config, e)
            record.path = record.verdict = None
            sp.set(why=repr(e)[:200])
        sp.set(identity=record.path is not None,
               hit=record.verdict is not None)
    return record
