"""Structured metrics: append-only JSONL event stream.

The reference's observability was prints + stdlib logging + the Ray dashboard
(SURVEY.md §5 "Metrics / logging": "No metrics files, no TensorBoard"). This
fills that gap with the smallest thing that composes: every subsystem emits
typed events (trial results, interval timing/estimate error, solver
makespans, task failures) to one JSONL file a notebook or `jq` can consume.

Disabled unless configured — ``search(metrics_path=...)`` /
``orchestrate(metrics_path=...)`` or :func:`configure` directly.

Stretches of time are :func:`span` events on the same stream (one event per
span, emitted at its end, carrying its start); ``docs/architecture.md``
("Metrics stream & spans") has the record and the table of names.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import json
import logging
import os
import sys
import threading
import time
from typing import Optional

from saturn_tpu.analysis import concurrency as tsan

logger = logging.getLogger("saturn_tpu")


class MetricsWriter:
    """Thread-safe JSONL appender (the engine launches tasks from threads).

    Events are buffered in memory and written in batches — size-bounded
    (``max_buffered`` events) and time-bounded (``max_latency_s`` since the
    oldest unwritten event) — so emission stays off the step critical path:
    the old line-buffered stream paid a syscall + page-cache write per event
    from inside interval hot loops. Hot-path callers just append under the
    lock; the engine/orchestrator/service call :func:`flush` at interval
    boundaries, and ``close()`` always drains.

    Torn-tail guarantees are unchanged: each drain is a single ``write()``
    of whole ``\\n``-terminated lines, so a crash can tear at most the last
    line in flight — exactly what ``read_events``/``tail_events`` already
    skip-and-warn on. What buffering *does* change is the loss window: a
    crash between flushes drops the buffered (never-written) events, which
    is why the durability journal — not metrics — is the ledger of record.
    """

    def __init__(self, path: str, max_buffered: int = 256,
                 max_latency_s: float = 2.0):
        self.path = path
        self.max_buffered = max(1, int(max_buffered))
        self.max_latency_s = float(max_latency_s)
        self._lock = tsan.lock("metrics.writer")
        self._fh = open(path, "a")
        self._buf: list = []
        self._oldest: Optional[float] = None  # monotonic ts of _buf[0]

    def event(self, kind: str, **fields) -> None:
        rec = {"ts": time.time(), "kind": kind}
        rec.update(fields)
        line = json.dumps(rec, default=str)
        now = time.monotonic()
        with self._lock:
            if self._fh.closed:
                # The module-level event() reads _WRITER without _CONF_LOCK,
                # so a racing configure()/scoped() may close this writer
                # between the read and this call. Dropping the event is fine;
                # raising inside an engine launcher thread would record a
                # spurious task failure.
                return
            self._buf.append(line)
            if self._oldest is None:
                self._oldest = now
            if (len(self._buf) >= self.max_buffered
                    or now - self._oldest >= self.max_latency_s):
                self._drain_locked()

    def _drain_locked(self) -> None:
        if not self._buf or self._fh.closed:
            self._buf = []
            self._oldest = None
            return
        data = "\n".join(self._buf) + "\n"
        self._buf = []
        self._oldest = None
        try:
            self._fh.write(data)
            self._fh.flush()
        except (OSError, ValueError):
            pass

    def flush(self) -> None:
        """Write out everything buffered (interval-boundary durability for
        live ``tail_events`` followers and post-run ``read_events``)."""
        with self._lock:
            self._drain_locked()

    def close(self) -> None:
        """Drain, then close the stream, fsyncing first: ``configure``/
        ``scoped`` rotate sinks by closing the old writer, so rotation is a
        durability point — a crash right after must not lose the rotated-out
        events to the page cache."""
        with self._lock:
            self._drain_locked()
            if not self._fh.closed:
                try:
                    self._fh.flush()
                    # sanctioned-unlocked: close IS the rotation durability
                    # point; fsync under the lock keeps late event() callers
                    # from interleaving appends into a half-synced stream.
                    os.fsync(self._fh.fileno())
                except (OSError, ValueError):
                    pass
            self._fh.close()


_WRITER: Optional[MetricsWriter] = None
_CONF_LOCK = tsan.lock("metrics.conf")


def configure(path: Optional[str]) -> None:
    """Point the global metrics stream at ``path`` (None disables)."""
    global _WRITER
    if path:
        _listen_for_compiles()
    with _CONF_LOCK:
        if _WRITER is not None:
            _WRITER.close()
        _WRITER = MetricsWriter(path) if path else None


def enabled() -> bool:
    """True when a metrics sink is configured — lets emitters skip *computing*
    expensive event fields (e.g. the task_interval MFU numerator's one-time
    shardflow trace) when every event would be dropped anyway."""
    # sanctioned-unlocked: single-reference read of a lock-managed global
    return _WRITER is not None


def event(kind: str, **fields) -> None:
    """Emit an event if metrics are configured; no-op otherwise."""
    # Invariant: _WRITER swaps are atomic (one assignment under _CONF_LOCK)
    # and a stale writer is drained-then-closed, where event() degrades to
    # a documented drop (see MetricsWriter.event) — taking _CONF_LOCK here
    # would put a mutex acquisition on every hot-path emission.
    # sanctioned-unlocked: single-reference read of a lock-managed global
    w = _WRITER
    if w is not None:
        w.event(kind, **fields)


def flush() -> None:
    """Drain the configured writer's buffer to disk; no-op when metrics are
    off. Called at interval boundaries (engine, orchestrator, service loop)
    so telemetry lands off the step critical path but before the next
    interval's work starts."""
    # sanctioned-unlocked: same single-reference-read contract as event()
    w = _WRITER
    if w is not None:
        w.flush()


# ---------------------------------------------------------------------- spans
_SPAN_IDS = itertools.count(1)  # next() on it is atomic: ids are process-unique
_OPEN = threading.local()       # .stack: the spans open on this thread
_ANNOTATION = None              # the profiler's annotation class, on first use
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the host's seconds by kind: the ``jax.monitoring`` duration -> the field of
#: the span it was spent under (the four the benchmark's own clock totals)
_HOST_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE_EVENT: "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_LISTENING = False
_ROOTS: list = []       # the root spans open in the process (``with`` blocks)
_gc_t0: Optional[float] = None   # the collector's pass under way, if stamped


def _stack() -> list:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


class span:
    """``with metrics.span("launch.build", task=t.name) as sp:`` — one
    stretch of the program's time, as ONE event at its end.

    The event's ``kind`` is the span's name; besides the caller's fields
    (``sp.set(outcome="refused")`` adds more inside the block) it carries
    ``ts`` (end, stamped by the writer like any event), ``ts_start``
    (``time.time()`` at entry: the events' clock), ``dur_s``
    (``perf_counter``), ``id`` (unique in the process), ``parent`` (the id of
    the ``parent=`` handed over, else of the span open on this thread at
    entry, else None), ``root`` (the id of the outermost enclosing span —
    ``search`` or ``orchestrate``: what every span of one call shares) and
    ``thread``. An exception leaves the span emitted with
    ``error=<type name>`` and propagates untouched, ``BaseException``s
    (``SimulatedKill``) included; a span that closes in a ``finally`` while
    a kill (no ``Exception``) passes through its thread carries it too.

    What the host did inside it, stamped by the two listeners (no event of
    their own; each field left out where nothing was counted): ``trace_s`` /
    ``lower_s`` / ``compile_s`` / ``cache_read_s`` are JAX's own durations
    (jaxpr trace, jaxpr -> MLIR, backend compile, persistent-cache
    retrieval) that ended on a thread while this span was the innermost open
    there, nested ones counted once (a jit traced inside a trace is inside
    the outer duration) and a compile that the cache answered under
    ``cache_read_s`` alone; ``gc_s`` / ``gc_n`` / ``gc_full`` /
    ``gc_full_max_s`` are the cyclic collector's passes that ran on that
    thread (seconds, passes, generation-2 passes, the longest of those). A
    root span (``search``, ``orchestrate``) carries the collector's passes
    of the whole process over its extent instead.

    ``min_s``: a span shorter than this and without ``error`` emits nothing
    (a wait that did not wait).

    The block is also annotated for the profiler (``saturn.<name>``): under
    any profiler session the same stretch sits on the host plane of the
    trace, on the trace's clock, beside the device's ops.

    Threads: a new thread has no open span. Whoever starts one takes
    :func:`current_span` first and the thread runs ``with
    metrics.under(that):`` (or opens its first span with ``parent=that``).

    Cost: with no sink configured nothing is stamped, allocated or emitted
    (the :func:`enabled` contract); what is left is the annotation, a flag
    check when no profiler runs. No span belongs inside a per-step loop: the
    finest grain in the package is one per phase per task per interval.
    """

    __slots__ = ("name", "fields", "id", "parent", "root", "ts_start",
                 "_handed", "_t0", "_ann", "_min_s", "_seconds", "_gc")

    def __init__(self, name: str, parent: Optional["span"] = None,
                 min_s: float = 0.0, **fields):
        self.name = name
        self.fields = fields
        self.id: Optional[int] = None
        self.parent: Optional[int] = None
        self.root: Optional[int] = None
        self._handed = parent
        self._min_s = min_s
        # field -> [(end, seconds)] of the durations counted here, in order
        self._seconds: Optional[dict] = None
        self._gc: Optional[dict] = None  # the collector's passes, as emitted

    def open(self) -> "span":
        """Stamp the start and take an id WITHOUT entering the block: for an
        event that is emitted by hand because a generator yields while the
        stretch is open (``task_interval``). ``ids()`` gives the event its
        ``id`` / ``parent`` / ``root``; ``under(sp)`` makes the phases in
        between its children. A no-op without a sink."""
        if not enabled():
            return self
        above = self._handed
        if above is None or above.id is None:
            stack = _stack()
            above = stack[-1] if stack else None
        self.id = next(_SPAN_IDS)
        if above is not None and above.id is not None:
            self.parent, self.root = above.id, above.root
        else:
            self.root = self.id
        self.ts_start = time.time()
        self._t0 = time.perf_counter()
        return self

    def ids(self) -> dict:
        if self.id is None:
            return {}
        return {"id": self.id, "parent": self.parent, "root": self.root}

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def close(self) -> None:
        """The end of a span taken with :meth:`open`: the span's one event,
        as ``__exit__`` emits it, from whichever thread the stretch ends on
        (``thread`` names that one). For a stretch that begins on one thread
        and ends on another (a grid point's ``trial.config``: prepared on
        the caller's thread, measured on the search's measuring thread),
        which no ``with`` block can hold. No annotation for the profiler; a no-op without a
        sink."""
        if self.id is None:
            return
        dur_s = time.perf_counter() - self._t0
        if dur_s < self._min_s and "error" not in self.fields:
            return
        event(self.name, ts_start=self.ts_start, dur_s=dur_s,
              thread=threading.current_thread().name,
              **self.ids(), **self._stamped(), **self.fields)

    def _add_seconds(self, field: str, secs: float) -> None:
        """One of JAX's durations that ended just now under this span. It
        replaces those of its kind that it contains: they ended after it
        began (one thread: what ended inside it began inside it)."""
        if self._seconds is None:
            self._seconds = {}
        mine = self._seconds.setdefault(field, [])
        end = time.time()
        while mine and mine[-1][0] > end - secs:
            mine.pop()
        mine.append((end, secs))

    def _add_gc(self, secs: float, full: bool) -> None:
        if self._gc is None:
            self._gc = {"gc_s": 0.0, "gc_n": 0, "gc_full": 0,
                        "gc_full_max_s": 0.0}
        self._gc["gc_s"] += secs
        self._gc["gc_n"] += 1
        if full:
            self._gc["gc_full"] += 1
            self._gc["gc_full_max_s"] = max(self._gc["gc_full_max_s"], secs)

    def _stamped(self) -> dict:
        out = {field: sum(secs for _, secs in mine)
               for field, mine in (self._seconds or {}).items()}
        out.update(self._gc or {})
        if not out.get("gc_full", 1):
            del out["gc_full_max_s"]  # no full pass: no longest one
        return {k: round(v, 6) for k, v in out.items()}

    def __enter__(self) -> "span":
        global _ANNOTATION
        if _ANNOTATION is None:
            import jax.profiler

            _ANNOTATION = jax.profiler.TraceAnnotation
        self._ann = _ANNOTATION("saturn." + self.name)
        self._ann.__enter__()
        if self.open().id is not None:
            _stack().append(self)
            if self.parent is None:
                _ROOTS.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self.id is not None:
                _stack().pop()  # blocks nest: the top of the stack is self
                if self.parent is None:
                    _ROOTS.remove(self)
                if exc_type is None:
                    # a ``finally`` on a kill's way out: the block itself
                    # raised nothing, the thread is being unwound around it
                    passing = sys.exc_info()[0]
                    if passing is not None and not issubclass(passing,
                                                              Exception):
                        exc_type = passing
                if exc_type is not None:
                    self.fields["error"] = exc_type.__name__
                self.close()
        finally:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def current_span() -> Optional[span]:
    """The innermost span open on this thread (its ``.id`` is what a child's
    ``parent`` will read), or None — always None without a sink. Take it
    before starting a thread and hand it to :func:`under` there."""
    stack = getattr(_OPEN, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def under(parent: Optional[span]):
    """The receiving end of a thread hand-off: spans opened on THIS thread
    inside the block, with no span of this thread's own above them, are
    children of ``parent`` (a span taken with :func:`current_span` or
    ``as sp`` on the thread that started this one). Emits nothing."""
    if parent is None or parent.id is None:
        yield
        return
    stack = _stack()
    stack.append(parent)
    try:
        yield
    finally:
        stack.pop()


def _on_duration(name: str, secs: float, fun_name: Optional[str] = None,
                 **_) -> None:
    """JAX's four compile-path durations, each on the thread it was spent
    on: added to the innermost span open there (no event of its own), and
    one ``compile`` event per backend compile."""
    field = _HOST_SECONDS.get(name)
    if field is None:
        return
    # JAX clocks the persistent compilation cache's retrievals under the
    # backend compile's event too; the retrieval it reported on this thread
    # just before says so.
    cached = field == "compile_s" and bool(
        _OPEN.__dict__.pop("cache_read", False))
    if not enabled():
        return
    if field == "cache_read_s":
        _OPEN.cache_read = True  # read by the compile's duration that follows
    sp = current_span()  # the listener runs on the thread that did the work
    if sp is not None and not cached:
        sp._add_seconds(field, secs)
    if field == "compile_s":
        event("compile", seconds=secs, program=fun_name, cached=cached,
              in_span=None if sp is None else {"name": sp.name, "id": sp.id},
              thread=threading.current_thread().name)


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: each pass of the cyclic collector, on the thread it
    ran on, counted into the innermost span open there and into every open
    root span. Passes do not overlap (the collector runs one at a time), so
    one stamp holds the pass under way."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter() if enabled() else None
        return
    if _gc_t0 is None:
        return
    secs, _gc_t0 = time.perf_counter() - _gc_t0, None
    full = info.get("generation") == 2
    roots = tuple(_ROOTS)
    here = current_span()
    if here is not None and here not in roots:
        here._add_gc(secs, full)
    for root in roots:
        root._add_gc(secs, full)


def _listen_for_compiles() -> None:
    """While a sink is configured: one ``compile`` event per backend (XLA)
    compile -- the jitted function's name (``program``), its ``seconds``,
    whether the persistent compilation cache gave it (``cached``) and the
    span open on the compiling thread (``in_span``: what places a compile
    inside an interval) -- and the host's seconds by kind and the
    collector's passes on the spans they fall under (``span``). Registered
    once per process, at the first sink; no-ops without one."""
    global _LISTENING
    with _CONF_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)


def read_events(path: str, kind: Optional[str] = None) -> list:
    """Read a JSONL metrics file back as dicts, optionally filtered by
    ``kind`` — the test/analysis counterpart to :func:`event`. Lines that
    fail to parse (a crashed writer's torn tail) are skipped with a
    WARNING — losing the last in-flight event to a crash is expected,
    losing it *silently* is not."""
    out = []
    with open(path) as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                logger.warning(
                    "metrics: skipping torn/corrupt line %d of %s "
                    "(%d bytes) — a crashed writer's unflushed tail",
                    lineno, path, len(line),
                )
                continue
            if kind is None or rec.get("kind") == kind:
                out.append(rec)
    return out


def tail_events(path: str, kind: Optional[str] = None,
                poll_s: float = 0.2, stop=None, follow: bool = True):
    """Generator over a live JSONL metrics stream (``tail -f`` semantics).

    Yields events from the start of the file, then keeps polling for
    appended lines every ``poll_s`` until ``stop`` (a ``threading.Event``)
    is set — or returns at EOF when ``follow=False``. A partial trailing
    line (the writer mid-append) is buffered, not parsed, so a torn tail
    never raises and never yields a truncated record; the line is delivered
    once its newline lands."""
    buf = ""
    with open(path) as fh:
        while True:
            chunk = fh.read(65536)
            if chunk:
                buf += chunk
                while "\n" in buf:
                    line, buf = buf.split("\n", 1)
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        # A mid-file torn line: a pre-crash writer's tail
                        # that a restarted writer appended past.
                        logger.warning(
                            "metrics: skipping torn/corrupt line in %s "
                            "(%d bytes)", path, len(line),
                        )
                        continue
                    if kind is None or rec.get("kind") == kind:
                        yield rec
                continue
            if not follow or (stop is not None and stop.is_set()):
                return
            time.sleep(poll_s)


@contextlib.contextmanager
def scoped(path: Optional[str]):
    """Route events to ``path`` for the enclosed region, then restore the
    previous sink and close the file — so ``orchestrate(metrics_path=...)``
    cannot leak its writer into later runs."""
    global _WRITER
    if not path:
        yield
        return
    _listen_for_compiles()
    mine = MetricsWriter(path)
    with _CONF_LOCK:
        prev = _WRITER
        _WRITER = mine
    try:
        yield
    finally:
        with _CONF_LOCK:
            # A configure() call inside the region may have replaced (and
            # closed) our writer — only close/restore what is still ours.
            if _WRITER is mine:
                _WRITER = prev
        mine.close()
