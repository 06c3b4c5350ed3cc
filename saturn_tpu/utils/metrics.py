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
import itertools
import json
import logging
import os
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
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LISTENING = False


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
    (``SimulatedKill``) included.

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
                 "_handed", "_t0", "_ann")

    def __init__(self, name: str, parent: Optional["span"] = None, **fields):
        self.name = name
        self.fields = fields
        self.id: Optional[int] = None
        self.parent: Optional[int] = None
        self.root: Optional[int] = None
        self._handed = parent

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
        if self.id is not None:
            event(self.name, ts_start=self.ts_start,
                  dur_s=time.perf_counter() - self._t0,
                  thread=threading.current_thread().name,
                  **self.ids(), **self.fields)

    def __enter__(self) -> "span":
        global _ANNOTATION
        if _ANNOTATION is None:
            import jax.profiler

            _ANNOTATION = jax.profiler.TraceAnnotation
        self._ann = _ANNOTATION("saturn." + self.name)
        self._ann.__enter__()
        if self.open().id is not None:
            _stack().append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self.id is not None:
                _stack().pop()  # blocks nest: the top of the stack is self
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


def _on_cache_hit(name: str, **_) -> None:
    if name == _CACHE_HIT_EVENT:
        _OPEN.cache_hit = True  # read by the duration event that follows


def _on_duration(name: str, secs: float, fun_name: Optional[str] = None,
                 **_) -> None:
    if name != _COMPILE_EVENT:
        return
    # JAX clocks the persistent compilation cache's retrievals under the
    # same event; the hit it reported on this thread just before says so.
    cached = bool(_OPEN.__dict__.pop("cache_hit", False))
    if not enabled():
        return
    sp = current_span()  # the listener runs on the compiling thread
    event("compile", seconds=secs, program=fun_name, cached=cached,
          in_span=None if sp is None else {"name": sp.name, "id": sp.id},
          thread=threading.current_thread().name)


def _listen_for_compiles() -> None:
    """One ``compile`` event per backend (XLA) compile while a sink is
    configured: the jitted function's name (``program``), its ``seconds``,
    whether the persistent compilation cache gave it (``cached``) and the
    span open on the compiling thread (``in_span``: what places a compile
    inside an interval). Registered once per process, at the first sink; a
    no-op without one."""
    global _LISTENING
    with _CONF_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_cache_hit)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


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
