"""The benchmark's own reductions over the program's span events.

A span is an event of the program's JSONL stream that carries ``id``,
``ts_start`` and ``dur_s`` (``saturn_tpu/utils/metrics.py::span``): one
stretch of host time, from ``ts_start`` to ``ts`` on the events' clock
(``time.time()``), with ``parent`` / ``root`` ids and the ``thread`` it ran
on. ``task_interval`` carries ``id`` and ``parent`` too but is stamped by
hand: it is a *node* of the tree (from ``ts_launch`` to ``ts``) whose
[``ts_start``, ``ts``] is the gang's steps, not a span record.

Nothing here calls into the program: events in (``run.events(phase, None)``),
seconds out. All intervals are (start, end) pairs in seconds on one clock.
A run of a commit without spans yields no span event, and every reduction
then returns None (the metric is left out of the line).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from perf.lib import trace_reduce

Interval = Tuple[float, float]

#: spans that enclose a whole call or a whole interval: they say that the
#: program ran, not what it was doing
ENCLOSING = ("orchestrate", "interval", "fused_interval", "search")
ANNOTATION_PREFIX = "saturn."


# ----------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The same stretches with overlaps merged, sorted."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def subtract(intervals: Iterable[Interval],
             holes: Iterable[Interval]) -> List[Interval]:
    """What is left of ``intervals`` (merged) outside every hole."""
    holes = union(holes)
    out: List[Interval] = []
    for s, e in union(intervals):
        at = s
        for hs, he in holes:
            if he <= at:
                continue
            if hs >= e:
                break
            if hs > at:
                out.append((at, hs))
            at = max(at, he)
        if at < e:
            out.append((at, e))
    return out


# --------------------------------------------------------------------- events
def is_span(e: Dict[str, Any]) -> bool:
    return "id" in e and "ts_start" in e and "dur_s" in e


def spans(events: Sequence[Dict[str, Any]], *kinds: str) -> List[Dict[str, Any]]:
    """Span events, of the given kinds if any; a kind ending in ``.*`` takes
    every kind under that prefix (``launch.*``)."""
    out = [e for e in events if is_span(e)]
    if kinds:
        exact = {k for k in kinds if not k.endswith(".*")}
        prefixes = tuple(k[:-1] for k in kinds if k.endswith(".*"))
        out = [e for e in out if e["kind"] in exact
               or (prefixes and e["kind"].startswith(prefixes))]
    return out


def extent(e: Dict[str, Any]) -> Interval:
    """The stretch a tree node covers: a span's [ts_start, ts]; a
    ``task_interval``'s [ts_launch, ts]."""
    start = e["ts_start"] if is_span(e) else e.get("ts_launch", e["ts_start"])
    return float(start), float(e["ts"])


def steps_of(events: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One entry per gang and interval: its node id and the stretch its
    steps ran in (``task_interval``'s [ts_start, ts])."""
    return [{"id": e.get("id"), "task": e["task"],
             "steps": (float(e["ts_start"]), float(e["ts"]))}
            for e in events if e.get("kind") == "task_interval"]


def root_span(events: Sequence[Dict[str, Any]], kind: str) -> Optional[Dict[str, Any]]:
    """The ``search`` / ``orchestrate`` span of a phase's events: the longest
    one, should a phase hold several calls."""
    found = spans(events, kind)
    return max(found, key=lambda e: e["dur_s"]) if found else None


def under_root(events: Sequence[Dict[str, Any]], root: Dict[str, Any]
               ) -> List[Dict[str, Any]]:
    return [e for e in events if e.get("root") == root["id"]]


def tree(events: Sequence[Dict[str, Any]]) -> Dict[int, Dict[str, Any]]:
    """id -> {"event", "children": [ids]} over every event that has an id."""
    nodes = {e["id"]: {"event": e, "children": []} for e in events if "id" in e}
    for i, n in nodes.items():
        p = n["event"].get("parent")
        if p in nodes:
            nodes[p]["children"].append(i)
    return nodes


def self_seconds(events: Sequence[Dict[str, Any]]) -> Dict[int, float]:
    """id -> the node's duration minus what its children cover of it. A child
    on another thread counts where it overlaps (an ``interval`` waits on its
    launcher threads); one that starts after its parent ended (``ckpt.write``
    after its snapshot) covers nothing of it."""
    nodes = tree(events)
    out = {}
    for i, n in nodes.items():
        lo, hi = extent(n["event"])
        kids = clip((extent(nodes[c]["event"]) for c in n["children"]), lo, hi)
        out[i] = max(hi - lo - length(kids), 0.0)
    return out


def self_time_table(events: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per kind: how many, on how many threads, summed duration and summed
    self time; heaviest self time first. Sums over threads can pass the wall."""
    own = self_seconds(events)
    rows: Dict[str, Dict[str, Any]] = {}
    for e in events:
        if "id" not in e:
            continue
        r = rows.setdefault(e["kind"], {"kind": e["kind"], "n": 0, "dur_s": 0.0,
                                        "self_s": 0.0, "threads": set()})
        lo, hi = extent(e)
        r["n"] += 1
        r["dur_s"] += hi - lo
        r["self_s"] += own[e["id"]]
        r["threads"].add(e.get("thread", "-"))
    table = sorted(rows.values(), key=lambda r: -r["self_s"])
    for r in table:
        r["threads"] = len(r["threads"])
    return table


def print_table(phase: str, events: Sequence[Dict[str, Any]]) -> None:
    table = self_time_table(events)
    if not table:
        return
    print(f"perf: spans of the {phase}: kind, count, threads, seconds, self seconds",
          flush=True)
    for r in table:
        print(f"perf:   {r['kind']:<22} {r['n']:>4} {r['threads']:>3} "
              f"{r['dur_s']:>10.3f} {r['self_s']:>10.3f}", flush=True)
    compiles = [e for e in events if e.get("kind") == "compile"]
    for e in compiles:
        where = (e.get("in_span") or {}).get("name")
        print(f"perf:   compile {e.get('program')}: {e['seconds']:.3f}s"
              f"{' (persistent cache)' if e.get('cached') else ''} in {where}",
              flush=True)


# ------------------------------------------------------------------- shares
def thread_summed(found: Sequence[Dict[str, Any]]) -> float:
    """Seconds of the spans, merged within each thread and summed over the
    threads (trial threads run side by side)."""
    by_thread: Dict[str, List[Interval]] = {}
    for e in found:
        by_thread.setdefault(e.get("thread", "-"), []).append(extent(e))
    return sum(length(v) for v in by_thread.values())


def share_of_search(run: Any, pick) -> Optional[float]:
    """100 x (thread-summed seconds of the spans ``pick`` keeps, capped at
    the wall) / the ``search`` span, as ``search_host_share`` caps."""
    events = run.events("search", None)
    root = root_span(events, "search")
    if root is None or root["dur_s"] <= 0:
        return None
    mine = [e for e in spans(under_root(events, root)) if pick(e)]
    return 100.0 * min(thread_summed(mine), root["dur_s"]) / root["dur_s"]


def window_events(run: Any):
    """(events under the window's ``orchestrate`` span, that span) or
    (None, None) where the program has no spans."""
    events = run.events("window", None)
    root = root_span(events, "orchestrate")
    if root is None or root["dur_s"] <= 0:
        return None, None
    return under_root(events, root), root


def share_of_window(run: Any, *kinds: str, blocking: str = "") -> Optional[float]:
    """100 x the union of the spans of ``kinds`` over the ``orchestrate``
    span. ``blocking="any"`` leaves out what any gang's steps overlap;
    ``blocking="other"`` what another gang's steps overlap (a gang's own
    launch is never inside its own steps)."""
    events, root = window_events(run)
    if root is None:
        return None
    lo, hi = extent(root)
    gangs = steps_of(events)
    kept: List[Interval] = []
    for e in spans(events, *kinds):
        mine = [extent(e)]
        if blocking:
            holes = [g["steps"] for g in gangs
                     if blocking == "any" or g["id"] != e.get("parent")]
            mine = subtract(mine, holes)
        kept += mine
    return 100.0 * length(clip(kept, lo, hi)) / (hi - lo)


# ------------------------------------------------------- the profiler's trace
def annotations(xplane_path: str) -> List[Tuple[str, float, float, str]]:
    """The program's spans as the profiler saw them: (name without the
    ``saturn.`` prefix, start_ns, end_ns, line name) from the host plane."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    s = float(ev.start_ns)
                    out.append((ev.name[len(ANNOTATION_PREFIX):], s,
                                s + float(ev.duration_ns), line.name))
    return sorted(out, key=lambda a: a[1])


def clock_skew(found: Sequence[Tuple[str, float, float, str]],
               events: Sequence[Dict[str, Any]],
               wall_offset_s: float) -> Optional[Dict[str, Any]]:
    """The largest distance between an annotation's start in the trace,
    moved to the host clock by ``wall_offset_s``, and the same span's
    ``ts_start``: whether the two clocks are one. Annotation and event are
    paired by name and order of start."""
    by_name: Dict[str, List[float]] = {}
    for name, s, _, _ in found:
        by_name.setdefault(name, []).append(s / 1e9 + wall_offset_s)
    worst, n = None, 0
    for kind, starts in by_name.items():
        mine = sorted(float(e["ts_start"]) for e in spans(events, kind))
        if len(mine) != len(starts):
            continue  # a span that began before the profiler did, or after
        for a, b in zip(sorted(starts), mine):
            n += 1
            if worst is None or abs(a - b) > abs(worst["skew_s"]):
                worst = {"kind": kind, "skew_s": a - b}
    if worst is None:
        return None
    worst["n_paired"] = n
    return worst
