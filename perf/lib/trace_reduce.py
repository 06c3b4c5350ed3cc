"""From a profiler trace (``.xplane.pb``) to what the metrics read.

Uses ``jax.profiler.ProfileData`` and nothing external. A device plane is a
plane named ``/device:TPU:<n>``; its operations are the events of the line
``XLA Ops`` (one event per executed HLO instruction, nested ones such as the
body of a ``while`` included, which is why busy time is a *union* of
intervals and never a sum). Host spans are the benchmark's own
``TraceAnnotation``s (names starting with ``perf.``) on the host plane.
Times in the trace are nanoseconds from the start of the trace.

Across chips every device plane is reduced alone and the planes are then
averaged: ``busy_s``, ``ops``, ``collective_s`` and ``collective_exposed_s``
are means over the chips, and ``worst`` names the plane with the least busy
time (the chip that waited most) with its own numbers beside them.

Collectives are the operations whose instruction is named ``all-gather``,
``reduce-scatter``, ``all-reduce`` or ``collective-permute``. An asynchronous
one is in flight from the start of its ``-start`` operation to the end of its
``-done`` operation (paired by the ``-done``'s operand where the event holds
the HLO line, else first started, first done, per kind); a synchronous one
for its own event (the line ``Async XLA Ops`` holds the same flights, but
in this profiler on the first chip's plane only, so it is not read).
*Exposed* is the part of that time in which no other operation ran on the
same chip: the wait the step pays for.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "perf."
KERNEL_MARK = "saturn_"
#: events that enclose others on the ops line: they would count a loop's
#: whole body once more if they were summed by name
_ENCLOSING = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals: Iterable[Tuple[float, float]], lo: float, hi: float,
         keep: int = 50) -> List[Tuple[float, float]]:
    """The ``keep`` longest stretches of [lo, hi] that no interval covers."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if e <= lo or s >= hi:
            continue
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    out.sort(key=lambda g: g[0] - g[1])
    return out[:keep]


def short_name(event_name: str) -> str:
    """An op event is named by its whole HLO line (``%fusion.12 = bf16[...]
    fusion(...)``): keep the instruction's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _kernel_name(event_name: str) -> Optional[str]:
    """``saturn_flash_fwd`` from ``%jvp_saturn_flash_fwd_.1 = ...`` or
    ``%transpose_jvp_saturn_flash_dkv__.1 = ...``: the Pallas kernel's own
    ``name=``, which JAX wraps in the transformation's name and underscores."""
    text = short_name(event_name)
    at = text.find(KERNEL_MARK)
    if at < 0:
        return None
    end = at
    while end < len(text) and (text[end].isalnum() or text[end] == "_"):
        end += 1
    return text[at:end].rstrip("_")


def collective_kind(event_name: str) -> Optional[str]:
    """``all-gather`` from ``%all-gather-start.3 = ...`` or
    ``%all-gather.7.fusion``; None for any other operation."""
    text = short_name(event_name)
    return next((c for c in COLLECTIVES if c in text), None)


def _done_operand(event_name: str) -> Optional[str]:
    """``all-gather-start.3`` from ``%all-gather-done.3 = ...
    all-gather-done(%all-gather-start.3)``; None where the event is named by
    the instruction alone."""
    _, _, call = event_name.partition("-done(")
    at = call.find("%")
    if at < 0:
        return None
    end = at + 1
    while end < len(call) and (call[end].isalnum() or call[end] in "._-"):
        end += 1
    return call[at + 1:end] or None


def collectives_in_flight(events: Iterable[Tuple[str, float, float]]
                          ) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of every collective among ``events`` (name,
    start_ns, end_ns; one chip's ops line), ``-start`` and ``-done`` joined
    into one stretch."""
    out: List[Tuple[float, float]] = []
    open_by_name: Dict[str, List[float]] = {}
    open_by_kind: Dict[str, List[Tuple[str, float]]] = {}
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        kind = collective_kind(name)
        if kind is None:
            continue
        short = short_name(name)
        if "-start" in short:
            open_by_name.setdefault(short, []).append(s)
            open_by_kind.setdefault(kind, []).append((short, s))
            continue
        if "-done" in short:
            started = None
            operand = _done_operand(name)
            if operand and open_by_name.get(operand):
                started = open_by_name[operand].pop(0)
                open_by_kind[kind].remove((operand, started))
            elif operand is None and open_by_kind.get(kind):
                first, started = open_by_kind[kind].pop(0)
                open_by_name[first].remove(started)
            out.append((s if started is None else started, e))
            continue
        out.append((s, e))
    # a start whose done fell outside the trace: its own issue time is lost
    # with it (microseconds), which no share can see
    return out


def reduce_trace(path: str) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, Any]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops: List[Tuple[float, float]] = []
            named: List[Tuple[str, float, float]] = []
            by_name: Dict[str, float] = {}
            kernels: Dict[str, List[Tuple[float, float]]] = {}
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            for line in lines:
                for ev in line.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    if d <= 0:
                        continue
                    ops.append((s, s + d))
                    named.append((ev.name, s, s + d))
                    kernel = _kernel_name(ev.name)
                    if kernel is not None:
                        kernels.setdefault(kernel, []).append((s, d))
                    name = kernel or short_name(ev.name)
                    if not name.startswith(_ENCLOSING):
                        by_name[name] = by_name.get(name, 0.0) + d / 1e9
            devices[plane.name] = {"ops": ops, "by_name": by_name,
                                   "kernels": kernels, "named": named}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((ev.name, s, s + float(ev.duration_ns)))
    window = next(((s, e) for n, s, e in spans if n == SPAN_PREFIX + "window"), None)
    if window is None:
        every = [iv for d in devices.values() for iv in d["ops"]]
        window = (min(s for s, _ in every), max(e for _, e in every)) if every else (0.0, 0.0)
    lo, hi = window
    out: Dict[str, Any] = {
        "window_ns": window, "window_s": (hi - lo) / 1e9, "spans": spans,
        "n_devices": len(devices), "devices": {},
    }
    merged: Dict[str, float] = {}

    def clipped(intervals):
        return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]

    for name, dev in sorted(devices.items()):
        inside = clipped(dev["ops"])
        flight = clipped(collectives_in_flight(dev["named"]))
        # what else ran: every operation that is no collective and encloses
        # no other (a ``while`` covers its whole body, collectives included)
        other = clipped((s, e) for n, s, e in dev["named"]
                        if collective_kind(n) is None
                        and not short_name(n).startswith(_ENCLOSING))
        # in flight while the chip was busy: a transfer that outlasts the
        # chip's work is the chip's idle time, not its busy time's share
        in_flight = (union_seconds(flight) + union_seconds(inside)
                     - union_seconds(flight + inside))
        out["devices"][name] = {
            "busy_s": union_seconds(inside), "n_ops": len(inside),
            "gaps": gaps(inside, lo, hi), "kernels": dev["kernels"],
            "n_collectives": len(flight), "collective_s": in_flight,
            "collective_exposed_s": union_seconds(flight + other) - union_seconds(other),
            "by_name": dev["by_name"]}
        for op, secs in dev["by_name"].items():
            merged[op] = merged.get(op, 0.0) + secs
    n = max(len(devices), 1)
    for key in ("busy_s", "collective_s", "collective_exposed_s"):
        out[key] = sum(d[key] for d in out["devices"].values()) / n
    out["n_collectives"] = sum(d["n_collectives"] for d in out["devices"].values())
    out["ops"] = sorted(((k, v / n) for k, v in merged.items()),
                        key=lambda kv: -kv[1])
    out["worst"] = (min(out["devices"], key=lambda k: out["devices"][k]["busy_s"])
                    if devices else None)
    return out
