"""From a profiler trace (``.xplane.pb``) to what the metrics read.

Uses ``jax.profiler.ProfileData`` and nothing external. A device plane is a
plane named ``/device:TPU:<n>``; its operations are the events of the line
``XLA Ops`` (one event per executed HLO instruction, nested ones such as the
body of a ``while`` included, which is why busy time is a *union* of
intervals and never a sum). Host spans are the benchmark's own
``TraceAnnotation``s (names starting with ``perf.``) on the host plane.
Times in the trace are nanoseconds from the start of the trace.
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


def reduce_trace(path: str) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, Any]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops: List[Tuple[float, float]] = []
            by_name: Dict[str, float] = {}
            kernels: Dict[str, List[Tuple[float, float]]] = {}
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            for line in lines:
                for ev in line.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    if d <= 0:
                        continue
                    ops.append((s, s + d))
                    kernel = _kernel_name(ev.name)
                    if kernel is not None:
                        kernels.setdefault(kernel, []).append((s, d))
                    name = kernel or short_name(ev.name)
                    if not name.startswith(_ENCLOSING):
                        by_name[name] = by_name.get(name, 0.0) + d / 1e9
            devices[plane.name] = {"ops": ops, "by_name": by_name,
                                   "kernels": kernels}
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
    busy, merged = [], {}
    for name, dev in sorted(devices.items()):
        inside = [(max(s, lo), min(e, hi)) for s, e in dev["ops"] if e > lo and s < hi]
        b = union_seconds(inside)
        busy.append(b)
        out["devices"][name] = {"busy_s": b, "n_ops": len(inside),
                                "gaps": gaps(inside, lo, hi),
                                "kernels": dev["kernels"]}
        for op, secs in dev["by_name"].items():
            merged[op] = merged.get(op, 0.0) + secs
    n = max(len(devices), 1)
    out["busy_s"] = sum(busy) / n
    out["ops"] = sorted(((k, v / n) for k, v in merged.items()),
                        key=lambda kv: -kv[1])
    return out
