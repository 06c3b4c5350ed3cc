"""Who waited for whom in a search (PR 39): the ``search`` span's wall split
four ways by two questions asked of every instant, over every thread.

H(t): a host-work leaf span is open (``HOST``: a grid point's build, its
compiles, its memory check with the audit below it, the static prior).
C(t): a chip-side span is open (``CHIP``: a point's init, staging, timing).

- wait for host = H and not C: the chip has nothing to measure (the first
  preparation, and every stretch the measuring thread waits for a point);
- wait for chip = C and not H: the host has nothing left to prepare;
- both busy = H and C; own = neither.

The four add up to the wall by construction. The program's two wait spans are
the cross-check and are not read here: a ``trial.wait_prepared`` lies where
its trial's chip side is idle, a ``trial.wait_measured`` where its trial's
host side is (``perf/tests/test_search_critical_path.py`` holds that on
recorded events). Events in, seconds out, as ``perf/lib/spans.py``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from perf.lib import spans

HOST = ("trial.build", "trial.compile", "trial.memory_check", "trial.memlens",
        "prior.memlens")
CHIP = ("trial.init", "trial.stage", "trial.timing")
#: the seconds JAX clocks on the host, as the program stamps them on a span
HOST_SECONDS = ("trace_s", "lower_s", "compile_s", "cache_read_s")


def search_tree(run: Any):
    """(the ``search`` span, the events under it) or (None, None)."""
    events = run.events("search", None)
    root = spans.root_span(events, "search")
    if root is None or root["dur_s"] <= 0:
        return None, None
    return root, spans.under_root(events, root)


def partition(run: Any) -> Optional[Dict[str, Any]]:
    """Seconds of the search's wall by class, the wall, and the stretches
    that are neither (for ``search_own_share``'s names). None where the
    program emits no spans."""
    root, mine = search_tree(run)
    if root is None:
        return None
    lo, hi = spans.extent(root)
    host = spans.union(spans.clip(
        (spans.extent(e) for e in spans.spans(mine, *HOST)), lo, hi))
    chip = spans.union(spans.clip(
        (spans.extent(e) for e in spans.spans(mine, *CHIP)), lo, hi))
    wait_for_host = spans.subtract(host, chip)
    wait_for_chip = spans.subtract(chip, host)
    neither = spans.subtract([(lo, hi)], host + chip)
    wall = hi - lo
    seconds = {"wait_for_host": spans.length(wait_for_host),
               "wait_for_chip": spans.length(wait_for_chip),
               "own": spans.length(neither)}
    seconds["both_busy"] = max(
        wall - seconds["wait_for_host"] - seconds["wait_for_chip"]
        - seconds["own"], 0.0)
    return {"wall": wall, "seconds": seconds, "neither": neither,
            "root": root, "events": mine}


def share(run: Any, which: str) -> Optional[float]:
    p = partition(run)
    return None if p is None else 100.0 * p["seconds"][which] / p["wall"]


def own_by_name(p: Dict[str, Any]) -> List[Any]:
    """[(kind, seconds)] of the ``search.*`` spans inside the stretches that
    are neither host work nor chip work, heaviest first, and the remainder
    no span names last (under the key None)."""
    named: Dict[str, List[spans.Interval]] = {}
    for e in spans.spans(p["events"], "search.*"):
        named.setdefault(e["kind"], []).append(spans.extent(e))
    rows, covered = [], []
    for kind, found in named.items():
        inside = [piece for s, e in p["neither"]
                  for piece in spans.clip(found, s, e)]
        rows.append((kind, spans.length(inside)))
        covered += inside
    rows.sort(key=lambda r: -r[1])
    rows.append((None, max(p["seconds"]["own"] - spans.length(covered), 0.0)))
    return rows


def host_seconds_by_thread(events: Sequence[Dict[str, Any]]
                           ) -> Dict[str, Dict[str, float]]:
    """{"main" | "others": {field: seconds}} over the spans: the thread a
    span's event names is the one its block ran on (a ``trial.config`` may
    end on another; it holds bookkeeping only)."""
    out = {"main": dict.fromkeys(HOST_SECONDS, 0.0),
           "others": dict.fromkeys(HOST_SECONDS, 0.0)}
    for e in spans.spans(events):
        side = out["main" if e.get("thread") == "MainThread" else "others"]
        for field in HOST_SECONDS:
            side[field] += float(e.get(field, 0.0))
    return out


def untimed_host_seconds(events: Sequence[Dict[str, Any]]) -> float:
    """Host-work seconds under ``trial.config`` spans whose ``outcome`` is
    not ``timed``: what preparing the points that took no timed step cost."""
    untimed = {e["id"] for e in spans.spans(events, "trial.config")
               if e.get("outcome") != "timed"}
    return sum(e["dur_s"] for e in spans.spans(
        events, "trial.build", "trial.compile", "trial.memory_check")
        if e.get("parent") in untimed)
