"""Engine: share of the window's wall in which no gang did device work: 1 -
union of the intervals' [first step, last step] spans over the wall."""

from perf.lib import readers, trace_reduce


def read(run):
    spans = readers.work_spans(run)
    if not spans or not run.window.get("wall_s"):
        return None
    lo, hi = run.window["wall_t0"], run.window["wall_t1"]
    busy = trace_reduce.union_seconds(
        (max(s["start"], lo) * 1e9, min(s["end"], hi) * 1e9) for s in spans
        if s["end"] > lo and s["start"] < hi)
    return 100.0 * (1.0 - busy / run.window["wall_s"])
