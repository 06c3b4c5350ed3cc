"""Trial runner: of the time the search spent measuring (the union of its
``trial.timing`` spans: the chip busy, the host waiting for it), the share
during which a ``trial.build`` or ``trial.compile`` span of *another* thread
was open: host work on a later grid point that the chip's work on this one
hid (PR 37). A search that walks its grid on one thread reads 0; trial
threads side by side on disjoint blocks hide each other's host work too, and
count. None where the program emits no spans, or timed no point."""

from perf.lib import spans

HOST_WORK = ("trial.build", "trial.compile")


def read(run):
    events = run.events("search", None)
    root = spans.root_span(events, "search")
    if root is None:
        return None
    mine = spans.under_root(events, root)
    timings = spans.spans(mine, "trial.timing")
    measured = spans.length(spans.extent(t) for t in timings)
    if measured <= 0:
        return None
    host = spans.spans(mine, *HOST_WORK)
    hidden = []
    for t in timings:
        lo, hi = spans.extent(t)
        hidden += spans.clip((spans.extent(e) for e in host
                              if e.get("thread") != t.get("thread")), lo, hi)
    return 100.0 * spans.length(hidden) / measured
