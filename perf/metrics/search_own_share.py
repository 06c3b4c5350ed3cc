"""Trial runner: share of the search's wall in which neither a host-work span
nor a chip-side span is open on any thread: the search's own bookkeeping
(PR 39; ``perf/lib/critical_path.py``). Its line names the ``search.*`` spans
that lie in those stretches and the remainder that no span names. None where
the program emits no spans."""

from perf.lib import critical_path


def read(run):
    p = critical_path.partition(run)
    if p is None:
        return None
    print("perf: the search's own " + f"{p['seconds']['own']:.3f}s: " + ", ".join(
        f"{kind or 'no span'} {secs:.3f}"
        for kind, secs in critical_path.own_by_name(p)), flush=True)
    return 100.0 * p["seconds"]["own"] / p["wall"]
