"""Trial runner: seconds of Python tracing a grid point: ``trace_s`` summed
over the spans of the search (JAX's jaxpr-trace durations, stamped by the
program on the span open on the tracing thread, a jit traced inside a trace
counted once) over its ``trial_config`` events. What ``search_host_share``
was meant to be, from inside and uncapped (PR 39). None where the program
stamps no such field, or the search walked no grid point."""

from perf.lib import critical_path, spans


def read(run):
    root, mine = critical_path.search_tree(run)
    if root is None:
        return None
    stamped = [e for e in spans.spans(mine) if "trace_s" in e]
    points = len(run.events("search", "trial_config"))
    if not stamped or not points:
        return None
    return sum(float(e["trace_s"]) for e in stamped) / points
