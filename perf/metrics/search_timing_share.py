"""Trial runner: share of the search's wall inside ``trial.timing`` spans, the
part of profiling that is measurement (the rest is building, compiling and
checking what is measured). None where the program emits no spans."""

from perf.lib import spans


def read(run):
    return spans.share_of_search(run, lambda e: e["kind"] == "trial.timing")
