"""Trial runner: share of the search's wall spent on grid points that took no
timed step (``trial.config`` spans whose ``outcome`` is not ``timed``: refused
by the chip's compiler, rejected by the memory check, infeasible, raised),
thread-summed and capped at the wall like ``search_host_share``. None where
the program emits no spans."""

from perf.lib import spans


def read(run):
    spans.print_table("search", run.events("search", None))
    return spans.share_of_search(
        run, lambda e: e["kind"] == "trial.config" and e.get("outcome") != "timed")
