"""Solver: share of the ``orchestrate`` call spent in ``solver.resolve`` spans
that no gang's steps overlap: the solves the chips wait for (the first one,
and a re-solve that outlasts its interval). None where the program emits no
spans."""

from perf.lib import spans


def read(run):
    return spans.share_of_window(run, "solver.resolve", blocking="any")
