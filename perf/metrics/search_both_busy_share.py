"""Trial runner: share of the search's wall in which a host-work span and a
chip-side span are open at the same time: a later grid point prepared while
an earlier one is measured (PR 39; ``perf/lib/critical_path.py``). A search
that walks its grid on one thread reads 0. None where the program emits no
spans."""

from perf.lib import critical_path


def read(run):
    return critical_path.share(run, "both_busy")
