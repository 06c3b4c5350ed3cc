"""Trial runner: share of the search's wall in which a chip-side span (a grid
point's init, staging or timing) is open and no host-work span on any
thread: the host has nothing left to prepare, so this is what timing fewer
steps could save (PR 39; ``perf/lib/critical_path.py``). None where the
program emits no spans."""

from perf.lib import critical_path


def read(run):
    return critical_path.share(run, "wait_for_chip")
