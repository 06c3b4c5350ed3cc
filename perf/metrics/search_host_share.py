"""Trial runner: share of the search's wall spent tracing and lowering in
Python (``jax.monitoring`` durations, summed over trial threads and capped at
the wall), which no compile cache removes."""


def read(run):
    if not run.search:
        return None
    clock, wall = run.search["clock"], run.search["wall_s"]
    return 100.0 * min(clock["trace_s"] + clock["lower_s"], wall) / wall
