"""End to end: process start (of ``perf/run.py``, the priming child of a
checkout's first run included) to the start of the window: import, search,
warm-up."""


def read(run):
    return run.setup_s
