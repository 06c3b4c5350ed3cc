"""End to end: host-clock wall of the ``search()`` call over the number of
jobs; profile cache on and empty, XLA compile cache as the checkout has it."""


def read(run):
    if not run.search:
        return None
    return run.search["wall_s"] / len(run.jobs)
