"""Trial runner: grid points tried per job (``trial_config`` events, an exact
count)."""


def read(run):
    if not run.search:
        return None
    return len(run.events("search", "trial_config")) / len(run.jobs)
