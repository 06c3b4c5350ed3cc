"""Step program: milliseconds per step as the program itself clocks its
intervals (sum of ``elapsed_s`` over sum of steps, per job), token-weighted
over the jobs."""

from perf.lib import readers


def read(run):
    per_job = {}
    for span in readers.work_spans(run):
        took, steps = per_job.get(span["task"], (0.0, 0))
        per_job[span["task"]] = (took + span["elapsed_s"], steps + span["batches"])
    weight = total = 0.0
    for name, (took, steps) in per_job.items():
        if steps:
            tokens = run.job(name).tokens_per_step * steps
            total += tokens * 1e3 * took / steps
            weight += tokens
    return total / weight if weight else None
