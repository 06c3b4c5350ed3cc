"""Technique: how far the realized step time of the window is from the time
the trial measured for the chosen strategy: (sum of the program's own
``elapsed_s``) / (sum over intervals of steps x chosen ``per_batch_time``),
minus 1, in percent. Positive: the trial was optimistic."""

from perf.lib import readers


def read(run):
    realized = promised = 0.0
    for span in readers.work_spans(run):
        trial = run.chosen.get(span["task"], {}).get("per_batch_s")
        if not trial:
            continue
        realized += span["elapsed_s"]
        promised += span["batches"] * trial
    if promised <= 0.0:
        return None
    return 100.0 * (realized / promised - 1.0)
