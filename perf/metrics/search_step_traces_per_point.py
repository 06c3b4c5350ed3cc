"""Trial runner: Python traces of the train step per grid point. Every
``trial_config`` event of the search whose grid point built its bundle
carries ``step_traces`` (PR 34): how often the model's Python step function
was called for that point, counted where it is called. Since PR 34 a
bundle keeps its one trace and the window program, the 1-step program, the
memlens audit and ``step_flops`` all read it: 1.0. Before, a point was
traced where it was built, again where its window program was lowered and
again for the audit: 3, and 2 for a point the compiler refused before the
audit; that program carries no such field, and the metric is left out."""


def read(run):
    if not run.search:
        return None
    counts = [e["step_traces"] for e in run.events("search", "trial_config")
              if "step_traces" in e]
    if not counts:
        return None
    return sum(counts) / len(counts)
