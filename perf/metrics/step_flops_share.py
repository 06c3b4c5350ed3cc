"""Engine: share of the ``orchestrate`` call inside ``step_flops`` spans: what
the package's own ``tflops`` / ``mfu`` figure (a re-trace of the step, once
per compiled program) costs the window. None where the program emits no
spans."""

from perf.lib import spans


def read(run):
    return spans.share_of_window(run, "step_flops")
