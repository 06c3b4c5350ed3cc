"""Engine: share of the ``orchestrate`` call in which a thread that trains or
orchestrates waited on a checkpoint: ``ckpt.wait_pending`` and
``ckpt.snapshot`` (the gang's thread, before its state may be donated again)
and ``ckpt.flush`` (the join of the writer threads). The writer threads' own
``ckpt.write`` is not in it. None where the program emits no spans."""

from perf.lib import spans


def read(run):
    return spans.share_of_window(
        run, "ckpt.wait_pending", "ckpt.snapshot", "ckpt.flush")
