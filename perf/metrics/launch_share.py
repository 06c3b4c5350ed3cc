"""Engine: share of the ``orchestrate`` call spent launching gangs
(``launch.build`` / ``.restore`` / ``.init`` / ``.compile``) while no other
gang's steps ran. None where the program emits no spans."""

from perf.lib import spans


def read(run):
    return spans.share_of_window(run, "launch.*", blocking="other")
