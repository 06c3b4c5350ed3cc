"""End to end: tokens trained by all jobs of the cell (steps read back from
the checkpoints x batch x seq) over the host-clock wall of the one
``orchestrate()`` call, which returns after its own checkpoint flush."""


def read(run):
    if not run.window.get("tokens"):
        return None
    return run.window["tokens"] / run.window["wall_s"]
