"""Step program: the rows a held expert's grouped products ran at: the mean
over the window's ``task_interval`` events of ``moe_rows_mean``, the step's
counter that is a routed layer's held pairs over its held experts (a mean over
the interval's steps and layers). 1536 at 16 of 64 experts under top-6 at
16384 tokens a step where the routing is even. None where the events carry no
such counter (a program without a routed layer)."""


def read(run):
    rows = [float(e["moe_rows_mean"]) for e in run.events("window", "task_interval")
            if e.get("moe_rows_mean")]
    return sum(rows) / len(rows) if rows else None
