"""Step program: the fullest held expert's rows over the mean held expert's,
from the routed layers' counters on the ``task_interval`` events
(``moe_rows_max``: the largest over the interval's steps and layers;
``moe_rows_mean``: held pairs a layer and step over the experts held): how
unevenly the router fills the grouped product's groups (1.0: evenly). None
where the events carry no such counters."""


def read(run):
    events = [e for e in run.events("window", "task_interval")
              if e.get("moe_rows_mean")]
    if not events:
        return None
    return (max(float(e["moe_rows_max"]) for e in events)
            / (sum(float(e["moe_rows_mean"]) for e in events) / len(events)))
