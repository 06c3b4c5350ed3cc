"""Step program: steps of the window whose routed layers overflowed the row
buffer and took the exact second path (every held expert over every token
under a mask), from ``moe_second_path`` on the ``task_interval`` events (the
steps of an interval in which any layer did; 0 where the buffer held every
step's pairs). None where the events carry no such counter."""


def read(run):
    events = [e for e in run.events("window", "task_interval")
              if "moe_second_path" in e]
    if not events:
        return None
    return sum(float(e["moe_second_path"]) for e in events)
