"""Step program: sliding-window layers the device ran a period, against what
the program says it holds: calls of ``saturn_swa_dq`` in the traced window
(the backward's one call a sliding layer and step, with remat or without) over
steps x periods, the periods from ``stack_layers``, ``stack_lead`` and
``stack_kinds`` of the ``task_interval`` events. Reads the period's count of
sliding layers (3.0) when the device ran what the program says; a sliding
layer run through the full kernel (``saturn_flash_dq``) is not counted and
reads lower, 0 where all are. None where the events carry no ``stack_kinds``
with a ``sliding_attention`` entry."""

import json


def read(run):
    if run.trace is None:
        return None
    intervals = run.events("window", "task_interval")
    stacks = {json.dumps([e.get("stack_layers"), e.get("stack_kinds"),
                          e.get("stack_lead")], sort_keys=True) for e in intervals}
    steps = run.window.get("steps")
    if len(stacks) != 1 or not steps:
        return None
    layers, kinds, lead = json.loads(stacks.pop())
    if not layers or not kinds or "sliding_attention" not in kinds:
        return None
    periods = (layers - sum((lead or {}).values())) // sum(kinds.values())
    if not periods:
        return None
    calls = sum(len(dev["kernels"].get("saturn_swa_dq", ()))
                for dev in run.trace["devices"].values())
    return calls / (steps * periods * len(run.trace["devices"]))
