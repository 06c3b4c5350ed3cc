"""Step program: full-attention layers the device ran a period, where the
period's other layers are short convolutions: calls of ``saturn_flash_dq`` in
the traced window (the backward's one call a full-attention layer and step,
with remat or without) over steps x periods, the periods from
``stack_layers``, ``stack_lead`` and ``stack_kinds`` of the ``task_interval``
events. Reads the period's count of full-attention layers (1.0) when the
device ran what the program says: a convolution layer run through the
attention kernel reads higher, a full layer run through the plain twin lower.
None where the events carry no ``stack_kinds`` with a ``conv`` entry."""

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
    if not layers or not kinds or "conv" not in kinds:
        return None
    periods = (layers - sum((lead or {}).values())) // sum(kinds.values())
    if not periods:
        return None
    calls = sum(len(dev["kernels"].get("saturn_flash_dq", ()))
                for dev in run.trace["devices"].values())
    return calls / (steps * periods * len(run.trace["devices"]))
