"""Step program: linear-attention layers the device ran a period, against
what the program says it holds: calls of ``saturn_gdn_fwd`` in the traced
window over steps x periods x the forward passes a step makes of a layer,
the periods from ``stack_layers`` and ``stack_kinds`` of the ``task_interval``
events. The rule has no backward kernel yet, so no kernel of it runs once a
layer and step: the differentiated forward runs once without remat and twice
with it (a rematerialised layer's first forward is the vjp's forward rule
too, and keeps the chunks' states like the second), and the chosen grid
point's ``remat`` says which. Reads the period's count of linear layers (3.0)
when the device ran what the program says; a dropped layer reads lower. None
where the events carry no ``stack_kinds`` (a program without several block
kinds) or the trace holds no such kernel."""

import json


def read(run):
    if run.trace is None:
        return None
    intervals = run.events("window", "task_interval")
    stacks = {json.dumps([e.get("stack_layers"), e.get("stack_kinds")], sort_keys=True)
              for e in intervals}
    remat = {bool(run.chosen.get(e["task"], {}).get("params", {}).get("remat"))
             for e in intervals}
    steps = run.window.get("steps")
    if len(stacks) != 1 or len(remat) != 1 or not steps:
        return None
    layers, kinds = json.loads(stacks.pop())
    if not layers or not kinds:
        return None
    periods = layers // sum(kinds.values())
    calls = sum(len(dev["kernels"].get("saturn_gdn_fwd", ()))
                for dev in run.trace["devices"].values())
    if not periods or not calls:
        return None
    passes = 2 if remat.pop() else 1
    return calls / (steps * periods * passes * len(run.trace["devices"]))
