"""Step program: passes over the layer stack that the device ran, per layer
the program says it holds: calls of ``saturn_flash_dq`` in the traced window
(the backward's one call per layer *application*; the forward kernel runs
twice under remat) over steps x ``stack_layers`` of the ``task_interval``
events. Reads the model's pass count (4.0 at Ouro) when the device ran what
the program says it ran; a dropped or folded pass reads lower. None where the
events carry no ``stack_layers`` (a program older than PR 28) or the trace
holds no such kernel (search chose dense attention)."""


def read(run):
    if run.trace is None:
        return None
    layers = {e.get("stack_layers") for e in run.events("window", "task_interval")}
    steps = run.window.get("steps")
    if len(layers) != 1 or not steps:
        return None
    (per_stack,) = layers
    calls = sum(len(dev["kernels"].get("saturn_flash_dq", ()))
                for dev in run.trace["devices"].values())
    if not per_stack or not calls:
        return None
    return calls / (steps * per_stack * len(run.trace["devices"]))
