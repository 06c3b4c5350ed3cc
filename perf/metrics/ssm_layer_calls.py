"""Step program: calls of ``saturn_ssd_fwd`` the device ran a step, in the
traced window: the state-space layers of the stack times the forward passes
a step makes of a layer. The recurrence has no backward kernel yet, so the
differentiated forward runs once a layer without remat and twice with it (a
rematerialised layer's first forward is the vjp's forward rule too): 10.0 at
five such layers under remat when the device ran what the program says, 5.0
without; a dropped layer reads lower. None where the trace holds no such
kernel or the window's steps are not known."""


def read(run):
    if run.trace is None:
        return None
    steps = run.window.get("steps")
    calls = sum(len(dev["kernels"].get("saturn_ssd_fwd", ()))
                for dev in run.trace["devices"].values())
    if not steps or not calls:
        return None
    return calls / (steps * len(run.trace["devices"]))
