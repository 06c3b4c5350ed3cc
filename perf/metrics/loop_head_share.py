"""Kernels: device time of the fused head + loss kernels (``saturn_ce_*``)
over the device's busy time in the traced window. The head is counted once a
step and the stack ``passes`` times, so in a looped model's cell this is
small (38 % in the GPT-J cell): the share of the device's work that the loop
does not multiply. None where the trace holds no such kernel."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    lo, hi = run.trace["window_ns"]
    took = sum(min(s + d, hi) - max(s, lo)
               for dev in run.trace["devices"].values()
               for kernel, calls in dev["kernels"].items()
               if kernel.startswith("saturn_ce_")
               for s, d in calls if s + d > lo and s < hi)
    if took <= 0:
        return None
    return 100.0 * took / 1e9 / (run.trace["busy_s"] * len(run.trace["devices"]))
