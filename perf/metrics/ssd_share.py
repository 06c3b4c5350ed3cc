"""Kernels: device time of the state-space recurrence's kernels
(``saturn_ssd_*``) over the device's busy time in the traced window: how much
of the device's work the recurrence's own kernels are. (Its backward runs as
plain XLA ops until it has a kernel, and is not in it; the projections, the
convolution and the gated norm are matrices and elementwise ops like any
layer's.) None where the trace holds no such kernel."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    lo, hi = run.trace["window_ns"]
    took = sum(min(s + d, hi) - max(s, lo)
               for dev in run.trace["devices"].values()
               for kernel, calls in dev["kernels"].items()
               if kernel.startswith("saturn_ssd_")
               for s, d in calls if s + d > lo and s < hi)
    if took <= 0:
        return None
    return 100.0 * took / 1e9 / (run.trace["busy_s"] * len(run.trace["devices"]))
