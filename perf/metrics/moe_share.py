"""Kernels: device time of the grouped-product kernels (``saturn_gmm_*``) over
the device's busy time in the traced window: how much of the device's work the
routed experts' own kernels are. (The sort, the gathers between token order
and row order, the router and the shared expert are plain XLA ops and are not
in it.) None where the trace holds no such kernel."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    lo, hi = run.trace["window_ns"]
    took = sum(min(s + d, hi) - max(s, lo)
               for dev in run.trace["devices"].values()
               for kernel, calls in dev["kernels"].items()
               if kernel.startswith("saturn_gmm_")
               for s, d in calls if s + d > lo and s < hi)
    if took <= 0:
        return None
    return 100.0 * took / 1e9 / (run.trace["busy_s"] * len(run.trace["devices"]))
