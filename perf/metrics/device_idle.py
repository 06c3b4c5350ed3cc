"""Device: share of the traced window in which no operation ran on the
device (1 - union of the device's op intervals over the window), averaged
over the chips used."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0 or not run.trace["n_devices"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
