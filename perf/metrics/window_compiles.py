"""Engine: backend (XLA) compiles inside the window, counted from
``jax.monitoring``. A persistent-cache hit is not a compile."""


def read(run):
    if "clock" not in run.window:
        return None
    return float(run.window["clock"]["backend_compiles"])
