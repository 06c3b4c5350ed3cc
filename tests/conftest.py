"""Test harness: 8 virtual CPU devices (SURVEY.md §4's test-pyramid plan).

Multi-device behavior is tested without TPU hardware via XLA's host-platform
device emulation — the TPU-native analog of the reference's fake-8-GPUs solver
stub (``milp.py:57-62``), but as a proper fixture instead of a hardcoded flag.
Must run before jax initializes its backends, hence top of conftest.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
def _supports_collective_timeout_flag() -> bool:
    """Does this jaxlib's XLA know the collective-timeout flag?

    XLA FATALLY aborts on unknown XLA_FLAGS at first backend init
    (``parse_flags_from_env.cc``), which would take down the whole suite at
    the first test that touches a device — so probe in a subprocess first.
    The verdict is cached in a tmp sentinel keyed on the jaxlib version
    (the probe costs a ~3s jax import).
    """
    import json
    import subprocess
    import sys
    import tempfile

    import jaxlib.version

    sentinel = os.path.join(tempfile.gettempdir(), "saturn_xla_flag_probe.json")
    try:
        with open(sentinel) as f:
            rec = json.load(f)
        if rec.get("jaxlib") == jaxlib.version.__version__:
            return bool(rec["supported"])
    except (OSError, ValueError, KeyError):
        pass
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_cpu_collective_call_terminate_timeout_seconds=600"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", "import jax; jax.devices()"],
        capture_output=True,
        env=env,
        timeout=120,
    )
    ok = r.returncode == 0
    try:
        tmp = f"{sentinel}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"jaxlib": jaxlib.version.__version__, "supported": ok}, f)
        os.replace(tmp, sentinel)
    except OSError:
        pass
    return ok


if (
    "collective_call_terminate_timeout" not in os.environ["XLA_FLAGS"]
    and _supports_collective_timeout_flag()
):
    # 8 emulated devices = 8 collective threads timesharing this host's ONE
    # core: XLA's default 40s cross-module-collective rendezvous abort
    # ("Termination timeout ... Exiting") fires spuriously under load
    # (observed on ppermute pipeline tests). Give stragglers 10 minutes.
    # NOTE the flag is baked into compiled programs: clear the persistent
    # cache below if it predates a change to this value.
    os.environ["XLA_FLAGS"] += (
        " --xla_cpu_collective_call_terminate_timeout_seconds=600"
    )
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: off here unless JAX_COMPILATION_CACHE_DIR is
# set from outside (the package sets a directory of its own only on a TPU
# backend, ``utils/profile_cache.maybe_enable_persistent_compile_cache``).
# The reason it stays off by default: XLA:CPU loads cache entries written by
# an execution context whose CPU feature detection differed (cpu_aot_loader's
# "machine type doesn't match" is a warning) and runs them — wrong code that
# silently kills partition threads and wedges every later 8-partition
# collective program. Cold compiles cost minutes; a poisoned cache costs the
# whole suite.


import numpy as np
import pytest


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def tiny_task(tmp_path):
    """A GPT-2 test-tiny task over a synthetic corpus — fast on CPU."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    def get_model(**kw):
        return build_gpt2("test-tiny", **kw)

    def get_loader():
        return make_lm_dataset(
            context_length=64, batch_size=8, vocab_size=256, n_tokens=64 * 8 * 8
        )

    return Task(
        get_model=get_model,
        get_dataloader=get_loader,
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=16),
        save_dir=str(tmp_path / "ckpts"),
    )


