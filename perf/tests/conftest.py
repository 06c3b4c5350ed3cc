"""``python -m pytest perf/tests`` runs on the CPU: hold JAX to it before any
test imports it, and make the repo importable from any working directory."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
# four virtual CPU devices, for the tests of what exists only across chips
# (the sharded reference, the four-chip cell's rehearsal)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
