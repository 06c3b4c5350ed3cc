"""PR 46: the interpreter exits after a several-lane save, whatever the save
came to. PR 43 built the lanes and was refused because a benchmark run left
a process behind; this is the test it lacked: a child ``python -c`` that
saves with ``save_async`` and then flushes, or returns without flushing, or
has a crash barrier kill a lane, must *return* within its own time limit.

The children lower the module's private size threshold so that a tree of a
few tens of MB takes ``_LANES`` lanes (it is a constant, not a knob: no
environment name reads it).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from saturn_tpu.utils import checkpoint as ckpt

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import os, sys, threading
import numpy as np
from saturn_tpu.utils import checkpoint as ckpt

ckpt._LANE_MIN_BYTES = 1
path = sys.argv[1]
# 48 MB in eight members and a 0-d one: four lanes, still writing when
# save_async returns
tree = {f"m{i}": np.full((6 << 20) // 4, i, np.float32) for i in range(8)}
tree["step"] = np.asarray(7, np.int32)
"""

CASES = {
    "flushes_and_returns": """
        ckpt.save_async(path, tree)
        ckpt.flush()
        left = [t.name for t in threading.enumerate()
                if t.name.startswith("ckpt-")]
        print("left", left)
        sys.exit(1 if left else 0)
    """,
    "returns_without_flushing": """
        ckpt.save_async(path, tree)
        print("returned")
    """,
    "a_lane_is_killed_then_flush": """
        from saturn_tpu.resilience.crash import CrashInjector
        ckpt.set_crash_barrier(CrashInjector("mid-shard-write").barrier)
        ckpt.save_async(path, tree)
        try:
            ckpt.flush()
        except RuntimeError as e:
            print("raised", type(e.__cause__).__name__)
        left = [t.name for t in threading.enumerate()
                if t.name.startswith("ckpt-")]
        print("left", left)
    """,
    "a_lane_is_killed_and_nobody_joins": """
        from saturn_tpu.resilience.crash import CrashInjector
        ckpt.set_crash_barrier(CrashInjector("mid-shard-write").barrier)
        ckpt.save_async(path, tree)
        print("returned")
    """,
    "the_manifest_is_killed_then_save_again": """
        from saturn_tpu.resilience.crash import CrashInjector, SimulatedKill
        ckpt.set_crash_barrier(CrashInjector("pre-manifest-rename").barrier)
        try:
            ckpt.save(path, tree)
        except SimulatedKill:
            print("raised SimulatedKill")
        ckpt.set_crash_barrier(None)
        ckpt.save(path, tree)
        print("left", [t.name for t in threading.enumerate()
                       if t.name.startswith("ckpt-")])
    """,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_interpreter_exits(tmp_path, case):
    path = str(tmp_path / "t.npz")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    script = PRELUDE + textwrap.dedent(CASES[case])
    # the limit is the test: a thread that is no daemon, or a wait for a
    # mark that never comes, keeps the child and fails here
    done = subprocess.run([sys.executable, "-c", script, path], env=env,
                          capture_output=True, text=True, timeout=60)
    out = done.stdout + done.stderr
    tmps = [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    if case == "flushes_and_returns":
        assert done.returncode == 0, out
        assert "left []" in done.stdout
        assert ckpt.verify(path) and not tmps
        lanes = [n for n in os.listdir(tmp_path) if ckpt._SHARD_RE.search(n)]
        assert len(lanes) == ckpt._LANES
        got = ckpt.load_arrays(path)
        assert int(got["step"]) == 7 and float(got["m5"][-1]) == 5.0
    elif case == "a_lane_is_killed_then_flush":
        assert done.returncode == 0, out
        assert "raised SimulatedKill" in done.stdout and "left []" in done.stdout
        assert not os.path.exists(path) and not tmps
    elif case == "the_manifest_is_killed_then_save_again":
        assert done.returncode == 0, out
        assert "raised SimulatedKill" in done.stdout and "left []" in done.stdout
        assert ckpt.verify(path) and not tmps
    else:
        # whatever the exit caught the lanes at, a whole checkpoint or none
        assert "returned" in done.stdout, out
        assert not os.path.exists(path) or ckpt.verify(path)
