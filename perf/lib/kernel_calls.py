"""The calls of named kernels in a traced window, each with the job that was
running: what a roofline reader walks (``readers.kernel_roofline`` has the
same loop for the two families it knows)."""

from __future__ import annotations

from typing import Any, Callable, Iterator, Tuple

from perf.lib import readers


def owned_calls(run: Any, wanted: Callable[[str], bool]) -> Iterator[Tuple[str, Any, float]]:
    """(kernel, the job of the interval the call fell into, the call's device
    seconds) for every call of a kernel ``wanted`` names; a call outside
    every interval is left out."""
    off, spans = run.trace["wall_offset_s"], readers.work_spans(run)
    for dev in run.trace["devices"].values():
        for kernel, calls in dev["kernels"].items():
            if not wanted(kernel):
                continue
            for start_ns, dur_ns in calls:
                wall = start_ns / 1e9 + off
                owner = next((s for s in spans
                              if s["start"] - 0.5 <= wall <= s["end"] + 0.5), None)
                if owner is not None:
                    yield kernel, run.job(owner["task"]), dur_ns / 1e9
