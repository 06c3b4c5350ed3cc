"""Profiler tracing: wrap a region in a jax.profiler trace.

The reference had no tracer at all — profiling was wall-clock timing only
(SURVEY.md §5 "Tracing / profiling: no tracer"). Here wall-clock timing stays
the scheduling signal (``utils/timing.py``), and this adds the TPU-native
deep-dive: XLA/TPU traces viewable in TensorBoard/Perfetto, produced by
passing ``trace_dir=`` to ``search``/``orchestrate``.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Iterator, Optional

log = logging.getLogger("saturn_tpu")


@contextlib.contextmanager
def profile_trace(trace_dir: Optional[str]) -> Iterator[None]:
    """Trace the enclosed region to ``trace_dir`` (no-op when None)."""
    if not trace_dir:
        yield
        return
    import jax

    # A trace that was asked for and cannot start is an error of the run, not
    # a warning: a run that quietly carries no trace measures nothing.
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", trace_dir)
