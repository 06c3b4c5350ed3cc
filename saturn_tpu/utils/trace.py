"""Profiler tracing: wrap a region in a jax.profiler trace.

The reference had no tracer at all — profiling was wall-clock timing only
(SURVEY.md §5 "Tracing / profiling: no tracer"). Here wall-clock timing stays
the scheduling signal (``utils/timing.py``), and this adds the TPU-native
deep-dive: XLA/TPU traces viewable in TensorBoard/Perfetto, produced by
passing ``trace_dir=`` to ``search``/``orchestrate``. The trace holds the
device's ops and, on the host plane above them, the program's own spans
(``saturn.<name>``, one per ``metrics.span``: ``docs/architecture.md``,
"Metrics stream & spans") — not every Python call: the Python tracer is off.
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
    # Python tracer off, host tracer at the level that records annotations:
    # JAX's defaults record every Python call, which slows the host it is
    # meant to measure and buries the spans.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", trace_dir)
