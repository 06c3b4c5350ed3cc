"""What one ``metrics.span()`` costs: nanoseconds a span with no sink, with a
sink, and with a sink under a running profiler (1e5 spans each), on the
machine it is run on. A builder's script (PERF.md, PR 26), not a test:

    chiprun -- python perf/tests/span_cost.py
"""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax

from saturn_tpu.utils import metrics

N = 100_000


def burst(n=N):
    t0 = time.perf_counter()
    for _ in range(n):
        with metrics.span("cost.probe", task="t", k=8):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="span-cost-")
    burst(1000)  # imports, first use
    print(f"span_cost: device {jax.devices()[0].device_kind}; {N} spans each")
    print(f"span_cost: no sink, no profiler: {burst():.0f} ns a span")
    with metrics.scoped(os.path.join(tmp, "a.jsonl")):
        print(f"span_cost: sink, no profiler: {burst():.0f} ns a span")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(os.path.join(tmp, "trace"), profiler_options=opts)
    try:
        print(f"span_cost: no sink, profiler running: {burst():.0f} ns a span")
        with metrics.scoped(os.path.join(tmp, "b.jsonl")):
            print(f"span_cost: sink, profiler running: {burst():.0f} ns a span")
    finally:
        jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main())
