"""Engine: bytes over seconds of the ``ckpt.write`` spans (shard files and
manifest, on the writer threads), in GB/s. None where the program emits no
such span."""

from perf.lib import spans


def read(run):
    events, root = spans.window_events(run)
    if root is None:
        return None
    writes = spans.spans(events, "ckpt.write")
    seconds = sum(e["dur_s"] for e in writes)
    if seconds <= 0:
        return None
    return sum(float(e.get("bytes", 0)) for e in writes) / seconds / 1e9
