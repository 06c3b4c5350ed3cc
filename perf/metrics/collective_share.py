"""Technique: share of a chip's busy time in which a collective (all-gather,
reduce-scatter, all-reduce, collective-permute) was in flight, from the
device trace, mean over the chips (``perf/lib/trace_reduce.py``). None where
the trace holds no collective (a one-chip block)."""


def read(run):
    t = run.trace
    if t is None or not t.get("n_collectives") or t["busy_s"] <= 0:
        return None
    shares = [100.0 * d["collective_s"] / d["busy_s"]
              for d in t["devices"].values() if d["busy_s"] > 0]
    return sum(shares) / len(shares) if shares else None
