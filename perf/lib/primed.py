"""The priming marker of ``perf/run.py``: a file in the XLA compile cache's
directory that says a checkout's cell has been primed -- and names what it
vouches for, so that a cache trimmed behind its back is primed again.

The marker is JSON: ``entries`` are the files of the persistent XLA cache
that the priming child wrote or, where JAX keeps a ``<key>-atime`` file
beside each entry (a cache with a size limit, which is the kind that gets
trimmed), read: the step programs, the window programs, the reference's.
``refusals`` are the records under ``saturn-refused/`` that stood at its end
(the programs the chip's compiler refused for memory, which a primed search
replays instead of compiling). A marker holds while every file it names is
still there. In a cache with no size limit a hit leaves no trace and the
marker names what the child wrote and the records: nothing trims such a
cache but its owner. Nothing here imports JAX.
"""

from __future__ import annotations

import json
import os
import time
from typing import List

REFUSED_SUBDIR = "saturn-refused"
ENTRY, ATIME = "-cache", "-atime"  # jax._src.lru_cache's two files of a key


def holds(marker: str, cache_dir: str) -> bool:
    """Whether ``marker`` exists and every file it names still does. A
    marker of the form before PR 32 (one line of text) names nothing and
    does not hold."""
    try:
        with open(marker) as f:
            said = json.load(f)
        named = list(said["entries"]) + list(said["refusals"])
    except (OSError, ValueError, KeyError, TypeError):
        return False
    gone = [n for n in named if not os.path.exists(os.path.join(cache_dir, n))]
    if gone:
        print(f"perf: {marker} names {len(gone)} file(s) of {len(named)} that are "
              f"gone (first: {gone[0]}): the cache was trimmed", flush=True)
    return not gone


def _files(cache_dir: str) -> List[str]:
    try:
        return sorted(n for n in os.listdir(cache_dir)
                      if os.path.isfile(os.path.join(cache_dir, n)))
    except OSError:
        return []


def write(marker: str, cache_dir: str, t_start: float) -> None:
    """Names the entries touched since ``t_start`` (an entry written, or its
    ``-atime`` file written by a hit) and the refusal records."""
    files = _files(cache_dir)
    touched = {n[:-len(ATIME)] + ENTRY if n.endswith(ATIME) else n for n in files
               if n.endswith((ENTRY, ATIME))
               and os.path.getmtime(os.path.join(cache_dir, n)) >= t_start - 1.0}
    entries = sorted(touched & set(files))
    refused = os.path.join(cache_dir, REFUSED_SUBDIR)
    refusals = [os.path.join(REFUSED_SUBDIR, n) for n in _files(refused)]
    os.makedirs(cache_dir, exist_ok=True)
    with open(marker + ".tmp", "w") as f:
        json.dump({"primed_s": round(time.time() - t_start, 1),
                   "entries": entries, "refusals": refusals}, f, indent=1)
    os.replace(marker + ".tmp", marker)
    print(f"perf: primed in {time.time() - t_start:.1f}s; the marker names "
          f"{len(entries)} cache entr(ies) and {len(refusals)} refusal record(s)",
          flush=True)
