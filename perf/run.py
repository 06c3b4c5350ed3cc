#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Chip or fail: without a TPU, or with another number of chips than the cell
asks for, the exit code is not 0 and no result is printed. The last line of
standard output is the result (one JSON object); everything before it is for
a reader. See perf/README.md.

The first run of a cell in a checkout finds no marker in the XLA compile
cache directory (one per cell, checkout path and source tree) and first runs
itself once as a child process (``--prime <marker>``)
that does the cell's search, warm-up and reference check and exits: it
compiles every program the cell uses into the cache, so that the timed search
of *every* run, the first included, meets a warm XLA cache, and writes the
marker, which names the cache entries and refusal records it vouches for
(``perf/lib/primed.py``): where one of them has gone since, the next run
primes again. This file imports
nothing of JAX before the child has ended (a chip belongs to one process).
"""

from __future__ import annotations

import time

_T0 = time.time()  # process start: set-up is clocked from here

import argparse
import hashlib
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def compile_cache_dir() -> str:
    """Where ``profile_cache.maybe_enable_persistent_compile_cache()`` will
    put the XLA cache: the environment's directory, else the checkout's."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO, ".jax_compile_cache"))


def checkout_fingerprint() -> str:
    """The checkout's path (part of every XLA cache key) and the content of
    every source file of the program and of the benchmark: a marker speaks
    for one checkout of one tree only, even where several share a cache
    directory or a later tree is unpacked at an earlier one's path."""
    h = hashlib.sha1(REPO.encode())
    for top in ("saturn_tpu", os.path.basename(HERE)):
        for folder, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs.sort()
            for name in sorted(files):
                if name.endswith((".py", ".json")):
                    path = os.path.join(folder, name)
                    h.update(os.path.relpath(path, REPO).encode())
                    with open(path, "rb") as f:
                        h.update(f.read())
    return h.hexdigest()[:12]


def prime_once(args) -> None:
    from perf.lib import primed  # imports nothing of JAX

    marker = os.path.join(compile_cache_dir(),
                          f"perf-primed.{args.workload}.{checkout_fingerprint()}")
    if os.environ.get("PERF_REHEARSAL_PLATFORM") or primed.holds(
            marker, compile_cache_dir()):
        return
    print(f"perf: no {marker} that holds: priming the XLA compile cache in a "
          f"child process first", flush=True)
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--workload", args.workload,
         "--seed", str(args.seed), "--seconds", str(args.seconds),
         "--trace", "0", "--prime", marker]
        + (["--bench-root", args.bench_root] if args.bench_root else []),
        stdout=sys.stderr)  # the child prints no result; keep stdout ours
    if child.returncode != 0 or not os.path.exists(marker):
        raise SystemExit(f"perf: the priming child failed ({child.returncode})")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--prime", default=None, metavar="MARKER", help=argparse.SUPPRESS)
    p.add_argument("--bench-root", default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    if not os.path.isdir(os.path.join(REPO, "saturn_tpu")):
        raise SystemExit("perf: the saturn_tpu package is not beside perf/")
    if not args.prime:
        prime_once(args)
    from perf.lib import harness

    return harness.main_run(args.workload, args.seed, args.seconds,
                            bool(args.trace), _T0, prime=args.prime,
                            root=args.bench_root, cache_dir=compile_cache_dir())


if __name__ == "__main__":
    sys.exit(main())
