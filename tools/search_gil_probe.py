"""Which calls of the thread that prepares ``search``'s grid points hold the
GIL long enough to make the measuring thread's clock late (PR 37; chip or fail, one chip, about
a minute a cell with a warm compile cache):

    chiprun -- python3 tools/search_gil_probe.py gpt2-medium.steady 3700000777

Runs the cell's timed search (``perf/lib/harness.py``) beside a probe thread
that waits 1 ms at a time; whenever it wakes more than 8 ms late it notes the
stack of the preparing thread (this one, ``search``'s caller), which is then
just back from whatever kept the interpreter's lock. Prints the search's spans as a timeline (which thread
did what when), the cyclic collector's passes (how many, how long, on which
thread), the late wakes by their two innermost frames, and the latest with
their stacks and the ``trial.timing`` they fell into.
Stacks at arbitrary Python lines (``_xla_gc_callback`` among them) are passes
of the cyclic collector: what ``utils/timing.py::undisturbed_clock`` keeps
out of a timed region. ``--disturbed`` runs the search without that clock's
settings, to see what they keep out.
"""

import argparse
import collections
import contextlib
import gc
import os
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LATE_S = 0.008


def probe(late, stop, preparing):
    gate = threading.Event()
    while not stop.is_set():
        t0 = time.perf_counter()
        gate.wait(0.001)
        lateness = time.perf_counter() - t0 - 0.001
        if lateness <= LATE_S:
            continue
        # the one frame, and not for long: a frame kept here keeps its locals,
        # and the measuring thread's are a train state on the chip (a kept
        # ``sys._current_frames()`` made GPT-J's second point run out of HBM)
        frame = sys._current_frames().get(preparing)
        stacks = []
        if frame is not None:
            stack = traceback.extract_stack(frame)[-7:]
            del frame
            stacks.append(" < ".join(
                f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
                for f in reversed(stack)))
        late.append((time.time(), lateness, stacks))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("--disturbed", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    if args.disturbed:
        from saturn_tpu.utils import timing

        timing.undisturbed_clock = contextlib.nullcontext
    from perf.lib import bench, harness

    run = harness.Run(bench.load_cell(args.cell, REPO), args.seed, 30.0, False,
                      time.time())
    harness.set_up(run)
    passes, began = [], {}

    def on_gc(phase, info):
        # the collector runs on whichever thread allocates, holding the GIL
        if phase == "start":
            began[threading.get_ident()] = time.perf_counter()
        else:
            passes.append((info["generation"], time.perf_counter()
                           - began.pop(threading.get_ident()),
                           threading.current_thread().name))

    gc.callbacks.append(on_gc)
    late, stop = [], threading.Event()
    thread = threading.Thread(
        target=probe, args=(late, stop, threading.get_ident()), daemon=True,
        name="gil-probe")
    thread.start()
    try:
        harness.timed_search(run)
    finally:
        stop.set()
        thread.join()
        gc.callbacks.remove(on_gc)
    import resource

    print(f"host memory: the process's resident peak "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20:.2f} GiB")
    for gen in (0, 1, 2):
        mine = [p for p in passes if p[0] == gen]
        by = collections.Counter(p[2] for p in mine)
        print(f"collector, generation {gen}: {len(mine)} passes, "
              f"{sum(p[1] for p in mine):.2f} s in all, the longest "
              f"{max((p[1] for p in mine), default=0) * 1e3:.0f} ms; by thread "
              f"{dict(by)}")
    spans = sorted((e for e in run.events("search", None)
                    if "dur_s" in e and e["kind"].startswith("trial")),
                   key=lambda e: e["ts_start"])
    t0 = spans[0]["ts_start"]
    print("the search's spans, by start: start s, seconds, thread, kind")
    for e in spans:
        what = e.get("outcome") or e.get("config") or ""
        print(f"  {e['ts_start'] - t0:7.2f} {e['dur_s']:7.2f}  "
              f"{e['thread']:<16} {e['kind']} {what}")
    timings = [(e["ts_start"], e["ts"]) for e in spans
               if e["kind"] == "trial.timing"]
    print(f"probe: {len(late)} wakes more than {LATE_S * 1e3:.0f} ms late; the "
          f"timings took {[round(b - a, 2) for a, b in timings]} s")
    count = collections.Counter()
    worst = collections.defaultdict(float)
    for _, lateness, stacks in late:
        for s in stacks or ["(the preparing thread has no frame)"]:
            key = " < ".join(s.split(" < ")[:2])
            count[key] += 1
            worst[key] = max(worst[key], lateness)
    for key, n in count.most_common(25):
        print(f"  {n:4d} x  worst {worst[key] * 1e3:7.1f} ms  {key}")
    print("the 12 latest wakes, with their stacks:")
    for at, lateness, stacks in sorted(late, key=lambda x: -x[1])[:12]:
        inside = [i for i, (a, b) in enumerate(timings) if a <= at <= b]
        print(f"  {lateness * 1e3:7.1f} ms late at "
              f"+{at - run.t_process_start:6.2f} s, inside timing {inside}:")
        for s in stacks:
            print("      " + s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
