"""What a cell's search costs the host, point by point (PR 41): the
benchmark's own set-up and timed search of one cell and nothing after them
(no warm-up, no window, no reference check), in a checkout given by path, so
that two trees can be read beside each other in one ``chiprun`` call:

    python3 tools/search_host_cost.py --tree perf_checkout/parent \
        --workload olmo-hybrid-7b-1chip.steady-8k --seed 41 [--profile OUT.pstats]

Chip or fail, as ``perf/run.py`` is. A tree's first run compiles its
programs into the XLA cache (run it once before the reading). Prints the
harness's ``search: wall`` line, then for each grid point how it ended
(``unbuilt`` where its point record ended it before anything was built, PR
47), what its ``trial.identity`` cost and what its fused head's rungs did
(``ce_ladder``, PR 51: a refused rung's planned bytes), then the spans of its
preparation (``trial.build`` / ``trial.compile`` / ``trial.memory_check``)
with JAX's own seconds on them (``trace_s`` / ``lower_s`` / ``cache_read_s``
/ ``compile_s``, nested traces counted once) and the collector's, then the
search's spans summed by name. ``--profile`` runs the search under cProfile
(the caller's thread: the one that prepares), prints the 40 functions with
the most own time and writes the stats. ``perf/`` is read, not edited.
"""

from __future__ import annotations

import argparse
import collections
import cProfile
import os
import pstats
import sys
import time

_T0 = time.time()
STAMPS = ("trace_s", "lower_s", "cache_read_s", "compile_s", "gc_s")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tree", required=True, help="root of a checkout")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--profile", default=None, metavar="OUT.pstats")
    p.add_argument("--bench-root", default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    from perf.lib import bench, harness

    run = harness.Run(bench.load_cell(args.workload, args.bench_root), args.seed, 30.0,
                      False, _T0)
    harness.set_up(run)
    try:
        prof = cProfile.Profile() if args.profile else None
        if prof:
            prof.enable()
        try:
            harness.timed_search(run)
        finally:
            if prof:
                prof.disable()
        spans = [e for e in run.events("search", None) if "dur_s" in e]
        by_id = {e["id"]: e for e in spans}

        def point_of(e):
            while e is not None and e["kind"] != "trial.config":
                e = by_id.get(e.get("parent"))
            return e

        import saturn_tpu

        print(f"host: {os.path.relpath(tree)} {args.workload}: search "
              f"{run.search['wall_s']:.2f}s (package "
              f"{os.path.relpath(os.path.dirname(saturn_tpu.__file__))})",
              flush=True)
        for e in sorted(spans, key=lambda e: e["ts_start"]):
            if e["kind"] != "trial.config":
                continue
            identity = [c for c in spans if c["kind"] == "trial.identity"
                        and c.get("parent") == e["id"]]
            how = " ".join(f"{k}={e[k]}" for k in ("refusal", "implied_by") if k in e)
            cost = "none" if not identity else (
                f"{sum(c['dur_s'] for c in identity):.3f}s"
                + "".join(f" (no identity: {c['why']})" for c in identity if "why" in c))
            print(f"host: point {e.get('config')} ended {e.get('outcome')}"
                  f"{' unbuilt' if e.get('unbuilt') else ''} {how} in "
                  f"{e['dur_s']:.2f}s; trial.identity {cost}", flush=True)
            if "ce_ladder" in e:   # the fused head's rungs (PR 51)
                print(f"host:   ce_ladder {e['ce_ladder']}", flush=True)
        for e in sorted(spans, key=lambda e: e["ts_start"]):
            point = point_of(e)
            if point is None or e is point or not e["kind"].startswith("trial."):
                continue
            stamps = " ".join(f"{k} {e[k]:.2f}" for k in STAMPS if e.get(k))
            print(f"host:   {point.get('config')} {e['kind']}"
                  f"{'/' + e['program'] if 'program' in e else ''} "
                  f"{e['dur_s']:.2f}s [{e['thread']}] {stamps}", flush=True)
        total = collections.defaultdict(lambda: collections.defaultdict(float))
        for e in spans:
            total[e["kind"]]["dur_s"] += e["dur_s"]
            for k in STAMPS:
                total[e["kind"]][k] += e.get(k, 0.0)
        for kind, t in sorted(total.items(), key=lambda kv: -kv[1]["dur_s"]):
            print(f"host: sum {kind} {t['dur_s']:.2f}s "
                  + " ".join(f"{k} {t[k]:.2f}" for k in STAMPS if t[k]),
                  flush=True)
        if prof:
            prof.dump_stats(args.profile)
            stats = pstats.Stats(prof, stream=sys.stdout)
            stats.sort_stats("tottime").print_stats(40)
        return 0
    finally:
        import shutil

        shutil.rmtree(run.tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
