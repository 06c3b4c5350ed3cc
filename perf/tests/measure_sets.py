"""Runs a cell as the bounds are set from: ``--sets`` sets of ``--runs`` runs,
the same seeds in every set, one process a run (this parent never imports
JAX, so the chip is the child's), and prints for each end-to-end metric and
set the median and the spread (distance between the first and third quartile
by ``statistics.quantiles(values, n=4)``, as a share of the median).

    chiprun --timeout 3000 -- python perf/tests/measure_sets.py \
        --workload <cell> --sets 2 --runs 6

Every result line goes to ``chiprun_out/sets.<cell>.json``. A builder's tool,
not a test and not part of a run.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SEEDS = [11, 1000003, 2147483659, 2147499999, 987654321, 1234567890,
         2147483647, 42, 2200000001, 31337, 1618033988, 271828182]


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=int, default=None)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--first-seed", type=int, default=0, help="index into SEEDS")
    args = p.parse_args()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for s in range(args.sets):
        for r in range(args.runs):
            seed = SEEDS[(args.first_seed + r) % len(SEEDS)]
            t0 = time.time()
            done = subprocess.run(
                [sys.executable, os.path.join(REPO, "perf", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(args.trace)],
                capture_output=True, text=True)
            lines = done.stdout.strip().splitlines()
            row = {"set": s, "run": r, "seed": seed, "rc": done.returncode,
                   "wall_s": time.time() - t0}
            try:
                row["result"] = json.loads(lines[-1])
            except (IndexError, ValueError):
                row["tail"] = (done.stdout[-1500:], done.stderr[-1500:])
            row["said"] = [l for l in lines[:-1] if l.startswith("perf: ")
                           and any(k in l for k in ("search: wall", "window:", "warm-up",
                                                    "reference check", "NOT", "primed"))]
            rows.append(row)
            res = row.get("result", {})
            print(f"set {s} run {r} seed {seed}: rc {done.returncode}, "
                  f"{row['wall_s']:.0f}s, correct {res.get('correct')}, "
                  + ", ".join(f"{k} {v['value']:.6g}" for k, v in
                              res.get("metrics", {}).items()), flush=True)
            if "tail" in row:
                print(row["tail"][0][-800:], row["tail"][1][-800:], flush=True)
            with open(os.path.join(out_dir, f"sets.{args.workload}.json"), "w") as f:
                json.dump(rows, f, indent=1)
    names = sorted({k for row in rows for k in row.get("result", {}).get("metrics", {})})
    for name in names:
        for s in range(args.sets):
            vals = [row["result"]["metrics"][name]["value"] for row in rows
                    if row["set"] == s and name in row.get("result", {}).get("metrics", {})]
            if vals:
                print(f"{name} set {s}: median {statistics.median(vals):.6g}, "
                      f"spread {100 * spread(vals):.3f}% of the median, "
                      f"min {min(vals):.6g}, max {max(vals):.6g} ({len(vals)} runs)",
                      flush=True)
    bad = [r for r in rows if r["rc"] != 0 or not r.get("result", {}).get("correct")]
    print(f"{len(rows)} runs, {len(bad)} not correct or failed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
