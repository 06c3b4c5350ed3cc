"""Runs a list of benchmark runs one after another, each in its own process
and from its own checkout, and keeps every line they print: the builder's
chip script behind PERF.md's PR 26 numbers (parent against change, traced
against untraced, the span tables).

    chiprun --timeout 3400 -- python perf/tests/span_runs.py \
        parent:gptj-6b-1chip.steady:0:11 change:gptj-6b-1chip.steady:0:11 ...

A run is ``<checkout>:<cell>:<trace>:<seed>``; ``<checkout>`` is a directory
under ``perf_checkout/`` (a ``git archive`` copy) or ``.`` for the tree the
script is in. Whole outputs go to ``chiprun_out/span_runs/``; the result
line and the lines about spans are printed. This parent never imports JAX
(a chip belongs to one process).
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
KEEP = ("spans of", "perf:   ", "trace clock", "window: orchestrate wall",
        "search: wall", "NOT CORRECT", "metric ", "primed in", "trace:")


def main(argv) -> int:
    out_dir = os.path.join(REPO, "chiprun_out", "span_runs")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    failed = 0
    for i, spec in enumerate(argv):
        where, cell, trace, seed = spec.split(":")
        root = REPO if where == "." else os.path.join(REPO, "perf_checkout", where)
        t0 = time.time()
        done = subprocess.run(
            [sys.executable, os.path.join(root, "perf", "run.py"), "--workload", cell,
             "--seed", seed, "--seconds", str(seconds), "--trace", trace],
            capture_output=True, text=True, cwd=root)
        with open(os.path.join(out_dir, f"{i:02d}.{where.strip('.') or 'tree'}."
                                        f"{cell}.t{trace}.log"), "w") as f:
            f.write(done.stdout + "\n==== stderr\n" + done.stderr[-20000:])
        lines = done.stdout.strip().splitlines()
        print(f"=== run {i} {spec}: rc {done.returncode}, {time.time() - t0:.0f}s",
              flush=True)
        for line in lines[:-1]:
            if any(k in line for k in KEEP):
                print(line, flush=True)
        try:
            result = json.loads(lines[-1])
            print("RESULT", spec, json.dumps(
                {"correct": result["correct"],
                 "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                 "device": result["device"],
                 "idle_gaps": result.get("breakdown", {}).get("idle_gaps")}),
                flush=True)
            failed += not result["correct"]
        except (IndexError, ValueError, KeyError):
            failed += 1
            print("NO RESULT", done.stdout[-1500:], done.stderr[-2500:], flush=True)
    print(f"{len(argv)} runs, {failed} failed or not correct", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
