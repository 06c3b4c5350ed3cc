"""The routing's drift inside a window, step by step and layer by layer, at
the LFM2 cell's own widths and batch -- the builder's chip script, not part of
the benchmark's runs.

    chiprun -- python3 perf/tests/lfm2_drift_on_chip.py [--seeds 3] [--steps 40]

For each seed of tokens: the cell's own batches (``harness.make_task``: Zipf
ids over the held rows, the window's ``dataset_batches`` walked round) through
``perf/reference/lfm2.py::train`` from the seeded weights at the cell's lr,
which hands back every routed layer's choice on each step's tokens
(``held_rows``). A step's row a layer is what ``ops/moe.py::routed_layout``
would lay out for it: every held expert's rows rounded up to whole tiles, at
least one, against the row buffer ``routed_plan`` sizes from
``run.overrides.routed_buffer``; over it the program's step takes the exact
second path (``moe_second_path``). Prints a line a step and writes
``chiprun_out/lfm2_drift.<cell>.json`` after every seed: the curve the cell's
window length is held against (PERF.md, PR 52).
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="lfm2-8b-a1b-1chip.steady-8k")
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_147_483_659)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--batch", type=int, default=None, help="the job's own unless given")
    p.add_argument("--bench-root", default=None)
    args = p.parse_args()

    from perf.lib import bench, harness
    from saturn_tpu.ops import moe
    from saturn_tpu.utils import profile_cache

    cell = bench.load_cell(args.workload, args.bench_root)
    harness.accelerator_devices(cell.chips)
    profile_cache.maybe_enable_persistent_compile_cache()
    job = harness.plan_jobs(cell.traffic, 10.0)[0]
    batch = job.batch if args.batch is None else args.batch
    ref = harness.reference_module(cell.config)
    arch = ref.arch_from_config(cell.config, job.seq)
    plan = moe.routed_plan(batch * job.seq, arch.experts, arch.held, arch.top_k,
                           buffer=cell.config["run"]["overrides"].get("routed_buffer"))
    tile = plan.row_tile
    tmp = tempfile.mkdtemp(prefix="perf-lfm2-drift-")
    os.makedirs("chiprun_out", exist_ok=True)
    out = os.path.join("chiprun_out", f"lfm2_drift.{args.workload}.json")
    curves = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        task = harness.make_task(cell.config, cell.traffic, job, seed, tmp, name="drift",
                                 batch=batch, batch_count=args.steps)
        batches = [task.batch_at(k) for k in range(args.steps)]
        routing = []
        losses, _ = ref.train(arch, harness.weight_seed(cell.config), batches, job.lr,
                              routing=routing)
        steps = []
        for k, got in enumerate(routing):
            rows = [sum(max(1, -(-c // tile)) * tile for c in layer)
                    for layer in got["counts"]]
            steps.append({"step": k, "loss": losses[k], "rows": rows,
                          "pairs": [sum(layer) for layer in got["counts"]],
                          "fullest": [max(layer) for layer in got["counts"]]})
            print(f"drift seed {seed} step {k:2d}: rows a layer {rows} of {plan.rows} "
                  f"({max(rows) / plan.rows:.3f} of the buffer at the fullest layer"
                  f"{', OVER' if max(rows) > plan.rows else ''}); pairs over the mean "
                  + ", ".join(f"{sum(layer) / got['mean']:.3f}" for layer in got["counts"])
                  + "; the fullest expert's rows over an even share "
                  + ", ".join(f"{max(layer) * arch.held / got['mean']:.2f}"
                              for layer in got["counts"]), flush=True)
        curves.append({"seed": seed, "steps": steps})
        with open(out, "w") as f:
            json.dump({"workload": args.workload, "batch": batch, "seq": job.seq,
                       "buffer_rows": plan.rows, "row_tile": tile,
                       "mean_pairs": batch * job.seq * arch.top_k * arch.held / arch.experts,
                       "curves": curves}, f)
    first_over = [next((s["step"] for s in c["steps"] if max(s["rows"]) > plan.rows), None)
                  for c in curves]
    print(f"drift: the first step over the buffer, by seed: {first_over}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
