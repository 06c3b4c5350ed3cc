"""The readings the limits of ``perf/reference/limits.json`` are set from, at
a cell's own size, on the chip -- not part of the benchmark's runs.

    chiprun -- python perf/tests/control_on_chip.py --workload <cell> \
        --seeds 12 --control-seeds 3 --grid-point '{"remat": true, "attention": "flash"}'

For each seed (of tokens; the weights are the configuration's fixed
``run.weight_seed``, as in every run), in one process and with no search: the
plain reference, the program (the cell's technique at the given grid point,
through the same ``refcheck`` calls a run makes, its checkpoint read back),
and for the first ``--control-seeds`` seeds the control (the reference with
its matmuls, forward and backward, in fp8). In a cell of more than one chip
the reference and the control are sharded over the cell's chips as in a run
(``--grid-point '{"remat": true, "offload": false, "attention": "dense"}'``
is the four-chip cell's). Every task has the same name, so
the technique compiles once; the reference's jitted pieces are made once
too. Prints every number and writes them to
``chiprun_out/control.<cell>.json`` after every seed. Every side's numbers
go through ``refcheck.verdict`` under the committed limits, as a run's do:
the exit code is 1 if a control came out correct or the program did not.
``test_control.py`` keeps the same comparison at tiny size on the CPU.
"""

import argparse
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_147_483_659)
    p.add_argument("--grid-point", default='{"remat": true, "attention": "flash"}')
    p.add_argument("--job", type=int, default=0)
    p.add_argument("--bench-root", default=None)
    p.add_argument("--skip-program", action="store_true",
                   help="the reference and the control only (a cell's own runs "
                        "are readings of the program at its size)")
    args = p.parse_args()

    from perf.lib import bench, harness, refcheck
    from saturn_tpu import library
    from saturn_tpu.utils import profile_cache

    cell = bench.load_cell(args.workload, args.bench_root)
    devices = harness.accelerator_devices(cell.chips)
    profile_cache.maybe_enable_persistent_compile_cache()
    library.register_default_library()
    tech = library.retrieve(cell.traffic["technique_names"][0])()
    config = json.loads(args.grid_point)
    job = harness.plan_jobs(cell.traffic, 10.0)[args.job]
    want = cell.traffic["reference_check"]
    sequences, steps = int(want["sequences"]), int(want["steps"])
    ref = harness.reference_module(cell.config)
    arch = ref.arch_from_config(cell.config, job.seq)
    weights = harness.weight_seed(cell.config)
    fp8 = refcheck.lowp_mm("fp8")
    limits = refcheck.load_limits()
    wrong = []  # a control that passed, a program that did not
    tmp = tempfile.mkdtemp(prefix="perf-control-")
    os.makedirs("chiprun_out", exist_ok=True)
    out = os.path.join("chiprun_out", f"control.{args.workload}.json")
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        clone = harness.make_task(cell.config, cell.traffic, job, seed,
                                  os.path.join(tmp, "ckpts"), name="control",
                                  batch=sequences, batch_count=steps)
        batches = [clone.batch_at(k) for k in range(steps)]
        ref_losses, ref_logits, ref_state = refcheck.reference_side(
            ref, arch, weights, batches, job.lr, devices=devices)
        row = {"seed": seed, "ref_losses": ref_losses, "ref_s": time.perf_counter() - t0}
        if i < args.control_seeds:
            t1 = time.perf_counter()
            c_losses, c_logits, c_state = refcheck.reference_side(
                ref, arch, weights, batches, job.lr, fp8, devices=devices)
            leaves = {}
            row["fp8"] = {"logits_rel_rms": refcheck.logits_error(ref_logits, c_logits),
                          **refcheck.loss_errors(ref_losses, c_losses),
                          **refcheck.state_errors(ref_state, c_state, None, leaves),
                          "leaves": leaves,
                          "losses": c_losses, "seconds": time.perf_counter() - t1}
            row["fp8"]["correct"] = refcheck.verdict(
                row["fp8"], limits, harness.say, f"fp8-control.{seed}")
            if row["fp8"]["correct"]:
                wrong.append(f"the fp8 control of seed {seed} came out correct")
            del c_logits, c_state
            gc.collect()
        if args.skip_program:
            del ref_logits, ref_state
            row["seconds"] = time.perf_counter() - t0
            rows.append(row)
            print(json.dumps({k: ({a: b for a, b in v.items() if a != "leaves"}
                                  if isinstance(v, dict) else v) for k, v in row.items()}),
                  flush=True)
            with open(out, "w") as f:
                json.dump({"workload": args.workload, "grid_point": config, "rows": rows},
                          f, indent=1)
            gc.collect()
            continue
        t1 = time.perf_counter()
        sys_logits = refcheck.system_logits(clone, config, batches[0])
        row["program"] = {"logits_rel_rms": refcheck.logits_error(ref_logits, sys_logits)}
        del ref_logits, sys_logits
        gc.collect()
        sys_losses, sys_state, read_back = refcheck.system_side(
            clone, tech, config, devices, steps, os.path.join(tmp, "events.jsonl"),
            seed, release=False)
        clone.clear_ckpt()
        row["program"].update(read_back)
        row["program"].update(refcheck.loss_errors(ref_losses, sys_losses))
        leaves = {}
        row["program"].update(refcheck.state_errors(ref_state, sys_state, None, leaves))
        row["program"]["leaves"] = leaves
        row["program"]["losses"] = sys_losses
        row["program"]["correct"] = refcheck.verdict(
            row["program"], limits, harness.say, f"program.{seed}")
        if not row["program"]["correct"]:
            wrong.append(f"the program of seed {seed} came out not correct")
        row["program"]["seconds"] = time.perf_counter() - t1
        del ref_state, sys_state
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps({k: ({a: b for a, b in v.items() if a != "leaves"}
                              if isinstance(v, dict) else v) for k, v in row.items()}),
              flush=True)
        with open(out, "w") as f:
            json.dump({"workload": args.workload, "grid_point": config, "rows": rows},
                      f, indent=1)
        gc.collect()
    for name in refcheck.PRINTED:
        if name == "ckpt_leaves_differ" and args.skip_program:
            continue
        prog = [r["program"][name] for r in rows if "program" in r]
        line = (f"{name}: program max {max(prog):.6g} (min {min(prog):.6g}, "
                f"{len(prog)} seeds)" if prog else f"{name}: program not read")
        ctl = [r["fp8"][name] for r in rows if "fp8" in r and name in r["fp8"]]
        if ctl:
            line += f"; fp8 control min {min(ctl):.6g} (max {max(ctl):.6g}, {len(ctl)} seeds)"
        print(line, flush=True)
    for w in wrong:
        print(f"WRONG: {w}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
