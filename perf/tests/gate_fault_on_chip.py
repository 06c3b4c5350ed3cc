"""A gate-side fault at the SmallThinker cell's own widths must come out not
``correct`` -- the builder's chip script behind ``GATE_UP``
(``perf/reference/smallthinker.py``: the seeded up tables lean on their gate
tables, which makes relu's kink smaller), not part of the benchmark's runs.

    chiprun -- python3 perf/tests/gate_fault_on_chip.py [--seeds 2]

For each seed of tokens, in one process and with no search, through the same
``refcheck`` calls a run makes (``control_on_chip.py``'s way): the plain
reference, the sound program, and the program with ``silu`` for ``relu`` in
its experts (``expert_act`` ``swiglu``: the same three tables, the gate's
activation alone differs). Every side's numbers go through
``refcheck.verdict`` under the committed limits; each leaf's own numbers are
kept. The exit code is 1 if the faulted program came out correct or the sound
one did not. Writes ``chiprun_out/gate_fault.<cell>.json`` after every seed.
"""

import argparse
import gc
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

#: silu for relu, and what ``GPT2Config`` asks of a stack whose experts are SwiGLU
FAULT = {"expert_act": "swiglu", "mlp_act": "swiglu"}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="smallthinker-21b-1chip.steady-8k")
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--first-seed", type=int, default=2_147_483_659)
    p.add_argument("--grid-point", default='{"remat": true, "attention": "flash"}')
    p.add_argument("--bench-root", default=None)
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
    job = harness.plan_jobs(cell.traffic, 10.0)[0]
    want = cell.traffic["reference_check"]
    sequences, steps = int(want["sequences"]), int(want["steps"])
    ref = harness.reference_module(cell.config)
    arch = ref.arch_from_config(cell.config, job.seq)
    limits = refcheck.load_limits()
    faulted = {**cell.config, "run": {**cell.config["run"], "overrides": {
        **cell.config["run"].get("overrides", {}), **FAULT}}}
    wrong, rows = [], []
    tmp = tempfile.mkdtemp(prefix="perf-gate-fault-")
    os.makedirs("chiprun_out", exist_ok=True)
    out = os.path.join("chiprun_out", f"gate_fault.{args.workload}.json")
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        sides = {side: harness.make_task(cfg, cell.traffic, job, seed,
                                         os.path.join(tmp, side), name=side,
                                         batch=sequences, batch_count=steps)
                 for side, cfg in (("sound", cell.config), ("silu_for_relu", faulted))}
        batches = [sides["sound"].batch_at(k) for k in range(steps)]
        ref_losses, ref_logits, ref_state = refcheck.reference_side(
            ref, arch, harness.weight_seed(cell.config), batches, job.lr, devices=devices)
        row = {"seed": seed}
        for side, clone in sides.items():
            row[side] = {"logits_rel_rms": refcheck.logits_error(
                ref_logits, refcheck.system_logits(clone, config, batches[0]))}
        del ref_logits
        gc.collect()
        for side, clone in sides.items():
            sys_losses, sys_state, read_back = refcheck.system_side(
                clone, tech, config, devices, steps, os.path.join(tmp, "events.jsonl"),
                seed, release=False)
            clone.clear_ckpt()
            numbers, leaves = row[side], {}
            numbers.update(read_back)
            numbers.update(refcheck.loss_errors(ref_losses, sys_losses))
            numbers.update(refcheck.state_errors(ref_state, sys_state, None, leaves))
            numbers["correct"] = refcheck.verdict(numbers, limits, harness.say, f"{side}.{seed}")
            numbers["leaves"] = {k: v["grad_rel_rms"] for k, v in leaves.items()}
            if numbers["correct"] != (side == "sound"):
                wrong.append(f"the {side} program of seed {seed} came out "
                             f"{'correct' if numbers['correct'] else 'not correct'}")
            del sys_state
            gc.collect()
        del ref_state
        rows.append(row)
        print(json.dumps({k: ({a: b for a, b in v.items() if a != "leaves"}
                              if isinstance(v, dict) else v) for k, v in row.items()}),
              flush=True)
        with open(out, "w") as f:
            json.dump({"workload": args.workload, "grid_point": config, "fault": FAULT,
                       "rows": rows}, f, indent=1)
    for w in wrong:
        print(f"WRONG: {w}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
