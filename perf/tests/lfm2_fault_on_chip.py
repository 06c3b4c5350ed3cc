"""A planted fault at the LFM2 cell's own widths must come out not ``correct``
-- the builder's chip script (after ``gate_fault_on_chip.py``), not part of
the benchmark's runs.

    chiprun -- python3 perf/tests/lfm2_fault_on_chip.py [--seeds 2]
        [--fault taps_reversed|period_rotated]

For each seed of tokens, in one process and with no search, through the same
``refcheck`` calls a run makes (``control_on_chip.py``'s way): the plain
reference, the sound program, and the faulted side. Every side's numbers go
through ``refcheck.verdict`` under the committed limits; each leaf's own
numbers are kept. The exit code is 1 if the faulted side came out correct or
the sound one did not. Writes ``chiprun_out/lfm2_fault[.<fault>].<cell>.json``
after every seed.

``taps_reversed``, **the convolution's taps in reverse order**, is planted in
what the program is handed: every ``conv_w`` leaf of its seeded weights
reversed along the taps, so its convolution multiplies the token itself by the
reference's tap 0 and the token two back by tap 2; before its state is
compared the same leaves of its first moments and weights are turned back, so
that a tap is held against the reference's tap it stands for and only what the
reversed order computed differs.

``period_rotated``, **the period one place on** (conv, conv, conv, full), is
planted on the reference's side (``perf/reference/lfm2.py::_order``: each layer
keeps its own weights and runs one place earlier, the attention layer last):
the sound program is held against a reference that runs the other order,
which is what a run of a program with the wrong order would compare.
"""

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _taps_reversed(tree):
    """Every ``conv_w`` leaf (.., taps, channels) of a nested tree of weights
    reversed along its taps."""
    return {k: (v[..., ::-1, :] if k == "conv_w" else
                _taps_reversed(v) if isinstance(v, dict) else v) for k, v in tree.items()}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="lfm2-8b-a1b-1chip.steady-8k")
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--fault", default="taps_reversed",
                   choices=("taps_reversed", "period_rotated"))
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
    wrong, rows = [], []
    tmp = tempfile.mkdtemp(prefix="perf-lfm2-fault-")
    os.makedirs("chiprun_out", exist_ok=True)
    tag = "" if args.fault == "taps_reversed" else f".{args.fault}"
    out = os.path.join("chiprun_out", f"lfm2_fault{tag}.{args.workload}.json")
    in_program = args.fault == "taps_reversed"
    # a compared side: (the reference's side, the program's side)
    pairs = {"sound": ("sound", "sound"),
             args.fault: ("sound", args.fault) if in_program else (args.fault, "sound")}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        sides = {side: harness.make_task(cell.config, cell.traffic, job, seed,
                                         os.path.join(tmp, side), name=side,
                                         batch=sequences, batch_count=steps)
                 for side in (("sound", args.fault) if in_program else ("sound",))}
        if in_program:
            sound_model = sides[args.fault]._get_model

            def faulted_model(**kw):
                spec = sound_model(**kw)
                return dataclasses.replace(
                    spec, init_fn=lambda rng: _taps_reversed(spec.init_fn(rng)))

            sides[args.fault]._get_model = faulted_model   # (the factory ``Task`` calls)
        batches = [sides["sound"].batch_at(k) for k in range(steps)]
        refs = {"sound": refcheck.reference_side(
            ref, arch, harness.weight_seed(cell.config), batches, job.lr, devices=devices)}
        if not in_program:
            whole = ref._order
            ref._order = functools.partial(whole, fault=args.fault)
            try:
                refs[args.fault] = refcheck.reference_side(
                    ref, arch, harness.weight_seed(cell.config), batches, job.lr,
                    devices=devices)
            finally:
                ref._order = whole
        row = {"seed": seed}
        logits = {side: refcheck.system_logits(clone, config, batches[0])
                  for side, clone in sides.items()}
        for side, (of_ref, of_sys) in pairs.items():
            row[side] = {"logits_rel_rms": refcheck.logits_error(refs[of_ref][1],
                                                                 logits[of_sys])}
        del logits
        refs = {side: (losses, None, state) for side, (losses, _, state) in refs.items()}
        gc.collect()
        states = {}
        for side, clone in sides.items():
            sys_losses, sys_state, read_back = refcheck.system_side(
                clone, tech, config, devices, steps, os.path.join(tmp, "events.jsonl"),
                seed, release=False)
            clone.clear_ckpt()
            if side == "taps_reversed":     # a tap against the tap it stands for
                sys_state = {name: {leaf: (v[..., ::-1, :] if leaf.endswith("conv_w") else v)
                                    for leaf, v in tree.items()}
                             for name, tree in sys_state.items()}
            states[side] = (sys_losses, sys_state, read_back)
        for side, (of_ref, of_sys) in pairs.items():
            ref_losses, _, ref_state = refs[of_ref]
            sys_losses, sys_state, read_back = states[of_sys]
            numbers, leaves = row[side], {}
            numbers.update(read_back)
            numbers.update(refcheck.loss_errors(ref_losses, sys_losses))
            numbers.update(refcheck.state_errors(ref_state, sys_state, None, leaves))
            numbers["correct"] = refcheck.verdict(numbers, limits, harness.say, f"{side}.{seed}")
            numbers["leaves"] = {k: v["grad_rel_rms"] for k, v in leaves.items()}
            if numbers["correct"] != (side == "sound"):
                wrong.append(f"the {side} side of seed {seed} came out "
                             f"{'correct' if numbers['correct'] else 'not correct'}")
        del states, refs
        gc.collect()
        rows.append(row)
        print(json.dumps({k: ({a: b for a, b in v.items() if a != "leaves"}
                              if isinstance(v, dict) else v) for k, v in row.items()}),
              flush=True)
        with open(out, "w") as f:
            json.dump({"workload": args.workload, "grid_point": config,
                       "fault": args.fault, "rows": rows}, f, indent=1)
    for w in wrong:
        print(f"WRONG: {w}", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
