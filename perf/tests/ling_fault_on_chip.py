"""A planted fault of ``perf/reference/ling.py`` at the Ling cell's own size,
on the chip -- not part of the benchmark's runs (``control_on_chip.py``'s way).

    chiprun -- python perf/tests/ling_fault_on_chip.py --fault bf16_state

The plain reference against itself with one thing wrong (the faults of
``perf/tests/test_reference_ling.py``: ``bf16_state``, ``scalar_gate``,
``decay_after``, ``scale_128``, ``key_not_shared``), on one seed of tokens,
through the ``refcheck`` calls a run makes, under the committed limits. It
says which limit, if any, the fault passes: the reading behind ``PERF.md``'s
note that the five limits cannot tell a delta-rule state held in bf16 from
bf16 products. Prints the numbers and writes them to
``chiprun_out/ling_fault.<fault>.json``. Exit code 0 either way.
"""

import argparse
import functools
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="ling3-flash-1chip.steady-8k")
    p.add_argument("--fault", default="bf16_state")
    p.add_argument("--seed", type=int, default=2_147_483_659)
    p.add_argument("--bench-root", default=None)
    args = p.parse_args()

    from perf.lib import bench, harness, refcheck
    from saturn_tpu.utils import profile_cache

    cell = bench.load_cell(args.workload, args.bench_root)
    devices = harness.accelerator_devices(cell.chips)
    profile_cache.maybe_enable_persistent_compile_cache()
    job = harness.plan_jobs(cell.traffic, 10.0)[0]
    want = cell.traffic["reference_check"]
    sequences, steps = int(want["sequences"]), int(want["steps"])
    ref = harness.reference_module(cell.config)
    arch = ref.arch_from_config(cell.config, job.seq)
    weights = harness.weight_seed(cell.config)
    clone = harness.make_task(cell.config, cell.traffic, job, args.seed,
                              os.path.join(tempfile.mkdtemp(prefix="perf-fault-"), "ckpts"),
                              name="fault", batch=sequences, batch_count=steps)
    batches = [clone.batch_at(k) for k in range(steps)]
    t0 = time.perf_counter()
    losses, logits, state = refcheck.reference_side(
        ref, arch, weights, batches, job.lr, devices=devices)
    for half in ("_mixer_half", "_ff_half"):       # what the reference's programs call
        setattr(ref, half, functools.partial(getattr(ref, half), fault=args.fault))
    ref._jitted.cache_clear()
    f_losses, f_logits, f_state = refcheck.reference_side(
        ref, arch, weights, batches, job.lr, devices=devices)
    leaves = {}
    row = {"fault": args.fault, "seed": args.seed,
           "logits_rel_rms": refcheck.logits_error(logits, f_logits),
           **refcheck.loss_errors(losses, f_losses),
           **refcheck.state_errors(state, f_state, None, leaves)}
    row["correct"] = refcheck.verdict(row, refcheck.load_limits(), harness.say,
                                      f"fault.{args.fault}")
    row["seconds"] = time.perf_counter() - t0
    print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"ling_fault.{args.fault}.json"), "w") as f:
        json.dump(dict(row, leaves=leaves), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
