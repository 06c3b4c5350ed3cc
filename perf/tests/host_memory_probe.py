"""Where a reference check's host memory goes, stage by stage, on the chip
(a one-chip machine has 40 GiB): not part of the benchmark's runs.

    chiprun -- python perf/tests/host_memory_probe.py --workload <cell>

Prints the process's resident memory (now and peak) after: import and device
start, the seeded weights, the reference's logits, its training steps, the
copy of its state to the host. With ``--flow``: after each phase of a priming
child instead (``harness.set_up``, ``timed_search``, ``reference_check``), the
process that meets every compiler cold."""

import argparse
import gc
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
T0 = time.time()


def say(where: str) -> None:
    with open("/proc/self/status") as f:
        now = next(int(line.split()[1]) for line in f if line.startswith("VmRSS")) / 2 ** 20
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"probe: {time.time() - T0:7.1f}s  resident {now:6.2f} GiB (peak {peak:6.2f})  "
          f"after {where}", flush=True)


def flow(args) -> int:
    from perf.lib import bench, harness

    run = harness.Run(bench.load_cell(args.workload), args.seed, 30.0, False, T0)
    harness.set_up(run)
    say("set_up")
    harness.timed_search(run)
    say("the search")
    ok = harness.reference_check(run)
    say(f"the reference check (correct: {ok})")
    for name, c in run.compared.items():
        print(f"probe: compared {name} = {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{'ok' if c['ok'] else 'NOT OK'}", flush=True)
    return 0 if ok else 1


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--steps", type=int, default=2)
    p.add_argument("--flow", action="store_true")
    p.add_argument("--seed", type=int, default=3600000201)
    args = p.parse_args()
    say("start")
    if args.flow:
        return flow(args)
    import jax
    import jax.numpy as jnp

    from perf.lib import bench, harness, refcheck
    from saturn_tpu.utils import profile_cache

    cell = bench.load_cell(args.workload)
    devices = harness.accelerator_devices(cell.chips)
    say(f"import and {len(devices)} device(s)")
    profile_cache.maybe_enable_persistent_compile_cache()
    job = harness.plan_jobs(cell.traffic, 10.0)[0]
    ref = harness.reference_module(cell.config)
    arch = ref.arch_from_config(cell.config, job.seq)
    seed = harness.weight_seed(cell.config)
    _, batches = refcheck.sample_batches(int(cell.config["vocab_size"]), job.seq, 1,
                                         args.steps, 3600000201)
    say("the batches")
    fns = ref._jitted(arch, float(job.lr), None)
    with jax.default_matmul_precision("highest"):
        params = fns["params"](ref.seed_key(seed))
        jax.block_until_ready(params)
        say("the seeded weights on the chip")
        del params
        out = fns["logits"](ref.seed_key(seed), jnp.asarray(batches[0]))
        jax.block_until_ready(out)
        say("the reference's logits (compiled and run)")
        del out
    gc.collect()
    losses, state = ref.train(arch, seed, batches, job.lr, keep_state=True)
    say(f"{args.steps} training steps and the state's copy to the host")
    del state
    gc.collect()
    say("the state dropped")
    stats = devices[0].memory_stats() or {}
    print(f"probe: device peak {stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
