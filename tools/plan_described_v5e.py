"""What a cell's step programs plan, compiled for a *described* v5e (no chip):
the bytes the compiler's memory analysis gives the K-step window program of
each grid point asked for, beside the program's 0.92 x HBM rule, and the
executable's size as the machine's compile cache would hold it (serialised,
zstd). Nothing runs, so nothing here is a measurement of time.

    python3 tools/plan_described_v5e.py --workload nemotron3-super-1chip.steady-8k \
        [--override held_heads=4 ...] [--seq 4096] [--k 8] [--config remat=1,attention=flash]

Off the TPU the fused head computes through plain XLA ops, as in the planned
GiB the other configurations' ``share_rule`` quote (PR 33, PR 36).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--override", action="append", default=[],
                    help="key=value over the configuration's run.overrides")
    ap.add_argument("--seq", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--config", action="append", default=[],
                    help="a grid point: remat=1,attention=flash (may repeat)")
    args = ap.parse_args()

    import jax
    from jax.experimental import serialize_executable, topologies

    from perf.lib import bench, harness
    from saturn_tpu.ops import ce, flash, gdn, moe, ssd
    from saturn_tpu.parallel.dp import DataParallel
    from saturn_tpu.utils.timing import hbm_bytes_required

    for mod in (ce, flash, gdn, ssd):  # lower the kernels, do not interpret
        mod._use_interpret = lambda: False
    moe._interpret = lambda: False

    cell = bench.load_cell(args.workload)
    cfg = json.loads(json.dumps(cell.config))
    for item in args.override:
        key, _, value = item.partition("=")
        cfg["run"].setdefault("overrides", {})[key] = _value(value)
    job = harness.plan_jobs(cell.traffic, 30.0)[0]
    if args.seq or args.batch:
        import dataclasses

        job = dataclasses.replace(job, seq=args.seq or job.seq,
                                  batch=args.batch or job.batch)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = [topo.devices[0]]
    limit = 15.75 * 2 ** 30
    points = [dict((k, _value(v)) for k, _, v in (kv.partition("=") for kv in c.split(",")))
              for c in args.config] or [{"remat": True, "attention": "flash"}]
    for point in points:
        point = {k: bool(v) if k == "remat" else v for k, v in point.items()}
        task = harness.make_task(cfg, cell.traffic, job, 0, "/tmp/plan-unused")
        tech = DataParallel()
        t0 = time.perf_counter()
        bundle = tech._build_uncached(task, devices, dict(point))
        params = sum(int(x.size) for x in jax.tree_util.tree_leaves(
            bundle.state_shapes["params"]))

        def window(state, stack, train=bundle.replay):
            return jax.lax.scan(train, state, stack)

        stack = jax.ShapeDtypeStruct((args.k, *bundle.batch_sds.shape),
                                     bundle.batch_sds.dtype)
        t1 = time.perf_counter()
        compiled = jax.jit(
            window, in_shardings=(bundle.state_shardings, bundle.stacked_sharding()),
            out_shardings=(bundle.state_shardings, jax.sharding.NamedSharding(
                bundle.mesh, jax.sharding.PartitionSpec())),
            donate_argnums=(0, 1)).lower(bundle.state_shapes, stack).compile()
        t2 = time.perf_counter()
        need = hbm_bytes_required(compiled)
        size = None
        try:
            import zstandard

            blob = serialize_executable.serialize(compiled)[0]
            size = len(zstandard.ZstdCompressor().compress(blob))
        except Exception as e:   # no zstd here, or an executable that will not serialise
            print(f"plan: executable size not read: {e!r}"[:200])
        text = compiled.as_text()
        kernels = sorted({name for name in (
            "saturn_flash_fwd", "saturn_flash_dq", "saturn_flash_dkv", "saturn_ssd_fwd",
            "saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw", "saturn_gdn_fwd",
            "saturn_swa_fwd", "saturn_ce_fwd", "saturn_mla_fwd",
            "saturn_mla_dq", "saturn_mla_dkv") if name in text})
        print(json.dumps({
            "workload": args.workload, "overrides": cfg["run"].get("overrides"),
            "seq": job.seq, "batch": job.batch, "k": args.k, "config": point,
            "params_M": round(params / 1e6, 2),
            "state_GiB_at_16B": round(params * 16 / 2 ** 30, 3),
            "planned_GiB": round(need / 2 ** 30, 3),
            "rule_GiB": round(0.92 * limit / 2 ** 30, 2),
            "passes": bool(need <= 0.92 * limit),
            "executable_zstd_MiB": None if size is None else round(size / 2 ** 20, 1),
            "trace_s": round(t1 - t0, 1), "lower_compile_s": round(t2 - t1, 1),
            "kernels": kernels}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
