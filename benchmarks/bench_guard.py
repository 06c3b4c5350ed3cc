"""Bench regression guard: fail if headline throughput drops >10%.

Compares a fresh ``bench.py`` run against the most recent recorded
``BENCH_r*.json`` in the repo root (the driver's per-round bench archive).
The comparison is shape-aware: a degraded (b2x256 CPU) record only gates
degraded runs on the same platform — a TPU number must never gate a CPU
fallback or vice versa (the per-shape baseline-key rule from round 4).

Prints ONE JSON line and exits non-zero on regression:

    {"metric": "bench_guard", "status": "ok"|"regression"|"skipped",
     "value": <new tokens/s>, "reference": <recorded tokens/s>, ...}

Run: ``python benchmarks/bench_guard.py`` (CI) — threshold overridable via
``SATURN_BENCH_GUARD_PCT`` (default 10).
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def latest_record():
    """(round, parsed-result) of the newest BENCH_r*.json with a parsed
    value, or None when no usable record exists (fresh clone)."""
    best = None
    for path in glob.glob(os.path.join(REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = rec.get("parsed")
        if not isinstance(parsed, dict):
            continue
        value = parsed.get("value")
        if not isinstance(value, (int, float)) or value <= 0:
            continue
        if parsed.get("tsan"):
            continue  # instrumented rows never serve as baselines
        n = int(m.group(1))
        if best is None or n > best[0]:
            best = (n, parsed)
    return best


def run_bench() -> dict:
    """Run bench.py in a subprocess and parse its single JSON stdout line."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=1200,
    )
    for line in reversed(r.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"bench.py produced no JSON line (rc={r.returncode}): "
        f"{(r.stderr or r.stdout).strip().splitlines()[-1:]}"
    )


def bench_plan_errors(new: dict) -> list:
    """Static plan verification for the benchmark's workload (saturn-lint).

    The headline bench is a single job on the measuring host's slice; its
    plan form is one full-capacity assignment. Running it through the real
    verifier end-to-end (Block/SliceTopology arithmetic, launch + capacity
    + timeline checks) means an analyzer or topology regression refuses the
    row loudly instead of silently blessing numbers from a state the
    orchestrator would reject.  Returns error diagnostics (JSON form).
    """
    sys.path.insert(0, REPO)
    from saturn_tpu.analysis import verify_plan
    from saturn_tpu.core.mesh import Block, SliceTopology
    from saturn_tpu.solver import milp

    topo = SliceTopology(devices=[object()])
    plan = milp.Plan(
        assignments={
            "bench_gpt2": milp.Assignment(
                apportionment=topo.capacity,
                block=Block(0, topo.capacity),
                start=0.0,
                runtime=1.0,
            )
        },
        makespan=1.0,
    )
    plan.compute_dependencies()
    report = verify_plan(plan, topology=topo, subject="bench_guard")
    return [d.to_json() for d in report.errors]


def bench_shardflow_errors() -> list:
    """Unsanctioned SAT-X findings over the technique + kernel sources
    (saturn-shardflow).

    The headline number is produced by a technique's step function; a row
    measured while that code carries an unsanctioned sharding funnel
    (SAT-X002 gather-to-replicated and friends) bakes the defect into the
    baseline every later round is compared against. AST-only — same
    any-environment rule as the ``tools/lint.py`` gate.  Returns error
    diagnostics (JSON form); sanctioned findings are info and pass.
    """
    sys.path.insert(0, REPO)
    from saturn_tpu.analysis.diagnostics import AnalysisReport
    from saturn_tpu.analysis.shardflow import passes as sf_passes

    report = AnalysisReport(subject="bench_guard-shardflow")
    sf_passes.scan_sources(sf_passes.default_source_paths(REPO), report)
    return [d.to_json() for d in report.errors]


def bench_memlens_errors() -> list:
    """Unsanctioned SAT-M findings over the in-tree techniques
    (saturn-memlens).

    The headline number is produced by a technique's step function; a row
    measured while that step carries an unsanctioned memory defect
    (SAT-M003 missed donation, or SAT-M001 predicted OOM under a declared
    capacity) bakes the defect into the baseline. The audit traces on
    virtual CPU devices, and the device-count flag must land before jax
    initializes — so it runs as the CLI subprocess, not in-process.
    Returns error diagnostics (JSON form); sanctioned findings are info
    and pass.
    """
    # The source tree is where THIS file lives, not REPO: REPO is the
    # record-lookup root and tests point it at a tmp dir, which must not
    # break the subprocess's ability to import saturn_tpu.
    src = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "saturn_tpu.analysis", "--json", "memlens"],
        capture_output=True, text=True, timeout=900, cwd=REPO, env=env,
    )
    if r.returncode == 2:
        raise RuntimeError(
            f"memlens audit unavailable: {(r.stderr or '').strip()[-200:]}"
        )
    for line in reversed(r.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            payload = json.loads(line)
            return [d for d in payload.get("diagnostics", [])
                    if d.get("severity") == "error"]
    raise RuntimeError(
        f"memlens audit produced no JSON line (rc={r.returncode})"
    )


#: Required key -> type for the ``benchmarks/sweep_cache.py`` static-prune
#: row. Same contract as the other ROW_REQUIRED tables: the bench
#: self-validates before printing, and recorded rows can be re-checked
#: without re-running it.
SWEEP_PRUNE_ROW_REQUIRED = {
    "metric": str,
    "grid_points": int,
    "pruned_before_lowering": int,     # acceptance bar: >= 1
    "rejected_after_lowering": int,    # the "before" sweep's compile waste
    "contradictions": int,             # _fits_memory vs memlens-feasible: 0
    "before_s": float,
    "after_s": float,
    "saved_s": float,
    "capacity_bytes": int,
    "status": str,
}


def validate_sweep_prune_row(row) -> list:
    """Schema-check one static-prune sweep row; returns human-readable
    problems (empty list = valid)."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in SWEEP_PRUNE_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "sweep_static_prune":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'sweep_static_prune'"
        )
    pruned = row.get("pruned_before_lowering")
    if isinstance(pruned, int) and not isinstance(pruned, bool) and pruned < 1:
        problems.append(
            "pruned_before_lowering < 1 (the static pass pruned nothing)"
        )
    c = row.get("contradictions")
    if isinstance(c, int) and not isinstance(c, bool) and c != 0:
        problems.append(
            f"contradictions {c} != 0 (_fits_memory rejected a point "
            "memlens called feasible)"
        )
    return problems


#: Required key -> type for the ``benchmarks/pipeline_schedule.py`` row.
#: Same contract as the other ROW_REQUIRED tables: the bench self-validates
#: before printing, and recorded rows can be re-checked without re-running.
PIPELINE_ROW_REQUIRED = {
    "metric": str,
    "stages": int,
    "microbatches": int,
    "devices": int,
    "gpipe_ms": float,                 # AD-GPipe steady-state step time
    "f1b_ms": float,                   # staged 1F1B steady-state step time
    "speedup_1f1b_vs_gpipe": float,    # acceptance bar: >= 1.0 at M = S
    "bubble_gpipe": float,             # analytic (S-1)/(M+S-1)
    "bubble_1f1b": float,              # analytic (S-1)/(M+2(S-1))
    "status": str,
}


def validate_pipeline_row(row) -> list:
    """Schema-check one pipeline-schedule row; returns human-readable
    problems (empty list = valid)."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in PIPELINE_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "pipeline_schedule":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'pipeline_schedule'"
        )
    s = row.get("stages")
    if isinstance(s, int) and not isinstance(s, bool) and s < 2:
        problems.append("stages < 2 (no pipeline to schedule)")
    sp = row.get("speedup_1f1b_vs_gpipe")
    if isinstance(sp, (int, float)) and not isinstance(sp, bool) and sp < 1.0:
        problems.append(
            f"speedup_1f1b_vs_gpipe {sp} < 1.0 (1F1B must beat GPipe "
            "steady-state at M = S)"
        )
    bg, bf = row.get("bubble_gpipe"), row.get("bubble_1f1b")
    for key, b in (("bubble_gpipe", bg), ("bubble_1f1b", bf)):
        if (isinstance(b, (int, float)) and not isinstance(b, bool)
                and not 0.0 <= b < 1.0):
            problems.append(f"{key} {b} outside [0, 1)")
    if (isinstance(bg, (int, float)) and isinstance(bf, (int, float))
            and not isinstance(bg, bool) and not isinstance(bf, bool)
            and bf >= bg):
        problems.append(
            f"bubble_1f1b {bf} >= bubble_gpipe {bg} (1F1B's warmup-"
            "cooldown bubble must be the smaller one)"
        )
    return problems


#: Required key -> type for one ``benchmarks/chaos_campaign.py`` output row.
#: The campaign bench self-validates against this before printing, and CI
#: can re-check recorded rows — a schema drift (renamed key, stringified
#: count) breaks the comparison silently otherwise.
CHAOS_ROW_REQUIRED = {
    "metric": str,
    "seeds": list,
    "fault_classes": list,
    "jobs": int,
    "jobs_lost": int,
    "restarts": int,
    "quarantined_batches": int,
    "makespan_inflation": float,
    "trajectory_bit_identical": bool,
    "sentinel_overhead_pct": float,
    "platform": str,
    "status": str,
}


def validate_chaos_row(row) -> list:
    """Schema-check one chaos-campaign row; returns human-readable problems
    (empty list = valid)."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in CHAOS_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            # bool is an int subclass; a True in a count field is a bug
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # a whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "chaos_campaign":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'chaos_campaign'"
        )
    if isinstance(row.get("seeds"), list) and len(row["seeds"]) < 3:
        problems.append("fewer than 3 seeds")
    if (isinstance(row.get("fault_classes"), list)
            and len(row["fault_classes"]) < 4):
        problems.append("fewer than 4 fault classes")
    return problems


#: Required key -> type for the ``benchmarks/online_arrivals.py`` gateway
#: row. Same contract as CHAOS_ROW_REQUIRED: the bench self-validates before
#: printing, and recorded rows can be re-checked without re-running it.
ONLINE_ROW_REQUIRED = {
    "metric": str,
    "n_jobs": int,
    "accepted": int,
    "shed": int,
    "shed_rate": float,
    "admission_p50_s": float,
    "admission_p99_s": float,
    "makespan_s": float,
    "base_rate_hz": float,
    "burst_rate_hz": float,
    "gateway_window": int,
    "seed": int,
    "status": str,
}


def validate_online_row(row) -> list:
    """Schema-check one online-arrivals gateway row; returns human-readable
    problems (empty list = valid)."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in ONLINE_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "online_arrivals":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'online_arrivals'"
        )
    if (isinstance(row.get("accepted"), int)
            and isinstance(row.get("shed"), int)
            and isinstance(row.get("n_jobs"), int)
            and row["accepted"] + row["shed"] != row["n_jobs"]):
        problems.append("accepted + shed != n_jobs (lost arrivals)")
    sr = row.get("shed_rate")
    if isinstance(sr, (int, float)) and not isinstance(sr, bool):
        if not 0.0 <= sr <= 1.0:
            problems.append(f"shed_rate {sr} outside [0, 1]")
    p50, p99 = row.get("admission_p50_s"), row.get("admission_p99_s")
    if (isinstance(p50, (int, float)) and isinstance(p99, (int, float))
            and not isinstance(p50, bool) and not isinstance(p99, bool)
            and p99 < p50):
        problems.append("admission_p99_s < admission_p50_s")
    return problems


#: Required key -> type for the ``benchmarks/solver_scaling.py`` row. Same
#: contract as the other ROW_REQUIRED tables: the bench self-validates before
#: printing, and recorded rows can be re-checked without re-running it.
SOLVER_ROW_REQUIRED = {
    "metric": str,
    "mode": str,                 # "quick" or "full"
    "n_jobs": int,
    "deadline_s": float,
    "resolves": int,
    "deadline_misses": int,      # hard acceptance bar: must be 0
    "tier_counts": dict,         # tier name -> adoption count
    "solve_p50_s": float,
    "solve_p99_s": float,
    "admission_p50_s": float,
    "admission_p99_s": float,
    "quality_delta_pct": float,  # anytime vs exact MILP on subsampled instances
    "quality_samples": int,
    "seed": int,
    "status": str,
}


def validate_solver_row(row) -> list:
    """Schema-check one solver-scaling row; returns human-readable problems
    (empty list = valid)."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in SOLVER_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "solver_scaling":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'solver_scaling'"
        )
    if isinstance(row.get("n_jobs"), int) and row["n_jobs"] < 1:
        problems.append(f"n_jobs {row['n_jobs']} < 1")
    dm = row.get("deadline_misses")
    if isinstance(dm, int) and not isinstance(dm, bool) and dm != 0:
        problems.append(
            f"deadline_misses {dm} != 0 (a re-solve blew its budget)"
        )
    for lo, hi in (("solve_p50_s", "solve_p99_s"),
                   ("admission_p50_s", "admission_p99_s")):
        a, b = row.get(lo), row.get(hi)
        if (isinstance(a, (int, float)) and isinstance(b, (int, float))
                and not isinstance(a, bool) and not isinstance(b, bool)
                and b < a):
            problems.append(f"{hi} < {lo}")
    qd = row.get("quality_delta_pct")
    if isinstance(qd, (int, float)) and not isinstance(qd, bool):
        if qd > 10.0:
            problems.append(
                f"quality_delta_pct {qd} > 10 (anytime plan quality drifted "
                "too far from the exact MILP)"
            )
    tc = row.get("tier_counts")
    if isinstance(tc, dict):
        bad = [k for k, v in tc.items()
               if not isinstance(k, str)
               or isinstance(v, bool) or not isinstance(v, int)]
        if bad:
            problems.append(f"tier_counts has non-(str -> int) entries: {bad}")
    return problems


#: Required key -> type for the ``benchmarks/billion_scale.py`` checkpoint
#: I/O row (allgather-writer vs sharded-manifest save/restore timings). Same
#: contract as the other ROW_REQUIRED tables: the bench self-validates
#: before printing, and recorded rows can be re-checked without re-running.
CKPT_ROW_REQUIRED = {
    "metric": str,                  # "ckpt_io"
    "preset": str,
    "platform": str,
    "n_devices": int,
    "state_bytes": int,             # full train-state bytes on host
    "allgather_save_s": float,      # emulated legacy single-writer save
    "sharded_save_s": float,        # manifest + per-rank shard files, cold
    "sharded_async_block_s": float,  # caller-visible save_async latency
    "sharded_restore_s": float,     # restore_sharded onto a resized mesh
    "restore_bit_identical": bool,  # hard acceptance bar: must be True
    "shard_files": int,
    "speedup_vs_allgather": float,  # allgather_save_s / sharded_save_s
    "status": str,
}


def latest_ckpt_record():
    """(round, ckpt-row) of the newest ``BENCH_r*.json`` carrying a valid
    ``ckpt`` row, or None. Lives under the record's ``"ckpt"`` key — never
    under ``"parsed"`` — so checkpoint rows and headline-throughput rows
    can't gate each other."""
    best = None
    for path in glob.glob(os.path.join(REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        row = rec.get("ckpt")
        if not isinstance(row, dict) or validate_ckpt_row(row):
            continue
        n = int(m.group(1))
        if best is None or n > best[0]:
            best = (n, row)
    return best


def validate_ckpt_row(row, reference=None, pct=10.0) -> list:
    """Schema-check one checkpoint-I/O row; returns human-readable problems
    (empty list = valid). With ``reference`` (a previously recorded row of
    the same shape) also enforces the regression bar: the sharded save must
    not be more than ``pct`` percent slower than the recorded one."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in CKPT_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "ckpt_io":
        problems.append(f"metric is {row.get('metric')!r}, expected 'ckpt_io'")
    if row.get("restore_bit_identical") is not True:
        problems.append(
            "restore_bit_identical is not True — the sharded round trip "
            "corrupted at least one leaf"
        )
    for key in ("sharded_save_s", "allgather_save_s", "sharded_restore_s"):
        v = row.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool) and v <= 0:
            problems.append(f"{key} {v} <= 0")
    blk = row.get("sharded_async_block_s")
    cold = row.get("sharded_save_s")
    if (isinstance(blk, (int, float)) and isinstance(cold, (int, float))
            and not isinstance(blk, bool) and not isinstance(cold, bool)
            and cold > 0 and blk > cold * 1.5):
        problems.append(
            f"sharded_async_block_s {blk} > 1.5x cold save {cold} — the "
            "async path is not overlapping the disk write"
        )
    if isinstance(reference, dict):
        same_shape = all(
            row.get(k) == reference.get(k)
            for k in ("preset", "platform", "n_devices")
        )
        ref_s = reference.get("sharded_save_s")
        new_s = row.get("sharded_save_s")
        if (same_shape
                and isinstance(ref_s, (int, float))
                and isinstance(new_s, (int, float))
                and not isinstance(ref_s, bool)
                and not isinstance(new_s, bool)
                and ref_s > 0
                and new_s > ref_s * (1.0 + pct / 100.0)):
            problems.append(
                f"sharded_save_s {new_s} regressed >{pct}% vs recorded "
                f"{ref_s}"
            )
    return problems


#: Required key -> type for the ``benchmarks/fused_sweep.py`` row. Same
#: contract as the other ROW_REQUIRED tables: the bench self-validates
#: before printing, and recorded rows can be re-checked without re-running.
FUSED_ROW_REQUIRED = {
    "metric": str,                     # "fused_sweep_tokens_per_sec"
    "workload": str,                   # "fused_sweep"
    "platform": str,
    "n_members": int,                  # >= 2 or there is no stack
    "batches_per_member": int,
    "batch_size": int,
    "seq_len": int,
    "window": int,
    "value": float,                    # fused aggregate tokens/sec
    "coscheduled_tokens_per_sec": float,
    "fused_s": float,
    "coscheduled_s": float,
    "speedup_vs_coschedule": float,    # acceptance bar: >= 1.0
    "loss_divergence": float,          # max |fused - solo ref|: ~0 required
    "status": str,
}

#: The fused row's per-member losses are compared after the event stream's
#: 6-decimal rounding, so bit-identical trajectories read back as <= 1e-6
#: apart; anything past this tolerance means the stacked program changed
#: the math, and the row is a lie about "the same training, faster".
FUSED_LOSS_TOL = 1e-5


def validate_fused_row(row) -> list:
    """Schema-check one fused-sweep row; returns human-readable problems
    (empty list = valid). Refuses rows whose speedup claim is measured
    against diverged members: ``loss_divergence`` past FUSED_LOSS_TOL means
    the fused trajectories are not the solo trajectories."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in FUSED_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "fused_sweep_tokens_per_sec":
        problems.append(
            f"metric is {row.get('metric')!r}, expected "
            "'fused_sweep_tokens_per_sec'"
        )
    n = row.get("n_members")
    if isinstance(n, int) and not isinstance(n, bool) and n < 2:
        problems.append(f"n_members {n} < 2 (no stack to fuse)")
    sp = row.get("speedup_vs_coschedule")
    if isinstance(sp, (int, float)) and not isinstance(sp, bool) and sp < 1.0:
        problems.append(
            f"speedup_vs_coschedule {sp} < 1.0 (the stack must beat the "
            "co-scheduled pairs it replaces)"
        )
    div = row.get("loss_divergence")
    if isinstance(div, (int, float)) and not isinstance(div, bool):
        if not div <= FUSED_LOSS_TOL:
            problems.append(
                f"loss_divergence {div} > {FUSED_LOSS_TOL} (a fused member's "
                "final loss diverged from its solo reference — refusing to "
                "record a speedup over different training)"
            )
    return problems


#: Required key -> type for the ``benchmarks/twin_scale.py`` row. Same
#: contract as the other ROW_REQUIRED tables: the bench self-validates
#: before printing, and recorded rows can be re-checked without re-running.
TWIN_ROW_REQUIRED = {
    "metric": str,               # "twin_scale"
    "mode": str,                 # "quick" or "full"
    "n_jobs": int,               # full mode: >= 100_000 synthesized jobs
    "n_slices": int,             # full mode: >= 32 virtual slices
    "chips": int,
    "submitted": int,            # accepted by the real gateway
    "scheduled": int,            # ADMITted by the real admission controller
    "completed": int,
    "failed": int,
    "evicted": int,
    "shed": int,                 # gateway sheds (window/deadline/draining)
    "solves": int,               # real anytime_resolve calls
    "deadline_misses": int,      # hard acceptance bar: must be 0
    "tier_counts": dict,         # solver tier -> adoption count
    "makespan_sim_s": float,     # simulated campaign makespan
    "wall_s": float,             # real seconds the campaign took
    "seed": int,
    "fidelity": dict,            # twin-vs-real band check (may be empty
    #                              when the fidelity phase was skipped)
    "status": str,
}


def validate_twin_row(row) -> list:
    """Schema-check one twin-scale row; returns human-readable problems
    (empty list = valid).

    Enforces the twin's acceptance bars: zero solver deadline misses, the
    full-mode scale floor (>= 100k jobs over >= 32 virtual slices), a
    conservation check (every scheduled job reaches exactly one terminal
    verdict), and — when a fidelity phase ran — ``within_band``."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in TWIN_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "twin_scale":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'twin_scale'"
        )
    dm = row.get("deadline_misses")
    if isinstance(dm, int) and not isinstance(dm, bool) and dm != 0:
        problems.append(
            f"deadline_misses {dm} != 0 (a twin re-solve blew its real-"
            "clock budget)"
        )
    if row.get("mode") == "full":
        nj, ns = row.get("n_jobs"), row.get("n_slices")
        if isinstance(nj, int) and not isinstance(nj, bool) and nj < 100_000:
            problems.append(f"full-mode n_jobs {nj} < 100000")
        if isinstance(ns, int) and not isinstance(ns, bool) and ns < 32:
            problems.append(f"full-mode n_slices {ns} < 32")
    ints = {k: row.get(k)
            for k in ("scheduled", "completed", "failed", "evicted")}
    if all(isinstance(v, int) and not isinstance(v, bool)
           for v in ints.values()):
        done = ints["completed"] + ints["failed"] + ints["evicted"]
        if done < ints["scheduled"]:
            problems.append(
                f"completed+failed+evicted {done} < scheduled "
                f"{ints['scheduled']} (jobs left in limbo)"
            )
    tc = row.get("tier_counts")
    if isinstance(tc, dict):
        bad = [k for k, v in tc.items()
               if not isinstance(k, str)
               or isinstance(v, bool) or not isinstance(v, int)]
        if bad:
            problems.append(f"tier_counts has non-(str -> int) entries: {bad}")
        if not tc and row.get("solves", 0):
            problems.append("solves > 0 but tier_counts is empty")
    fid = row.get("fidelity")
    if isinstance(fid, dict) and fid and fid.get("within_band") is not True:
        problems.append(
            "fidelity.within_band is not True (the twin's tier/verdict/"
            "makespan distributions drifted outside the documented band)"
        )
    return problems


#: Pinned-seed twin regression campaign: the standing scheduling-policy
#: guard (ROADMAP item 3 headroom). A small tenant-tagged mix runs the real
#: control plane (gateway window, admission controller, anytime solver tier
#: ladder) on virtual slices in under a second; its tier shares, verdict
#: shares and simulated makespan are pinned here with bands. A scheduling-
#: policy change that shifts which solver tier wins, flips admission
#: verdicts, or moves the campaign makespan outside the band fails the
#: guard — BEFORE it gets to record a new headline baseline. Values pinned
#: from the seeded run (deterministic: simulated clock, seeded arrivals).
TWIN_REGRESSION = {
    "seed": 23,
    "n_jobs": 600,
    "n_slices": 4,
    "tenant_mix": {"burst": 10.0, "quiet-a": 1.0, "quiet-b": 1.0},
    "tier_shares": {"1": 0.5, "2": 0.5},
    "tier_band": 0.15,           # absolute share drift allowed per tier
    "verdict_shares": {"admit": 1.0},
    "verdict_band": 0.10,        # absolute share drift allowed per verdict
    "makespan_s": 1200.22,
    "makespan_tol": 0.20,        # +/- fraction
}


def twin_regression_errors() -> list:
    """Run the pinned-seed twin campaign and compare against the recorded
    band. Returns human-readable problems (empty list = in band).

    The campaign drives the REAL admission controller and solver over a
    simulated fleet, so this is the cheapest end-to-end check that a
    scheduling-policy change kept its distributional behavior: same tier
    adoption, same verdict mix, same makespan — and the tenant-tagged
    arrival mix keeps the fair-share path on the measured surface.
    """
    import shutil
    import tempfile

    sys.path.insert(0, REPO)
    from saturn_tpu.twin.runner import CampaignConfig, run_campaign

    pin = TWIN_REGRESSION
    out_dir = tempfile.mkdtemp(prefix="twin_regression_")
    try:
        cfg = CampaignConfig(
            n_jobs=pin["n_jobs"], n_slices=pin["n_slices"],
            chips_per_slice=8, interval_s=600.0, solve_deadline_s=5.0,
            base_rate_hz=4.0, burst_rate_hz=12.0, total_batches=3,
            max_inflight=2_000, metrics=False, compact_every=8,
            seed=pin["seed"], max_intervals=200,
            tenant_mix=dict(pin["tenant_mix"]),
        )
        s = run_campaign(cfg, out_dir)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    problems = []
    if s.get("status") != "ok":
        problems.append(f"campaign status {s.get('status')!r}, expected 'ok'")
    if s.get("deadline_misses"):
        problems.append(
            f"{s['deadline_misses']} solver deadline miss(es) in a campaign "
            "shape that historically has zero"
        )
    got_tiers = {str(k): v for k, v in (s.get("tier_shares") or {}).items()}
    for tier in set(pin["tier_shares"]) | set(got_tiers):
        want = pin["tier_shares"].get(tier, 0.0)
        got = got_tiers.get(tier, 0.0)
        if abs(got - want) > pin["tier_band"]:
            problems.append(
                f"tier {tier} share {got:.3f} outside pinned "
                f"{want:.3f} +/- {pin['tier_band']}"
            )
    got_verdicts = dict(s.get("verdict_shares") or {})
    for verdict in set(pin["verdict_shares"]) | set(got_verdicts):
        want = pin["verdict_shares"].get(verdict, 0.0)
        got = got_verdicts.get(verdict, 0.0)
        if abs(got - want) > pin["verdict_band"]:
            problems.append(
                f"verdict {verdict!r} share {got:.3f} outside pinned "
                f"{want:.3f} +/- {pin['verdict_band']}"
            )
    mk = s.get("makespan_s")
    if isinstance(mk, (int, float)) and not isinstance(mk, bool):
        lo = pin["makespan_s"] * (1.0 - pin["makespan_tol"])
        hi = pin["makespan_s"] * (1.0 + pin["makespan_tol"])
        if not lo <= mk <= hi:
            problems.append(
                f"makespan_sim {mk:.1f}s outside pinned "
                f"[{lo:.1f}, {hi:.1f}]s"
            )
    else:
        problems.append(f"campaign makespan_s missing/bad: {mk!r}")
    # The tenant mix must actually skew: the fair-share surface is only
    # exercised when the noisy neighbour dominates the arrival stream.
    sub = s.get("tenant_submitted") or {}
    bursty = sub.get("burst", 0)
    quiet = [v for k, v in sub.items() if k != "burst"]
    if not quiet or any(bursty < 4 * q for q in quiet):
        problems.append(
            f"tenant mix lost its burst skew: {sub!r} (burst must "
            "dominate every quiet tenant at least 4:1)"
        )
    return problems


#: Required key -> type for the ``benchmarks/tenant_fairshare.py`` row.
#: Same contract as the other ROW_REQUIRED tables: the bench self-validates
#: before printing, and recorded rows can be re-checked without re-running.
TENANT_ROW_REQUIRED = {
    "metric": str,                # "tenant_fairshare"
    "n_tenants": int,             # >= 3
    "n_jobs": int,                # contended-phase arrivals
    "burst_skew": float,          # bursty:quiet arrival-weight ratio, >= 10
    "bursty_tenant": str,
    "submitted": dict,            # tenant -> submit attempts
    "admitted": dict,             # tenant -> accepted admissions
    "shed": dict,                 # tenant -> gateway sheds
    "solo_p99_s": float,          # quiet tenant alone on the gateway
    "quiet_p99_s": float,         # quiet tenants under the burst
    "p99_ratio": float,           # quiet_p99 / solo_p99, must stay <= 2
    "warm_hit_rate": float,       # compile-ahead warm phase, must be >= .8
    "first_dispatch_wait_s": float,  # mean compile wait at first dispatch
    "wall_s": float,
    "seed": int,
    "status": str,
}

#: Acceptance bars for the tenant row (shared with the bench so the
#: self-validation and any later re-check apply identical thresholds).
TENANT_MIN_TENANTS = 3
TENANT_MIN_SKEW = 10.0
TENANT_P99_RATIO_MAX = 2.0
TENANT_WARM_HIT_MIN = 0.8


def validate_tenant_row(row) -> list:
    """Schema-check one tenant-fairness row; returns human-readable
    problems (empty list = valid).

    Enforces the fairness acceptance bars: >= 3 tenants at >= 10:1 burst
    skew, the bursty tenant sheds while every quiet tenant sheds NOTHING,
    quiet-tenant p99 admission latency within 2x its solo baseline, and a
    compile-ahead warm hit rate of at least 80%."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in TENANT_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "tenant_fairshare":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'tenant_fairshare'"
        )
    nt = row.get("n_tenants")
    if isinstance(nt, int) and not isinstance(nt, bool) \
            and nt < TENANT_MIN_TENANTS:
        problems.append(f"n_tenants {nt} < {TENANT_MIN_TENANTS}")
    skew = row.get("burst_skew")
    if isinstance(skew, (int, float)) and not isinstance(skew, bool) \
            and skew < TENANT_MIN_SKEW:
        problems.append(f"burst_skew {skew} < {TENANT_MIN_SKEW}")
    bursty = row.get("bursty_tenant")
    shed = row.get("shed")
    if isinstance(shed, dict) and isinstance(bursty, str):
        if not shed.get(bursty):
            problems.append(
                f"bursty tenant {bursty!r} shed nothing — the quota/"
                "pressure path was not exercised"
            )
        quiet_shed = {t: n for t, n in shed.items() if t != bursty and n}
        if quiet_shed:
            problems.append(
                f"quiet tenant(s) shed work under the burst: {quiet_shed!r}"
            )
    ratio = row.get("p99_ratio")
    if isinstance(ratio, (int, float)) and not isinstance(ratio, bool) \
            and ratio > TENANT_P99_RATIO_MAX:
        problems.append(
            f"quiet-tenant p99 ratio {ratio} > {TENANT_P99_RATIO_MAX}x "
            "solo baseline (the burst degraded the quiet tenants)"
        )
    hr = row.get("warm_hit_rate")
    if isinstance(hr, (int, float)) and not isinstance(hr, bool) \
            and hr < TENANT_WARM_HIT_MIN:
        problems.append(
            f"warm_hit_rate {hr} < {TENANT_WARM_HIT_MIN} (compile-ahead "
            "missed on jobs it was told about at admission)"
        )
    return problems


#: Required key -> type for the ``benchmarks/grow_defrag.py`` row. Same
#: contract as the other ROW_REQUIRED tables: the bench self-validates
#: before printing, and recorded rows can be re-checked without re-running.
GROW_ROW_REQUIRED = {
    "metric": str,               # "grow_defrag"
    "drained": int,              # deferred jobs admitted after the wave, >= 1
    "defrag_admitted": int,      # gangs the wave unblocked, >= 1
    "moves": int,                # victim relocations executed
    "grow_events": int,          # hysteresis-matured grow events surfaced
    "migrations_done": int,      # two-phase moves that reached migration_done
    "lost_jobs": int,            # unresolved intents + still-blocked, must be 0
    "cap_bytes": int,
    "need_bytes": int,
    "wall_s": float,
    "status": str,
}


def validate_grow_row(row) -> list:
    """Schema-check one grow/defrag row; returns human-readable problems
    (empty list = valid).

    Enforces the elastic scale-up acceptance bars: the wave actually
    unblocked a gang (defrag_admitted >= 1) and the backlog drained
    (drained >= 1) with nothing lost — every journaled ``migration_intent``
    reached a ``migration_done``/``migration_rollback`` and no gang stayed
    blocked (lost_jobs == 0)."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in GROW_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass  # whole-number float serialized as int is fine
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "grow_defrag":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'grow_defrag'"
        )
    dr = row.get("drained")
    if isinstance(dr, int) and not isinstance(dr, bool) and dr < 1:
        problems.append(
            f"drained {dr} < 1 (the DEFER backlog never drained — the "
            "occupancy gate stayed closed after the wave)"
        )
    da = row.get("defrag_admitted")
    if isinstance(da, int) and not isinstance(da, bool) and da < 1:
        problems.append(
            f"defrag_admitted {da} < 1 (the wave planned no gang admission)"
        )
    lj = row.get("lost_jobs")
    if isinstance(lj, int) and not isinstance(lj, bool) and lj != 0:
        problems.append(
            f"lost_jobs {lj} != 0 (a migration intent never closed, or a "
            "gang stayed blocked after the wave)"
        )
    return problems


def grow_defrag_errors() -> list:
    """Run the hardware-free grow/defrag bench and validate its row.

    Cheap (<1s, no JAX): the real monitor, occupancy gate, defrag planner
    and two-phase journal drive a scripted heal-and-compact loop. A
    scheduling or durability change that stops the backlog draining — or
    leaves a migration intent unresolved — fails the guard here."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import grow_defrag

    row = grow_defrag.run()
    return validate_grow_row(row)


#: Required key -> type for the ``benchmarks/comm_overlap.py`` row.
OVERLAP_ROW_REQUIRED = {
    "metric": str,               # "comm_overlap"
    "platform": str,
    "host_cores": int,
    "pairs": dict,               # per-lowering serial/overlapped results
    "headline": str,
    "serial_ms": float,
    "overlapped_ms": float,
    "speedup": float,
    "mfu_serial": float,
    "mfu_overlapped": float,
    "bit_identical": bool,       # SGD loss trajectories bitwise equal
    "priced": dict,              # shardflow static pricing, serial vs over
}

#: Measured-step-time noise tolerance. On a host that cannot overlap (one
#: core: XLA runs every thunk serially) the double-buffered program pays a
#: small copy tax over serial — bounded, not a regression. On hardware with
#: real DMA/compute concurrency the bar tightens to "no slower than serial".
OVERLAP_TOL_PCT = float(os.environ.get("SATURN_OVERLAP_TOL_PCT", "15"))


def validate_overlap_row(row) -> list:
    """Schema + acceptance check for one comm_overlap row.

    Bars: every pair's loss trajectory bitwise equal across the knob flip
    (overlap must never change arithmetic); measured overlapped step time
    within ``OVERLAP_TOL_PCT`` of serial everywhere and <= serial outright
    on a TPU; MFU
    non-decreasing within the same tolerance; and the shardflow-priced
    speedup strictly > 1 — the deterministic witness that the per-op-class
    overlap factors re-price the placement."""
    if not isinstance(row, dict):
        return [f"row is not a dict ({type(row).__name__})"]
    problems = []
    for key, typ in OVERLAP_ROW_REQUIRED.items():
        if key not in row:
            problems.append(f"missing key {key!r}")
            continue
        val = row[key]
        if typ in (int, float) and isinstance(val, bool):
            problems.append(f"{key!r} is bool, expected {typ.__name__}")
        elif typ is float and isinstance(val, int):
            pass
        elif not isinstance(val, typ):
            problems.append(
                f"{key!r} is {type(val).__name__}, expected {typ.__name__}"
            )
    if row.get("metric") != "comm_overlap":
        problems.append(
            f"metric is {row.get('metric')!r}, expected 'comm_overlap'"
        )
    if row.get("bit_identical") is not True:
        problems.append(
            "bit_identical is not true (an overlap knob changed the "
            "arithmetic, not just the communication schedule)"
        )
    tol = OVERLAP_TOL_PCT / 100.0
    sp = row.get("speedup")
    if isinstance(sp, (int, float)) and not isinstance(sp, bool):
        # The strict bar is for the chip alone. A timing of virtual CPU
        # devices sharing the host's cores says nothing about overlap: on
        # an idle 8-core host the same command gave 1.136 and then 0.926
        # (PR 24), so a ">= 1.0 on a multi-core CPU" bar was a coin flip.
        if row.get("platform") == "tpu" and sp < 1.0:
            problems.append(
                f"headline speedup {sp} < 1.0 on a TPU "
                "(overlapped step time exceeds serial)"
            )
        elif sp < 1.0 - tol:
            problems.append(
                f"headline speedup {sp} < {1.0 - tol:.2f} (the overlapped "
                "program costs more than the serialized-host copy tax)"
            )
    mfu_s, mfu_o = row.get("mfu_serial"), row.get("mfu_overlapped")
    if all(isinstance(x, (int, float)) and not isinstance(x, bool)
           for x in (mfu_s, mfu_o)) and mfu_o < mfu_s * (1.0 - tol):
        problems.append(
            f"mfu_overlapped {mfu_o} dropped more than {OVERLAP_TOL_PCT}% "
            f"below mfu_serial {mfu_s}"
        )
    priced = row.get("priced")
    if isinstance(priced, dict):
        psp = priced.get("speedup")
        if not (isinstance(psp, (int, float)) and not isinstance(psp, bool)
                and psp > 1.0):
            problems.append(
                f"priced speedup {psp!r} not > 1.0 (the overlap factors "
                "no longer discount the overlapped lowering's wire time)"
            )
    return problems


def comm_overlap_errors() -> list:
    """Run the comm/compute overlap bench and validate its row.

    The heavyweight part of the guard (a few minutes of jit on a cold CPU
    host): three serial/overlapped program pairs stepped for bit-identity
    and timed, plus the shardflow-priced pair. Kept at low reps — the
    validation bars are tolerance-based, not throughput-based."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import comm_overlap

    row = comm_overlap.run(reps=3, steps=2)
    return validate_overlap_row(row)


def shape_key(parsed: dict) -> tuple:
    """What must match for two bench numbers to be comparable."""
    return (
        parsed.get("workload"),    # e.g. benchmarks/coschedule.py tags its
        parsed.get("platform"),    # row "coschedule_pair"; bench.py rows
        parsed.get("batch_size"),  # carry no tag — the two never gate each
        parsed.get("seq_len"),     # other. batch_size: degraded runs only.
    )


def main() -> int:
    ref = latest_record()
    threshold = float(os.environ.get("SATURN_BENCH_GUARD_PCT", "10")) / 100.0
    if ref is None:
        print(json.dumps({
            "metric": "bench_guard", "status": "skipped",
            "reason": "no BENCH_r*.json with a parsed value",
        }))
        return 0
    n, parsed_ref = ref
    if os.environ.get("SATURN_TPU_TSAN", "") == "1":
        # The sanitizer's traced locks/queues sit on the measured hot path:
        # numbers produced under instrumentation are not comparable to (or
        # recordable as) baselines.
        print(json.dumps({
            "metric": "bench_guard", "status": "tsan_instrumented",
            "reason": "refusing to gate: SATURN_TPU_TSAN=1 instruments "
                      "the measured hot path",
        }))
        return 1
    new = run_bench()
    if new.get("tsan"):
        print(json.dumps({
            "metric": "bench_guard", "status": "tsan_instrumented",
            "value": new.get("value"),
            "reason": "bench row was produced under SATURN_TPU_TSAN=1",
        }))
        return 1
    try:
        plan_errors = bench_plan_errors(new)
    except Exception as e:
        plan_errors = [{"code": "SAT-P000", "severity": "error",
                        "message": f"verifier unavailable: "
                                   f"{type(e).__name__}: {e}"}]
    if plan_errors:
        # Refuse to record: a row measured under a plan the static verifier
        # rejects is not a baseline anyone should compare against.
        print(json.dumps({
            "metric": "bench_guard", "status": "plan_verification_failed",
            "value": new.get("value"), "diagnostics": plan_errors,
        }))
        return 1
    try:
        sf_errors = bench_shardflow_errors()
    except Exception as e:
        sf_errors = [{"code": "SAT-X000", "severity": "error",
                      "message": f"shardflow pass unavailable: "
                                 f"{type(e).__name__}: {e}"}]
    if sf_errors:
        # Same refusal for the sharding pass: the row was measured by a
        # technique whose source carries an unsanctioned SAT-X funnel.
        print(json.dumps({
            "metric": "bench_guard", "status": "shardflow_findings",
            "value": new.get("value"), "diagnostics": sf_errors,
        }))
        return 1
    try:
        ml_errors = bench_memlens_errors()
    except Exception as e:
        ml_errors = [{"code": "SAT-M000", "severity": "error",
                      "message": f"memlens pass unavailable: "
                                 f"{type(e).__name__}: {e}"}]
    if ml_errors:
        # Same refusal for the liveness pass: the row was measured by a step
        # function carrying an unsanctioned SAT-M memory defect.
        print(json.dumps({
            "metric": "bench_guard", "status": "memlens_findings",
            "value": new.get("value"), "diagnostics": ml_errors,
        }))
        return 1
    try:
        tw_errors = twin_regression_errors()
    except Exception as e:
        tw_errors = [f"twin regression campaign unavailable: "
                     f"{type(e).__name__}: {e}"]
    if tw_errors:
        # Same refusal for the scheduling policy: the row was measured by a
        # control plane whose tier/verdict/makespan distributions drifted
        # out of the pinned twin band.
        print(json.dumps({
            "metric": "bench_guard", "status": "twin_regression",
            "value": new.get("value"), "diagnostics": tw_errors,
        }))
        return 1
    try:
        gd_errors = grow_defrag_errors()
    except Exception as e:
        gd_errors = [f"grow/defrag bench unavailable: "
                     f"{type(e).__name__}: {e}"]
    if gd_errors:
        # Same refusal for the recovery path: the row was measured by a
        # control plane whose grow/defrag loop lost work or left a
        # migration intent unresolved.
        print(json.dumps({
            "metric": "bench_guard", "status": "grow_defrag_failed",
            "value": new.get("value"), "diagnostics": gd_errors,
        }))
        return 1
    try:
        ov_errors = comm_overlap_errors()
    except Exception as e:
        ov_errors = [f"comm overlap bench unavailable: "
                     f"{type(e).__name__}: {e}"]
    if ov_errors:
        # Same refusal for the overlapped lowerings: a knob flip that
        # changed arithmetic (or an overlapped program that got slower
        # than its serial twin beyond the serialized-host tax) must not
        # be recorded as a baseline.
        print(json.dumps({
            "metric": "bench_guard", "status": "comm_overlap_failed",
            "value": new.get("value"), "diagnostics": ov_errors,
        }))
        return 1
    out = {
        "metric": "bench_guard",
        "value": new.get("value"),
        "reference": parsed_ref["value"],
        "reference_round": n,
        "threshold_pct": threshold * 100.0,
    }
    if shape_key(new) != shape_key(parsed_ref):
        # e.g. the reference is a degraded CPU record but this host has a
        # live TPU — different workload shapes, no comparison to make.
        out["status"] = "skipped"
        out["reason"] = (
            f"shape mismatch: ran {shape_key(new)} vs "
            f"recorded {shape_key(parsed_ref)}"
        )
        print(json.dumps(out))
        return 0
    floor = parsed_ref["value"] * (1.0 - threshold)
    if new.get("value", 0.0) < floor:
        out["status"] = "regression"
        out["floor"] = round(floor, 1)
        print(json.dumps(out))
        return 1
    out["status"] = "ok"
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
