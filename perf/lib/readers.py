"""Reductions that several metric readers share: from the window's event
file to device-work spans, from a job to its required operations."""

from __future__ import annotations

from typing import Any, Dict, List

from perf.lib import flops


def work_spans(run: Any) -> List[Dict[str, Any]]:
    """One entry per stretch of device work in the window, from the
    ``task_interval`` events: whose, from when to when (host wall clock,
    seconds), how many steps it advanced and the seconds the program itself
    reports for them."""
    return sorted(
        ({"task": e["task"], "start": e["ts_start"], "end": e["ts"],
          "batches": int(e["batches"]), "elapsed_s": float(e["elapsed_s"])}
         for e in run.events("window", "task_interval")),
        key=lambda s: s["start"])


def job_flops_per_token(run: Any, job: Any) -> float:
    a = run.arch(job)
    return flops.required_flops_per_token(
        a.d_model, a.n_layers, a.d_ff, a.vocab_size, job.seq)


def kernel_roofline(run: Any, family: str):
    """Share of the roofline of one kernel family (``saturn_flash_`` or
    ``saturn_ce_``) over every call in the traced window: the least time the
    chip could take for the calls (by ``perf/lib/flops.py`` from the shapes
    of the job that was running, the larger of operations / peak and bytes /
    HBM bandwidth, per call) over the device time of the calls. None when the
    trace holds no such kernel (search chose dense attention or the logits
    loss)."""
    if run.trace is None or run.peaks is None:
        return None
    off = run.trace["wall_offset_s"]
    spans = work_spans(run)
    least = took = 0.0
    bound: Dict[str, float] = {}
    for dev in run.trace["devices"].values():
        for kernel, calls in dev["kernels"].items():
            if not kernel.startswith(family):
                continue
            for start_ns, dur_ns in calls:
                wall = start_ns / 1e9 + off
                owner = next((s for s in spans
                              if s["start"] - 0.5 <= wall <= s["end"] + 0.5), None)
                if owner is None:
                    continue
                job = run.job(owner["task"])
                a = run.arch(job)
                if family == "saturn_flash_":
                    need = flops.flash_call(kernel, job.batch, a.n_heads, job.seq,
                                            a.head_dim)
                else:
                    need = flops.ce_call(kernel, job.batch * job.seq, a.d_model,
                                         a.vocab_size)
                r = flops.roofline_share(need["flops"], need["bytes"],
                                         dur_ns / 1e9, run.peaks)
                least += r["least_s"]
                took += dur_ns / 1e9
                bound[r["bound"]] = bound.get(r["bound"], 0.0) + dur_ns / 1e9
    if took <= 0.0:
        return None
    which = max(bound, key=bound.get)
    print(f"perf: {family}* kernels: {took:.3f}s of device time, least possible "
          f"{least:.3f}s, bound by {which}", flush=True)
    return 100.0 * least / took
