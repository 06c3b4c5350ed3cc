"""Kernels: the gated-delta-rule kernels' (``saturn_gdn_*``) share of their
roofline over the traced window: per call the larger of required operations /
peak and least bytes / HBM bandwidth (``perf/lib/flops_hybrid.gdn_call``, from
the running job's shapes: the chunked form's products at chunk 64, each
operand crossing HBM once) over the call's device time. A line says which side
bounds it. None where the trace holds no such kernel (a program without the
layer, or search chose the plain scan)."""

from perf.lib import flops, flops_hybrid, readers


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    off, spans = run.trace["wall_offset_s"], readers.work_spans(run)
    least = took = 0.0
    bound = {}
    for dev in run.trace["devices"].values():
        for kernel, calls in dev["kernels"].items():
            if not kernel.startswith("saturn_gdn_"):
                continue
            for start_ns, dur_ns in calls:
                wall = start_ns / 1e9 + off
                owner = next((s for s in spans
                              if s["start"] - 0.5 <= wall <= s["end"] + 0.5), None)
                if owner is None:
                    continue
                job = run.job(owner["task"])
                a = run.arch(job)
                need = flops_hybrid.gdn_call(kernel, job.batch, a.n_heads, job.seq,
                                             a.key_dim, a.value_dim)
                r = flops.roofline_share(need["flops"], need["bytes"], dur_ns / 1e9,
                                         run.peaks)
                least, took = least + r["least_s"], took + dur_ns / 1e9
                bound[r["bound"]] = bound.get(r["bound"], 0.0) + dur_ns / 1e9
    if took <= 0.0:
        return None
    print(f"perf: saturn_gdn_* kernels: {took:.3f}s of device time, least possible "
          f"{least:.3f}s, bound by {max(bound, key=bound.get)}", flush=True)
    return 100.0 * least / took
