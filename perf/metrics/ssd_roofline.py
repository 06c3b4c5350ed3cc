"""Kernels: the state-space recurrence's kernels' (``saturn_ssd_*``) share of
their roofline over the traced window: per call the larger of required
operations / peak and least bytes / HBM bandwidth
(``perf/lib/flops_nemotron_h.ssd_call``, from the running job's shapes: the
chunked form's products at the published chunk, each operand and the kept
states crossing HBM once) over the call's device time. A line says which side
bounds it. None where the trace holds no such kernel (a program without the
layer, or search chose the plain scan)."""

from perf.lib import flops, flops_nemotron_h, kernel_calls


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    least = took = 0.0
    bound = {}
    for kernel, job, seconds in kernel_calls.owned_calls(
            run, lambda k: k.startswith("saturn_ssd_")):
        arch = run.arch(job)
        if not hasattr(arch, "ssm_heads"):
            return None
        need = flops_nemotron_h.ssd_call(kernel, arch, job.batch, job.seq)
        r = flops.roofline_share(need["flops"], need["bytes"], seconds, run.peaks)
        least, took = least + r["least_s"], took + seconds
        bound[r["bound"]] = bound.get(r["bound"], 0.0) + seconds
    if took <= 0.0:
        return None
    print(f"perf: saturn_ssd_* kernels: {took:.3f}s of device time, least possible "
          f"{least:.3f}s, bound by {max(bound, key=bound.get)}", flush=True)
    return 100.0 * least / took
