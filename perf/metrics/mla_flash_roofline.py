"""Kernels: the latent-attention kernels' (``saturn_mla_*``: flash attention
with 192 score lanes over 128 value lanes) share of their roofline over the
traced window: per call the larger of required operations / peak and least
bytes / HBM bandwidth (``perf/lib/flops_ling.mla_flash_call``: the causal half
of every product at its own width, whatever the kernel pads) over the call's
device time. A line says which side bounds it. None where the trace holds no
such kernel (a program without the layer, or search chose the plain twin)."""

from perf.lib import flops, flops_ling, kernel_calls


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    least = took = 0.0
    bound = {}
    for kernel, job, seconds in kernel_calls.owned_calls(
            run, lambda k: k.startswith("saturn_mla_")):
        arch = run.arch(job)
        if not hasattr(arch, "kv_latent"):
            return None
        need = flops_ling.mla_flash_call(kernel, arch, job.batch, job.seq)
        r = flops.roofline_share(need["flops"], need["bytes"], seconds, run.peaks)
        least, took = least + r["least_s"], took + seconds
        bound[r["bound"]] = bound.get(r["bound"], 0.0) + seconds
    if took <= 0.0:
        return None
    print(f"perf: saturn_mla_* kernels: {took:.3f}s of device time, least possible "
          f"{least:.3f}s, bound by {max(bound, key=bound.get)}", flush=True)
    return 100.0 * least / took
