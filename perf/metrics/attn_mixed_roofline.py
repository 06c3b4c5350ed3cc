"""Kernels: the attention kernels' share of their roofline where a stack mixes
full and sliding-window layers: every call of ``saturn_flash_*`` (a full
layer's) and of ``saturn_swa_*`` (a sliding layer's) in the traced window,
each against its own least time (``perf/lib/flops_laguna.attn_call``: the
causal or windowed half of the products at that kind's q heads, the k/v-side
tensors at the k/v heads), over the calls' device time. A line gives each
kind's own share and says which bounds the sum. None where the trace holds no
window kernel (a program without such layers, or search chose the plain
twins) or the ``Arch`` is no such stack's."""

from perf.lib import flops, flops_laguna, kernel_calls


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    by_kind = {}
    for kernel, job, took in kernel_calls.owned_calls(
            run, flops_laguna.ATTN_KERNELS.__contains__):
        a = run.arch(job)
        if not hasattr(a, "ffs"):
            return None
        need = flops_laguna.attn_call(kernel, a, job.batch, job.seq)
        r = flops.roofline_share(need["flops"], need["bytes"], took, run.peaks)
        least, total = by_kind.get(need["kind"], (0.0, 0.0))
        by_kind[need["kind"]] = (least + r["least_s"], total + took)
    if flops_laguna.SLIDING not in by_kind:
        return None
    least = sum(v[0] for v in by_kind.values())
    took = sum(v[1] for v in by_kind.values())
    slowest = max(by_kind, key=lambda k: by_kind[k][1] - by_kind[k][0])
    print("perf: attention kernels: " + "; ".join(
        f"{k} {t:.3f}s of device time, least possible {l:.3f}s ({100 * l / t:.1f} %)"
        for k, (l, t) in sorted(by_kind.items()))
        + f"; the {slowest} calls hold most of the time over the least", flush=True)
    return 100.0 * least / took
