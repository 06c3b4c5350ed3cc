"""Kernels: the grouped-product kernels' (``saturn_gmm_*``) share of their
roofline where the experts read and write a latent width that is not the
stream's (``gmm_roofline.py``'s quantity with
``perf/lib/flops_nemotron_h.gmm_call``: the rows really routed, from
``moe_pairs_held`` of the ``task_interval`` events, times d_latent x
d_expert; the rows in and out once and the held tables once) over the call's
device time. A line says which side bounds it. None where the trace holds no
such kernel, the events carry no such counter, or the configuration's
``Arch`` has no latent width."""

from perf.lib import flops, flops_nemotron_h, kernel_calls


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    rows = {e["task"]: float(e["moe_pairs_held"])
            for e in run.events("window", "task_interval") if "moe_pairs_held" in e}
    least = took = 0.0
    bound = {}
    for kernel, job, seconds in kernel_calls.owned_calls(
            run, lambda k: k.startswith("saturn_gmm_")):
        arch = run.arch(job)
        if job.name not in rows or not hasattr(arch, "d_latent"):
            continue
        need = flops_nemotron_h.gmm_call(kernel, arch, rows[job.name])
        r = flops.roofline_share(need["flops"], need["bytes"], seconds, run.peaks)
        least, took = least + r["least_s"], took + seconds
        bound[r["bound"]] = bound.get(r["bound"], 0.0) + seconds
    if took <= 0.0:
        return None
    print(f"perf: saturn_gmm_* kernels (latent rows): {took:.3f}s of device time, "
          f"least possible {least:.3f}s, bound by {max(bound, key=bound.get)}", flush=True)
    return 100.0 * least / took
