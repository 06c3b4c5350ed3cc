"""Kernels: the grouped-product kernels' (``saturn_gmm_*``) share of their
roofline over the traced window: per call the larger of required operations /
peak and least bytes / HBM bandwidth (``perf/lib/flops_laguna.gmm_call``: the
rows really routed, from ``moe_pairs_held`` of the ``task_interval`` events,
times d_model x d_expert; the rows in and out once and the held tables once)
over the call's device time. A line says which side bounds it. None where the
trace holds no such kernel (a program without the layer, or search chose the
plain twin) or the events carry no such counter."""

from perf.lib import flops, flops_laguna, kernel_calls


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    rows = {e["task"]: float(e["moe_pairs_held"])
            for e in run.events("window", "task_interval") if "moe_pairs_held" in e}
    least = took = 0.0
    bound = {}
    for kernel, job, seconds in kernel_calls.owned_calls(
            run, lambda k: k.startswith("saturn_gmm_")):
        if job.name not in rows:
            continue
        need = flops_laguna.gmm_call(kernel, run.arch(job), rows[job.name])
        r = flops.roofline_share(need["flops"], need["bytes"], seconds, run.peaks)
        least, took = least + r["least_s"], took + seconds
        bound[r["bound"]] = bound.get(r["bound"], 0.0) + seconds
    if took <= 0.0:
        return None
    print(f"perf: saturn_gmm_* kernels: {took:.3f}s of device time, least possible "
          f"{least:.3f}s, bound by {max(bound, key=bound.get)}", flush=True)
    return 100.0 * least / took
