"""Step program: the steps' share of the chip's bf16 peak by an LFM2 stack's
own required operations (``perf/lib/flops_lfm2.py``: the conv mixers' and the
attention mixer's projections, the leading dense SwiGLU, the routers, the
routed experts a token really multiplies among the held ones, the causal half
of attention, the held rows of the tied head), as ``mfu.py`` does it for a
stack of identical attention blocks: x tokens trained, over the seconds the
program clocks for its steps x chips x peak. None where the configuration's
``Arch`` is no such stack's."""

from perf.lib import flops_lfm2, readers


def read(run):
    spans = readers.work_spans(run)
    took = sum(s["elapsed_s"] for s in spans)
    if took <= 0.0 or run.peaks is None:
        return None
    need = 0.0
    for s in spans:
        job = run.job(s["task"])
        arch = run.arch(job)
        if getattr(arch, "family", None) != "lfm2":
            return None
        need += (flops_lfm2.required_flops_per_token(arch, job.seq)
                 * s["batches"] * job.tokens_per_step)
    return 100.0 * need / took / (len(run.devices) * run.peaks["bf16_flops_per_s"])
