"""Step program: the steps' share of the chip's bf16 peak by a Ling stack's own
required operations (``perf/lib/flops_ling.py``: the held matrices of each
mixer as multiplied, the routed experts a token really multiplies, latent
attention's causal half at 192 + 128 lanes, the delta rule's chunked
products), as ``mfu.py`` does it for a stack of identical attention blocks: x
tokens trained, over the seconds the program clocks for its steps x chips x
peak. None where the configuration's ``Arch`` is no such stack's."""

from perf.lib import flops_ling, readers


def read(run):
    spans = readers.work_spans(run)
    took = sum(s["elapsed_s"] for s in spans)
    if took <= 0.0 or run.peaks is None:
        return None
    need = 0.0
    for s in spans:
        job = run.job(s["task"])
        arch = run.arch(job)
        if not hasattr(arch, "kv_latent"):
            return None
        need += (flops_ling.required_flops_per_token(arch, job.seq)
                 * s["batches"] * job.tokens_per_step)
    return 100.0 * need / took / (len(run.devices) * run.peaks["bf16_flops_per_s"])
