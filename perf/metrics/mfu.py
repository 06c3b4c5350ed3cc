"""Step program: model FLOP/s utilization of the steps themselves: operations
the forward and backward passes *require* per token (``perf/lib/flops.py``:
6 x matmul parameters + 12 L S d; recomputation not counted, head counted) x
tokens trained, over the seconds the program itself clocks for its steps (sum
of ``elapsed_s`` of the intervals) x chips x the published bf16 peak. Launch,
checkpoint and solve are not in the denominator: they are the engine's
(``engine_overhead``), and the whole window's rate is ``train_tokens_per_s``."""

from perf.lib import readers


def read(run):
    spans = readers.work_spans(run)
    took = sum(s["elapsed_s"] for s in spans)
    if took <= 0.0 or run.peaks is None:
        return None
    need = sum(readers.job_flops_per_token(run, run.job(s["task"]))
               * s["batches"] * run.job(s["task"]).tokens_per_step for s in spans)
    peak = len(run.devices) * run.peaks["bf16_flops_per_s"]
    return 100.0 * need / took / peak
