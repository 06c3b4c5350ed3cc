"""Trial runner: grid points per job that took no timed step because the
chip's compiler refused their program for memory (HBM over the limit, or a
kernel over its scoped VMEM) or the program's own memory check rejected it.
Since PR 29 a compiler's refusal is compiled once per compile cache (the
priming run) and replayed from its record under ``saturn-refused/``
afterwards: what a counted point still costs a primed run is its build, its
trace and lowering and the text hash (about 4 s at the cells' widths), and,
for a point the 0.92 x HBM rule rejects, a cached compile and the memory
check's re-trace; ``search_refused_share`` has the seconds."""


def read(run):
    if not run.search:
        return None
    refused = [e for e in run.events("search", "trial_config")
               if "error" in e or "memory_rejected" in e]
    return len(refused) / len(run.jobs)
