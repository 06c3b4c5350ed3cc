"""Trial runner: grid points per job that took no timed step because the
chip's compiler refused their program for memory (HBM over the limit, or a
kernel over its scoped VMEM) or the program's own memory check rejected it:
compiles paid for nothing, in every run (a refused compile is not cached)."""


def read(run):
    if not run.search:
        return None
    refused = [e for e in run.events("search", "trial_config")
               if "error" in e or "memory_rejected" in e]
    return len(refused) / len(run.jobs)
