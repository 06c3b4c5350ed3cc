"""Device: peak bytes in use over the chip's limit, on the fullest chip
(``memory_stats()``), read at the end of the window: the largest the program
held through search and window (an earlier line says which of the two set
it). The reference check comes later and is not in it."""


def read(run):
    mem = run.window.get("memory")
    if not mem or not mem["bytes_limit"]:
        return None
    return 100.0 * mem["peak_bytes"] / mem["bytes_limit"]
