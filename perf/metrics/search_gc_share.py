"""Trial runner: share of the search's wall inside passes of Python's cyclic
collector, on whichever thread they ran (``gc_s`` of the ``search`` span: the
process's passes over its extent, counted by the program's ``gc.callbacks``
entry; PR 39). Its line prints the full (generation-2) passes and the longest.
None where the program counts no passes."""

from perf.lib import critical_path


def read(run):
    root, _ = critical_path.search_tree(run)
    if root is None or "gc_s" not in root:
        return None
    print(f"perf: collector in the search: {root['gc_s']:.3f}s in "
          f"{root.get('gc_n', 0)} passes, {root.get('gc_full', 0)} full, the "
          f"longest {root.get('gc_full_max_s', 0.0):.3f}s", flush=True)
    return 100.0 * float(root["gc_s"]) / root["dur_s"]
