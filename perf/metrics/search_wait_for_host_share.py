"""Trial runner: share of the search's wall in which a host-work span is open
on some thread (a grid point's build, compile or memory check, the static
prior) and no chip-side span (init, stage, timing) on any: the chip has
nothing to measure, because the first point is still in preparation or the
measuring thread waits for the next (PR 39; ``perf/lib/critical_path.py``).
With ``search_both_busy_share``, ``search_wait_for_chip_share`` and
``search_own_share`` it adds up to 100. Its line also says where JAX's own
seconds were spent by thread, and what the points that took no timed step
cost the host. None where the program emits no spans."""

from perf.lib import critical_path


def read(run):
    p = critical_path.partition(run)
    if p is None:
        return None
    s = p["seconds"]
    print(f"perf: search wall {p['wall']:.3f}s: wait for host "
          f"{s['wait_for_host']:.3f}s, both busy {s['both_busy']:.3f}s, wait for "
          f"chip {s['wait_for_chip']:.3f}s, own {s['own']:.3f}s", flush=True)
    by = critical_path.host_seconds_by_thread(p["events"])
    if any(v for side in by.values() for v in side.values()):
        for who, side in (("the main thread", by["main"]),
                          ("other threads", by["others"])):
            print(f"perf:   host seconds on {who}: " + ", ".join(
                f"{field} {side[field]:.3f}"
                for field in critical_path.HOST_SECONDS), flush=True)
    print(f"perf:   host work of the points that took no timed step: "
          f"{critical_path.untimed_host_seconds(p['events']):.3f}s", flush=True)
    return 100.0 * s["wait_for_host"] / p["wall"]
