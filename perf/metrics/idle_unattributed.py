"""Device: of the device's idle time in the traced window, the share that no
span of the program covers other than the enclosing ones (``orchestrate``,
``interval``): whether the spans are enough to say what the host was doing
while the chip waited. The idle stretches are the trace's gaps (the longest
50 a chip, which is where the idle time is), moved to the host clock by
``wall_offset_s``. The spans are the program's span events and its
``saturn.*`` annotations in the same trace (a span that ran before the
program's sink was open, such as the first call's ``import``, is only there).
None without a trace or where the program emits no spans. Prints the
window's self-time table and the distance between the two clocks."""

from perf.lib import spans, trace_reduce


def read(run):
    if run.trace is None:
        return None
    events, root = spans.window_events(run)
    if root is None:
        return None
    spans.print_table("window", run.events("window", None))
    off = run.trace["wall_offset_s"]
    covered = [spans.extent(e) for e in spans.spans(events)
               if e["kind"] not in spans.ENCLOSING]
    path = trace_reduce.find_xplane(run.window.get("trace_dir") or "")
    found = spans.annotations(path) if path else []
    covered += [(s / 1e9 + off, e / 1e9 + off) for name, s, e, _ in found
                if name not in spans.ENCLOSING]
    for name, s, e, _ in found:
        if name == "import":
            print(f"perf: saturn.import in the window: {(e - s) / 1e9:.3f}s",
                  flush=True)
    idle = bare = 0.0
    for dev in run.trace["devices"].values():
        gaps = [(s / 1e9 + off, e / 1e9 + off) for s, e in dev["gaps"]]
        idle += spans.length(gaps)
        bare += spans.length(spans.subtract(gaps, covered))
    skew = spans.clock_skew(found, events, off)
    if skew is not None:
        print(f"perf: trace clock against the events' clock: {len(found)} saturn.* "
              f"annotations, {skew['n_paired']} paired; largest distance "
              f"{skew['skew_s'] * 1e3:+.3f} ms ({skew['kind']})", flush=True)
    if idle <= 0:
        return None
    return 100.0 * bare / idle
