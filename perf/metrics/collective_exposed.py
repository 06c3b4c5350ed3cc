"""Technique: share of the traced window in which a collective was in flight
on a chip and no other operation ran there: the communication the step waits
for, which an overlapped program hides. Mean over the chips; the worst chip
is printed beside it. None where the trace holds no collective."""


def read(run):
    t = run.trace
    if t is None or not t.get("n_collectives") or t["window_s"] <= 0:
        return None
    per_chip = {name: 100.0 * d["collective_exposed_s"] / t["window_s"]
                for name, d in t["devices"].items()}
    worst = max(per_chip, key=per_chip.get)
    print(f"perf: collectives exposed: {per_chip[worst]:.3f} % of the window on "
          f"{worst} (the most), {min(per_chip.values()):.3f} % the least", flush=True)
    return sum(per_chip.values()) / len(per_chip)
