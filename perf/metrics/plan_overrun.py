"""Solver: the window's wall over the makespan of the first plan, minus 1, in
percent: what the plan did not price (launches, checkpoints, re-solves)."""


def read(run):
    solves = run.events("window", "solve")
    if not solves or not run.window.get("wall_s"):
        return None
    makespan = float(solves[0]["plan"]["makespan"])
    if makespan <= 0.0:
        return None
    return 100.0 * (run.window["wall_s"] / makespan - 1.0)
