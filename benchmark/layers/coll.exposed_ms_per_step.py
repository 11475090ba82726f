"""Collective time per step during which no other operation ran on that
device (all-gather, reduce-scatter, all-reduce events of the device trace),
on the worst device."""


def read(run):
    t = run.get("trace")
    if not t or not run.get("trace_steps"):
        return None
    return t["exposed_collective_s"] * 1e3 / run["trace_steps"]
