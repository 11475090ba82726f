"""Mean decode steps per fused dispatch in the window: the scheduler's exact
counters, ``fused_k_sum / fused_dispatches``."""


def read(run):
    c = run.get("counters")
    if not c:
        return None
    waves = c["close"]["fused_dispatches"] - c["open"]["fused_dispatches"]
    steps = c["close"]["fused_k_sum"] - c["open"]["fused_k_sum"]
    return steps / waves if waves else None
