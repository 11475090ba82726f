"""Device time of the MoE block's grouped matmuls (the traced ``%ragged-dot*``
/ ``%moe_gmm*`` calls ``benchmark/moe_cost.py`` matches, forward and
backward) per traced step, in milliseconds."""

from benchmark import moe_cost


def read(run):
    gmm = moe_cost.traced_gmm(run)
    if gmm is None or not run.get("trace_steps"):
        return None
    return 1e3 * gmm["seconds"] / run["trace_steps"]
