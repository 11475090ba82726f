"""The MoE block's grouped matmuls' share of their roofline: the least time
of the traced ``%ragged-dot*`` / ``%moe_gmm*`` calls the reader matched
(``benchmark/moe_cost.py``: ``2 * rows * hidden * intermediate`` FLOP a call,
from the event's own shape, over the published bf16 peak; compute-bound)
over the time the device trace gives those same calls."""

from benchmark import device, moe_cost


def read(run):
    gmm = moe_cost.traced_gmm(run)
    if gmm is None:
        return None
    peak = device.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * gmm["flops"] / peak / gmm["seconds"]
