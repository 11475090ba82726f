"""The chunked state-space scan's forward kernel's share of its roofline:
the least time of the traced ``%ssd_chunk_fwd*`` calls (``benchmark/
ssd_cost.py``: the larger of its FLOPs over the bf16 peak and its bytes over
the HBM bandwidth, from the event's own shape; memory-bound at the published
sizes) over the time the device trace gives them."""

from benchmark import ssd_cost


def read(run):
    return ssd_cost.roofline_pct(ssd_cost.traced_ssd(run, (ssd_cost.SSD_FWD, )))
