"""The chunked state-space scan's backward kernel's share of its roofline:
the least time of the traced ``%ssd_chunk_bwd*`` calls (``benchmark/
ssd_cost.py``: twice the forward's FLOPs over the bf16 peak or its bytes over
the HBM bandwidth, whichever is larger; what it recomputes is not counted)
over the time the device trace gives them."""

from benchmark import ssd_cost


def read(run):
    return ssd_cost.roofline_pct(ssd_cost.traced_ssd(run, (ssd_cost.SSD_BWD, )))
