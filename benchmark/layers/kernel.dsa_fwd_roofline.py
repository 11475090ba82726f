"""The sparse-attention forward kernel's share of its roofline: the least
time of the traced ``%dsa_fwd*`` custom calls (``benchmark/dsa_cost.py``: ``4
* heads * head_dim`` FLOP a CHOSEN (query, key) pair, the pairs from the
event's shape and ``sa_config.topk``, over the published bf16 peak, or the
bytes of q, k, v and o once over the HBM peak if that is longer; the scores a
call makes again and the pairs it computes and masks count as no work) over
the time the device trace gives them, a recomputed forward included."""

from benchmark import dsa_cost


def read(run):
    return dsa_cost.roofline_pct(run, dsa_cost.FWD)
