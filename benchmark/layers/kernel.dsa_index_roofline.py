"""The sparse-attention indexer kernel's share of its roofline: the least
time of the traced ``%dsa_index*`` custom calls (``benchmark/dsa_cost.py``:
``2 * indexer heads * indexer width`` FLOP a CAUSAL (query, key) pair from
the event's own shape, over the published bf16 peak; the ReLU, the weighted
sum of the heads and the selection, which are most of its time, count as no
work) over the time the device trace gives them."""

from benchmark import dsa_cost


def read(run):
    return dsa_cost.roofline_pct(run, dsa_cost.INDEX)
