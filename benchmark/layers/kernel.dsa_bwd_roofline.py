"""The sparse-attention backward kernels' share of their roofline: the least
time of the traced ``%dsa_bwd*`` custom calls (``benchmark/dsa_cost.py``: ``8
* heads * head_dim`` FLOP a CHOSEN pair, each kernel of the pair credited by
its name with its own two matmuls, recomputed scores with none; over the
published bf16 peak) over the time the device trace gives them."""

from benchmark import dsa_cost


def read(run):
    return dsa_cost.roofline_pct(run, dsa_cost.BWD)
