"""The latent-attention backward kernels' share of their roofline: the least
time of the traced ``%mla_bwd*`` custom calls (``benchmark/mla_cost.py``: dV
and dP at the values' width, dQ and dK at the keys', ``4 * (d_qk + d_v)``
FLOP a live pair, each kernel of a pair credited by its name with its own
two matmuls, recomputed scores with none; over the published bf16 peak) over
the time the device trace gives them."""

from benchmark import mla_cost


def read(run):
    return mla_cost.roofline_pct(run, mla_cost.BWD)
