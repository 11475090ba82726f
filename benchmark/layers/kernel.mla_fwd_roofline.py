"""The latent-attention forward kernel's share of its roofline: the least
time of the traced ``%mla_fwd*`` custom calls (``benchmark/mla_cost.py``: ``2
* (d_qk + d_v)`` FLOP a live (head, query, key) pair, QK^T at the keys' width
and PV at the values', over the published bf16 peak; compute-bound) over the
time the device trace gives them, a recomputed forward included."""

from benchmark import mla_cost


def read(run):
    return mla_cost.roofline_pct(run, mla_cost.FWD)
