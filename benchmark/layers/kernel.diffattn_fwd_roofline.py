"""The differential attention forward calls' share of their roofline: the
least time of a traced step's attention layers (``benchmark/diffattn_cost.py``:
``2 * 2 * (64 + 128)`` FLOP a live (query, key) pair and pair of heads, the
live pairs from the event's sequence and the file's ``sliding_window`` and
``layer_types``, over the bf16 peak, or q, k, v and the output over the HBM
bandwidth, whichever is larger) times the traced steps, over the time the
device trace gives the ``%mla_fwd*`` calls (in a program whose attention is
all differential every one is such a call), a recomputed forward included."""

from benchmark import diffattn_cost


def read(run):
    return diffattn_cost.roofline_pct(run, diffattn_cost.FWD)
