"""The flash attention forward kernel's share of its roofline: the least
time of the traced ``%flash_fwd*`` custom calls (``benchmark/flash_cost.py``:
``4 * heads * d * mean keys per query * seq * rows`` FLOP a call over the
published bf16 peak; compute-bound at these shapes) over the time the device
trace gives them."""

from benchmark import flash_cost


def read(run):
    return flash_cost.roofline_pct(run, ("%flash_fwd", ))
