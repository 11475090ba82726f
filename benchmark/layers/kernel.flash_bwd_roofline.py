"""The flash attention backward kernels' share of their roofline, the
``%flash_dq*`` and ``%flash_dkdv*`` custom calls together: four matmuls (dP,
dQ, dV, dK), one forward's count a call (``benchmark/flash_cost.py``), over
the time the device trace gives them. The kernels' recomputation of the
scores is not counted, so the share can only be understated."""

from benchmark import flash_cost


def read(run):
    return flash_cost.roofline_pct(run, ("%flash_dq", "%flash_dkdv"))
