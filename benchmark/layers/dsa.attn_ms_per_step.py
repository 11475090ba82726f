"""Device time of the sparse-attention kernels over the chosen pairs per
traced step and chip, in milliseconds: every traced custom call named
``%dsa_fwd*`` or ``%dsa_bwd*`` (the forward and the backward pair of each
layer, and a forward run again where recomputation keeps nothing)."""

from benchmark import dsa_cost


def read(run):
    return dsa_cost.ms_per_step(run, (dsa_cost.FWD, dsa_cost.BWD))
