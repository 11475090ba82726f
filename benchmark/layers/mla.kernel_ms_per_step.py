"""Device time of the latent-attention kernels per traced step and chip, in
milliseconds: every traced custom call named ``%mla_*`` (the forward and the
backward of each layer, and a forward run again where recomputation keeps
nothing). Where the two roofline shares credit each call with its least
work, this is the time itself."""

from benchmark import mla_cost


def read(run):
    return mla_cost.kernel_ms_per_step(run)
