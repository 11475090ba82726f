"""Device time of the block-diffusion attention kernels per traced step and
chip, in milliseconds: every traced custom call named ``%bdattn_*`` (the
forward, the recomputed forward and the backward of each layer). Where the
two roofline shares credit each call with its least work, this is the time
itself."""

from benchmark import bdattn_cost


def read(run):
    return bdattn_cost.kernel_ms_per_step(run)
