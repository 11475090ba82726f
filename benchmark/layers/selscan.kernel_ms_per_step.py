"""Device time of the Mamba-1 selective scan's kernels (every traced
``%selscan_*`` call, a recomputed forward included) per traced step, in
milliseconds."""

from benchmark import selscan_cost


def read(run):
    return selscan_cost.kernel_ms_per_step(run)
