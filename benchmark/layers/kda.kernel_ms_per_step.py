"""Device time of the Kimi Delta Attention scan's kernels (every traced
``%kda_*`` call, a recomputed forward included) per traced step, in
milliseconds."""

from benchmark import kda_cost


def read(run):
    return kda_cost.kernel_ms_per_step(run)
