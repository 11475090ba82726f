"""Device time of the state-space mixer's kernels (the traced ``%ssd_*`` and
``%causal_conv_*`` calls, a recomputed forward included) per traced step, in
milliseconds."""

from benchmark import ssd_cost


def read(run):
    return ssd_cost.kernel_ms_per_step(run)
