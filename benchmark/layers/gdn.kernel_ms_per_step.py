"""Device time of the Gated DeltaNet scan's kernels (every traced ``%gdn_*``
call, a recomputed forward included) per traced step, in milliseconds."""

from benchmark import gdn_cost


def read(run):
    return gdn_cost.kernel_ms_per_step(run)
