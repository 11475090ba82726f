"""Device time of the MoE blocks (``block_sparse_moe``) per traced step and
chip, every phase, in milliseconds: router, sort, dispatch, the grouped
matmuls, combine. Less ``moe.gmm_ms_per_step`` it is what surrounds the
matmuls."""

from benchmark import scope_time


def read(run):
    return scope_time.part_ms(run, "moe")
