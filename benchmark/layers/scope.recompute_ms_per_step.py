"""Device time of the forward pass recomputed inside the backward per traced
step and chip, in milliseconds: the events whose name stack carries
``rematted_computation``. 0 where the cell does not recompute; nothing only
where nothing is read."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "recompute")
