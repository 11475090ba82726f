"""Device time of the training step's backward pass per traced step and chip,
in milliseconds: the events whose name stack carries ``transpose(`` and no
``rematted_computation``."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "bwd")
