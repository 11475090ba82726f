"""Device time of the dense feed-forward blocks (``mlp``, ``shared_expert``)
per traced step and chip, every phase, in milliseconds."""

from benchmark import scope_time


def read(run):
    return scope_time.part_ms(run, "ffn")
