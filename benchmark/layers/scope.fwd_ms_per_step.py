"""Device time of the training step's forward pass per traced step and chip,
in milliseconds: the "XLA Ops" events whose name stack carries ``jvp(`` and
neither ``transpose(`` nor ``rematted_computation`` (``scope_time.phase_of``).
The head's loss and gradients are formed in one forward sweep
(``ops/chunked_ce.py``), so they count here."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "fwd")
