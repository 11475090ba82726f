"""Device time of recomputation in the looped cell, per traced step and chip,
in milliseconds: ``scope_time``'s phase ``recompute``, what the plan could not
keep by name, paid once a PASS and layer (32 applications a step). A program
without the step's scopes reports nothing."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "recompute") or None
