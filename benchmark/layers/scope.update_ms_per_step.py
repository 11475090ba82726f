"""Device time of the step after the gradients per traced step and chip, in
milliseconds: unscale, overflow test, global norm and clip
(``ds.step.grad_norm``), then the optimizer, the overflow select and the
scale-state update (``ds.step.optimizer``), the engine's own scopes."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "update")
