"""Device time of the sequence operators (``self_attn``, ``conv``, ``mamba``)
less their custom calls, per traced step and chip, every phase, in
milliseconds: q/k/v/o and in/out projections, q/k norms, rotary, gates. The
custom calls are the named kernels, which have metrics of their own
(``flash.kernel_ms_per_step``, ``conv.kernel_ms_per_step``,
``ssm.kernel_ms_per_step``)."""

from benchmark import scope_time


def read(run):
    return scope_time.part_ms(run, "mixer", less_custom=True)
