"""Seconds jax traced functions into jaxprs, over every compile of the process
(an inner jit's trace inside the outermost): the program's
``ds_compile_trace_seconds_total``, all keys."""

from benchmark import compile_anatomy


def read(run):
    return compile_anatomy.counter_sum("ds_compile_trace_seconds_total")
