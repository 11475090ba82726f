"""Seconds jax turned jaxprs into MLIR modules, over every compile of the
process: the program's ``ds_compile_lower_seconds_total``, all keys."""

from benchmark import compile_anatomy


def read(run):
    return compile_anatomy.counter_sum("ds_compile_lower_seconds_total")
