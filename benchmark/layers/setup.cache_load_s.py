"""Seconds spent reading executables back from the persistent compilation
cache: the program's ``ds_compile_cache_load_seconds_total``, all keys. Part of
``setup.compile_s``, which counts a load as a compile; near it on a cached
run, near 0 on one that compiles."""

from benchmark import compile_anatomy


def read(run):
    return compile_anatomy.counter_sum("ds_compile_cache_load_seconds_total")
