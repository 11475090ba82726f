"""Set-up compilation as JAX reports it, read when the window opens:
``cache_misses`` of ``benchmark.device.CompileCounter``."""


def read(run):
    return float(run["setup"]["cache_misses"])
