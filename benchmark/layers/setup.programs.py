"""Set-up compilation as JAX reports it, read when the window opens:
``programs`` of ``benchmark.device.CompileCounter``."""


def read(run):
    return float(run["setup"]["programs"])
