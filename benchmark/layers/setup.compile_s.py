"""Set-up compilation as JAX reports it, read when the window opens:
``compile_s`` of ``benchmark.device.CompileCounter``."""


def read(run):
    return float(run["setup"]["compile_s"])
