"""Serving programs compiled inside the measured window (CompileWatch count
at its close minus at its open). Should read 0."""


def read(run):
    c = run.get("compiles")
    return float(c["in_window"]) if c else None
