"""Device time of what XLA does to feed the Mamba-1 selective scan's kernels,
per traced step and chip, every phase, in milliseconds: the ops under the
program's ``ds.selscan.dt`` scope (``x_proj``, ``dt_proj`` with its float32
sum, bias and softplus, ``A = -exp(A_log)``, and the passes that lay ``x``,
``dt``, ``A``, ``D`` and ``[C | B]`` out as the kernels read them, with their
transposes in the backward), by ``scope_time``'s table of the innermost
``ds.*`` scope. A program without the scope reports nothing."""

from benchmark import scope_time

SCOPES = ("ds.selscan.dt", )


def read(run):
    table = scope_time.load(run)
    if table is None:
        return None
    scoped = sum(ms for (scope, _), ms in table["ds_ms"].items() if scope in SCOPES)
    return scoped or None
