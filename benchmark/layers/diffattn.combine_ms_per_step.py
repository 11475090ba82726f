"""Device time of what XLA does around the differential attention kernels, per
traced step and chip, every phase, in milliseconds: the ops under the
program's ``ds.diffattn.combine`` scope (the heads' reordering into ``[even |
odd]`` and the paired values before the call; ``lambda``, ``A1 V - lambda A2
V``, the 128-wide ``subln`` norm and ``1 - lambda_init`` after it), by
``scope_time``'s table of the innermost ``ds.*`` scope. A program without the
scope reports nothing."""

from benchmark import scope_time

SCOPES = ("ds.diffattn.combine", )


def read(run):
    table = scope_time.load(run)
    if table is None:
        return None
    scoped = sum(ms for (scope, _), ms in table["ds_ms"].items() if scope in SCOPES)
    return scoped or None
