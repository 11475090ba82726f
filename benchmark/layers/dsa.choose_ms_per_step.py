"""Device time of all that makes the sparse attention's choice, per traced
step and chip, in milliseconds: the ``%dsa_index*`` custom calls (the scores
of every causal pair and each row's threshold) and the ops under the
program's ``ds.dsa.index`` scope (the indexer's projections, its norm and
rotary) and ``ds.dsa.select`` (what of the selection is not inside a
kernel), every phase, by ``scope_time``'s table of the innermost ``ds.*``
scope. It reads something whichever way the choice is made."""

from benchmark import dsa_cost, scope_time

SCOPES = ("ds.dsa.index", "ds.dsa.select")


def read(run):
    kernels = dsa_cost.ms_per_step(run, (dsa_cost.INDEX, ))
    table = scope_time.load(run)
    scoped = None if table is None else sum(
        ms for (scope, _), ms in table["ds_ms"].items() if scope in SCOPES)
    if not kernels and not scoped:
        return None
    return (kernels or 0.0) + (scoped or 0.0)
