"""Device time of a looped model's exits, per traced step and chip, every
phase, in milliseconds: the ops whose innermost ``ds.*`` scope is the
program's ``ds.loop.exit`` (the gate after each pass but the last, the exit
distribution ``p``, its entropy, the statistics and the weighting of the loss),
by ``scope_time``'s table of the innermost ``ds.*`` scope. A program without
the scope (the commit before the loop) reports nothing."""

from benchmark import scope_time

SCOPE = "ds.loop.exit"


def read(run):
    table = scope_time.load(run)
    if table is None:
        return None
    scoped = sum(ms for (scope, _), ms in table["ds_ms"].items() if scope == SCOPE)
    return scoped or None
