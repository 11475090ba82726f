"""Share of the chips' busy time in the traced steps whose op carries a module
or a ``ds.*`` scope (a part other than ``unnamed``): how complete the naming
of device time is, the twin of ``host.idle_unnamed_pct.train``."""

from benchmark import scope_time


def read(run):
    table = scope_time.load(run)
    return None if table is None else scope_time.named_pct(table)
