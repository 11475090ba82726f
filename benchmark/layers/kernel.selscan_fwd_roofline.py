"""The Mamba-1 selective scan's forward kernel's share of its roofline: the
least time of the traced ``%selscan_fwd*`` calls whose result a backward call
used (as many as ``%selscan_bwd*`` calls; ``benchmark/selscan_cost.py``: the
bytes of ``x``, ``dt``, ``B``, ``C`` read, ``y`` and the float32 block states
written, from the event's own shape and the file's sizes, over the HBM
bandwidth: ``peaks.json`` has no vector peak, so the share is against memory
alone and can only be understated) over the time the device trace gives ALL of
them: a forward that a recomputed layer runs again adds time and no work."""

from benchmark import selscan_cost


def read(run):
    return selscan_cost.roofline_pct(run, selscan_cost.FWD)
