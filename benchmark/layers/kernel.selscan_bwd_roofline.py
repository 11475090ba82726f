"""The Mamba-1 selective scan's backward kernel's share of its roofline: the
least time of the traced ``%selscan_bwd*`` calls (``benchmark/
selscan_cost.py``: the bytes of ``x``, ``dt``, ``dy``, ``B``, ``C`` and the
float32 block states read, of ``dx``, ``d dt`` and ``B``'s and ``C``'s
gradients written, over the HBM bandwidth; the block's forward that the kernel
makes again is not counted, and there is no vector peak to hold it to) over the
time the device trace gives them."""

from benchmark import selscan_cost


def read(run):
    return selscan_cost.roofline_pct(run, selscan_cost.BWD)
