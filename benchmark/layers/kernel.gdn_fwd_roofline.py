"""The Gated DeltaNet scan's forward kernel's share of its roofline: the least
time of the traced ``%gdn_chunk_fwd*`` calls whose result a backward call used
(as many as ``%gdn_chunk_bwd*`` calls; ``benchmark/gdn_cost.py``: the larger
of the chunk algebra's FLOPs over the bf16 peak and the bytes of q, k of the
key heads, v and o of the value heads, ``g`` and ``beta`` one a head and token
and the float32 chunk states over the HBM bandwidth, from the event's own
shape and the file's ``linear_*`` keys and ``gdn_chunk_size``; memory-bound at
the published sizes) over the time the device trace gives ALL of them: a
forward that a recomputed layer runs again adds time and no work. A program
without the kernels reports nothing; a share over 100 is refused."""

from benchmark import gdn_cost


def read(run):
    return gdn_cost.roofline_pct(run, gdn_cost.GDN_FWD)
