"""Device time of what XLA does around the Kimi Delta Attention kernels, per
traced step and chip, every phase, in milliseconds: the ops under the
program's ``ds.kda.gates`` scope (``beta``, the beta-scaled operands ``beta
k`` and ``beta v``, and the pass that makes the mean decay for ``kda_stats``;
the gate ``g`` and its running sum are made inside the kernels) and
``ds.kda.norm`` (the L2 norms of q and k and the gated per-head output norm):
float32 passes over ``[tokens, heads * 128]``, by ``scope_time``'s table of
the innermost ``ds.*`` scope. A program without the scopes reports nothing."""

from benchmark import scope_time

SCOPES = ("ds.kda.gates", "ds.kda.norm")


def read(run):
    table = scope_time.load(run)
    if table is None:
        return None
    scoped = sum(ms for (scope, _), ms in table["ds_ms"].items() if scope in SCOPES)
    return scoped or None
