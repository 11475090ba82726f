"""Device time of what XLA does around the Gated DeltaNet kernels, per traced
step and chip, every phase, in milliseconds: the ops under the program's
``ds.gdn.gates`` scope (``beta``, the log decay ``g`` at ``[tokens, heads]``,
their lane layout, the mean decay for ``gdn_stats`` and the gradients' sums),
``ds.gdn.split`` (the slices of the fused projection's and the convolution's
outputs into q, k, v and z) and ``ds.gdn.norm`` (the norms where the kernels
do not run), by ``scope_time``'s table of the innermost ``ds.*`` scope. The
projections and the convolution kernel are not under these scopes. A program
without the scopes reports nothing."""

from benchmark import scope_time

SCOPES = ("ds.gdn.gates", "ds.gdn.split", "ds.gdn.norm")


def read(run):
    table = scope_time.load(run)
    if table is None:
        return None
    scoped = sum(ms for (scope, _), ms in table["ds_ms"].items() if scope in SCOPES)
    return scoped or None
