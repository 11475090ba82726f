"""Device time of the gated short-convolution kernels (the traced
``%short_conv_fwd*`` and ``%short_conv_bwd*`` calls, a recomputed forward
included) per traced step, in milliseconds."""

from benchmark import conv_cost


def read(run):
    conv = conv_cost.traced_conv(run)
    if conv is None or not run.get("trace_steps"):
        return None
    return 1e3 * conv["seconds"] / run["trace_steps"]
