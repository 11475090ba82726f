"""Device time of the differential attention kernels per traced step, in
milliseconds: every traced ``%mla_*`` call (forward and backward of the
windowed layer and of the causal ones). Prints the forward and the backward
calls apart, the shortest first (the windowed layer's), on a line before the
result: the events' shapes are alike, only their times tell them apart."""

from benchmark import diffattn_cost


def read(run):
    ms = diffattn_cost.kernel_ms_per_step(run)
    if ms is not None:
        for leg in (diffattn_cost.FWD, diffattn_cost.BWD):
            calls = ", ".join(f"{name} {each:.3f}" for name, each
                              in diffattn_cost.by_call(run, leg))
            print(f"diffattn: {leg}* calls, ms a call, shortest first: {calls}",
                  flush=True)
    return ms
