"""Device time of the flash attention kernels per traced step and chip, in
milliseconds: every traced custom call named ``%flash_*`` (the forwards, a
recomputed forward, and the backward, be it one ``%flash_dkdv_dq*`` call or
the pair ``%flash_dq*`` + ``%flash_dkdv*``). The trace sums a kernel's
events over the chips, so the sum is divided by their number. Where
``kernel.flash_bwd_roofline`` credits each call it matches with one forward's
operations however many the call does, this is the time itself."""

PREFIX = "%flash_"


def read(run):
    trace = run.get("trace")
    if not trace or not run.get("trace_steps"):
        return None
    seconds = sum(k["seconds"] for name, k in trace.get("kernels", {}).items()
                  if name.startswith(PREFIX))
    if not seconds:
        return None     # a CPU rehearsal, a program without the kernels
    return 1e3 * seconds / run["device"]["count"] / run["trace_steps"]
