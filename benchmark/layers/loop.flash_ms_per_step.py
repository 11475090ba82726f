"""Device time of the flash attention kernels in the looped cell, per traced
step and chip, in milliseconds: every traced custom call named ``%flash_*``.
Eight layers run four times are 32 forwards and 32 backwards a step (one
``%flash_dkdv_dq*`` each at this shape), however few call sites a scanned
stack leaves in the program. A program without the kernels (a CPU rehearsal)
reports nothing."""

PREFIX = "%flash_"


def read(run):
    trace = run.get("trace")
    if not trace or not run.get("trace_steps"):
        return None
    seconds = sum(k["seconds"] for name, k in trace.get("kernels", {}).items()
                  if name.startswith(PREFIX))
    if not seconds:
        return None
    return 1e3 * seconds / run["device"]["count"] / run["trace_steps"]
