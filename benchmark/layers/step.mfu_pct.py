"""Model FLOP/s utilization: measured tokens per second times the
benchmark's FLOPs per token (forward and backward, recomputation not
counted) over chips times the published bf16 peak. Read in the traced run,
whose first steps carry the profiler."""

from benchmark import device


def read(run):
    if ("train_flops_per_token" not in run
            or run["device"].get("platform", "tpu") != "tpu"):
        return None     # a rehearsal on a CPU has no utilization
    peak = device.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * run["end_to_end"]["train_tok_s"]
            * run["train_flops_per_token"] / (run["chips"] * peak))
