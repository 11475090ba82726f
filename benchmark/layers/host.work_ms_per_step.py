"""Host work of the training step loop per traced step: the self time of the
program's ``ds.train.*`` spans inside the traced window (profiler's clock),
``ds.train.loss_read`` left out because there the host waits for the
device."""

from benchmark import host_spans


def read(run):
    hs = host_spans.load(run)
    if not hs or not run.get("trace_steps"):
        return None
    lo, hi = hs["window"]
    work = [s["self"] for s in hs["spans"]
            if s["name"].startswith("ds.train.")
            and s["name"] != "ds.train.loss_read"
            and s["end"] >= lo and s["start"] <= hi]
    return sum(work) * 1e-6 / run["trace_steps"] if work else None
