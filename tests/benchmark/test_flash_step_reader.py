"""``flash.kernel_ms_per_step``: its manifest entry and its reader on
hand-built runs, the parent's kernels (``%flash_dq*`` + ``%flash_dkdv*``)
and the fused backward's (``%flash_dkdv_dq*``) alike. All on the CPU; no
number here is a measurement."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import manifest_checks  # noqa: E402  (beside this file)
from benchmark.run import cell_metrics, load_manifest, load_module  # noqa: E402

METRIC = "flash.kernel_ms_per_step"
# the cells whose own tests let a metric be added to them: the Granite cell's
# test pins the list of its per-layer metrics (ROADMAP D13)
CELLS = ["train-zero3-seq4k", "train-olmoe-1chip-seq4k", "train-lfm2moe-1chip-seq8k"]
FWD = ("%flash_fwd.3 = (bf16[32,4,8192,64]{3,2,1,0:T(8,128)(2,1)}, "
       "f32[32,4,8192,1]{3,2,1,0:T(8,128)}) custom-call(%bitcast.25, %copy")
DKDV_DQ = ("%flash_dkdv_dq.1 = (bf16[32,8192,64]{2,1,0:T(8,128)(2,1)}, "
           "bf16[32,8192,64]{2,1,0:T(8,128)(2,1)}, bf16[32,4,8192,64]{3,2,1,0")


def read(run):
    return load_module("layers", METRIC).read(run)


def kernel(count, ms):
    return {"count": count, "seconds": count * ms * 1e-3, "hlo": FWD[:160]}


def test_the_metric_is_an_appended_entry_of_the_kernel_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    entry = admitted["per_layer"][-1]
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "kernel",
                     "moves": "train_tok_s", "workloads": CELLS}
    manifest_checks.check_admitted(admitted)
    m = load_manifest()
    for cell in CELLS:
        assert METRIC in {x["name"] for x in cell_metrics(m, cell, "per_layer")}


@pytest.mark.parametrize("backward", ["pair", "fused"])
def test_the_reader_sums_every_flash_call_of_a_step(backward):
    """Two forwards a step (one recomputed) and the backward, four traced
    steps; other kernels' time is not read."""
    kernels = {"%flash_fwd.1": kernel(4, 20.0), "%flash_fwd.2": kernel(4, 19.0),
               "%folded_flash_fwd.2": kernel(4, 7.0), "%short_conv_fwd.3": kernel(8, 1.0),
               "%shard_map.7": kernel(4, 9.0)}
    if backward == "pair":
        kernels["%flash_dq.1"] = kernel(4, 24.0)
        kernels["%flash_dkdv.1"] = kernel(4, 27.0)
        want = 20.0 + 19.0 + 24.0 + 27.0
    else:
        kernels["%flash_dkdv_dq.1"] = dict(kernel(4, 36.0), hlo=DKDV_DQ[:160])
        want = 20.0 + 19.0 + 36.0
    run = {"trace": {"kernels": kernels}, "trace_steps": 4,
           "device": {"kind": "TPU v5 lite", "count": 1}}
    assert read(run) == pytest.approx(want)
    # four chips: the trace sums a kernel's events over them
    four = {name: dict(k, count=4 * k["count"], seconds=4 * k["seconds"])
            for name, k in kernels.items()}
    run4 = dict(run, trace={"kernels": four}, device={"kind": "TPU v5 lite", "count": 4})
    assert read(run4) == pytest.approx(want)


def test_the_reader_reports_nothing_where_there_is_nothing_to_read():
    device = {"kind": "cpu", "count": 1}
    assert read({"device": device}) is None                       # untraced
    assert read({"trace": {"kernels": {}}, "trace_steps": 2, "device": device}) is None
    assert read({"trace": {"kernels": {"%ssd_chunk_fwd.1": kernel(2, 1.0)}},
                 "trace_steps": 2, "device": device}) is None     # no flash call
    assert read({"trace": {"kernels": {"%flash_fwd.1": kernel(2, 1.0)}},
                 "device": device}) is None                       # no step count
