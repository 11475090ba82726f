#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses a machine with no TPU, sets up the cell (engine or trainer, weights
from ``--seed``, warm-up, correctness check against the plain reference),
measures for ``--seconds`` and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``. Everything else is on
earlier lines. All work, JAX included, runs in this one process.

Driven by data: the cell is ``workloads/<name>.json``, its configuration
``configs/<config>.json``, its runner ``runners/<runner>.py`` and each
per-layer metric ``layers/<metric>.py``; adding one adds a file.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` loaded by path (metric names hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest() -> dict:
    """``BENCHMARK.json`` and, after its entries, those of ``queued.json``:
    cells that are built and rehearsed but not admitted, so that one runs in
    full when named. An entry ``BENCHMARK.json`` has by name wins."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    queued = load_json("queued.json")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"] for e in manifest[group]}
        manifest[group] += [e for e in queued[group] if e["name"] not in have]
    return manifest


def cell_metrics(manifest: dict, cell: str, group: str) -> list:
    """The manifest's ``group`` metrics that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: tiny sizes, any platform; the line "
                         "names the platform, so it is never a result")
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        cell["traffic"].update(cell.get("rehearse", {}))

    from benchmark import device as dev
    device = dev.require_device(cell["chips"], args.rehearse)
    compiles = dev.CompileCounter()
    from deepspeed_tpu.runtime.compiler import configure_compile_cache
    log(f"device: {device}; compile cache at {configure_compile_cache()}")

    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    runner = load_module("runners", cell["runner"])
    run = runner.run(cell=cell, config=config, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     rehearse=args.rehearse, t_start=T_START, device=device,
                     compiles=compiles,
                     out_dir=out_dir, log=log)
    run["device"] = device
    run["config"] = config

    device["memory_peak_bytes"] = dev.memory_peak_bytes(cell["chips"])
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": {}, "device": device}
    if args.trace:
        from benchmark import reduce_trace
        run["trace"] = reduce_trace.reduce_dir(out_dir, cell["chips"], log,
                                                args.rehearse)
        # the traced interval by the host's clock where the runner gives it:
        # a stall at either edge leaves no device event to mark its end
        run["trace"]["window_s"] = max(run["trace"]["window_s"],
                                       run.get("trace_window_s") or 0.0)
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = run["trace"]["breakdown"]
        for m in cell_metrics(manifest, args.workload, "per_layer"):
            value = load_module("layers", m["name"]).read(run)
            if value is not None:   # a reader that finds nothing reports nothing
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(manifest, args.workload, "end_to_end"):
            line["metrics"][m["name"]] = {"value": run["end_to_end"][m["name"]],
                                          "unit": m["unit"]}
    shutil.rmtree(out_dir, ignore_errors=True)
    log(f"notes: {json.dumps(run.get('notes', {}))}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
