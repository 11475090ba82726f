"""Ouro's looped stack compiled for a described (not attached) TPU v5e at the
published widths, in the engine's fused step: no chip time, nothing runs.

A file of its own beside ``test_tpu_aot_compile_gdn.py`` (a worker's whole
share under ``--dist loadfile``): TWO of the cell's layers run four times at
4,096 tokens, scanned as the cell runs them, compiled ONCE for the module, and
the same two layers at ``total_ut_steps`` 1 traced for their plan. The whole
eight-layer cell by hand before a chip call, which is the evidence for the
depth and the sequence the cell runs:
``python tests/unit/ops/test_tpu_aot_compile_ouro.py [seq] [none] [unrolled]``
prints the plan (``none`` drops every kept name; PERF.md section 4 has it).
"""

import dataclasses
import importlib
import json
import pathlib
import re
import sys

import jax
import pytest
from jax.sharding import SingleDeviceSharding

import test_tpu_aot_compile_kda as kda_aot
import test_tpu_aot_compile_mla as mla

ROOT = pathlib.Path(__file__).parents[3]
CELL = "train-ouro-1chip-loop4-seq16k"
# what the chip holds at rest when the eight-layer cell's step is first traced:
# 12 B for each of its 612,438,017 parameters
IN_USE = 7_349_256_204


def cell_config(layers: int, seq=None, **over):
    sys.path.insert(0, str(ROOT))
    bench = ROOT / "benchmark"
    workload = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    return (dataclasses.replace(cfg, num_hidden_layers=layers, **over),
            workload["traffic"]["global_batch"], seq or workload["traffic"]["seq_len"])


@pytest.fixture(scope="module")
def step():
    """Two layers at the published widths run four times at 4,096 tokens,
    traced and compiled for a described v5e, once; and the plan of the same
    layers run once."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.ops import remat
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    try:
        # so much in use that the looped plan keeps a name and not all
        kda_aot.steer_to_the_chip(patch.setattr, in_use=10_200_000_000)
        chip = SingleDeviceSharding(topo.devices[0])
        cfg, rows, seq = cell_config(2, 4096)
        traced, n_params = mla.step_of(cfg, rows, seq, chip)
        looped = dict(remat._PLANS)
        remat.forget_plans()
        mla.step_of(dataclasses.replace(cfg, total_ut_steps=1), rows, seq, chip)
        yield {"cfg": cfg, "rows": rows, "seq": seq, "traced": traced,
               "n_params": n_params, "compiled": traced.lower().compile(),
               "plan": next(iter(looped.values())),
               "plan_once": next(iter(remat._PLANS.values()))}
    finally:
        patch.undo()
        remat.forget_plans()
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_the_plan_counts_an_application_a_pass_and_layer(step):
    """``T x N`` rows, each with the attention kernel's residuals; the same
    layers run ONCE are planned ``N`` rows and, with a quarter of the inputs
    and residuals to hold, keep more names."""
    from deepspeed_tpu.ops import remat
    cfg = step["cfg"]
    assert len(step["plan"]) == cfg.total_ut_steps * cfg.num_hidden_layers == 8
    assert len(step["plan_once"]) == cfg.num_hidden_layers
    assert all(set(remat.RESIDUAL_NAMES) <= set(row) for row in step["plan"])
    # a scanned body: every application keeps the same names
    assert len(set(step["plan"])) == 1 and len(set(step["plan_once"])) == 1
    kept = lambda plan: [n for n in plan[0] if n in remat.CANDIDATE_NAMES]  # noqa: E731
    assert len(kept(step["plan_once"])) > len(kept(step["plan"])) >= 0
    assert kept(step["plan_once"])[:len(kept(step["plan"]))] == kept(step["plan"])


def test_one_body_holds_the_kernels_and_every_pass_has_its_scope(step):
    """A scanned stack leaves ONE forward and ONE backward flash call site a
    pass in the program (the scan's body), at head 128 and group 1, which
    ``benchmark/flash_cost.py`` reads off the call; the passes' scopes, the
    exits' and the head's are on the ops' paths; the head's sweep takes the
    four streams' rows at once; no array of seq x seq."""
    sys.path.insert(0, str(ROOT))
    from benchmark import flash_cost
    cfg, rows, seq = step["cfg"], step["rows"], step["seq"]
    calls = mla.custom_calls(step["compiled"])
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0] for line in calls]
    kernels = {n: names.count(n) for n in set(names)}
    # forward, recomputed forward? no: the kernel's output is kept by name
    assert kernels["flash_fwd"] == cfg.total_ut_steps, kernels
    assert kernels["flash_dkdv_dq"] == cfg.total_ut_steps, kernels
    assert not {"flash_dq", "flash_dkdv"} & set(kernels), kernels
    config = json.loads((ROOT / "benchmark" / "configs" / "ouro-2.6b-train1.json").read_text())
    fwd = next("%" + line.split("%", 1)[1] for line in calls if "%flash_fwd" in line)
    heads, d = cfg.num_attention_heads, cfg.head_dim_
    assert flash_cost.call_flops(fwd, config) == 4.0 * rows * heads * d * (seq + 1) / 2 * seq
    text = step["compiled"].as_text()
    for scope in ("ds.step.loss", "ds.head.loss", "ds.loop.exit",
                  *(f"ds.loop.pass{t}" for t in range(cfg.total_ut_steps))):
        assert f"/{scope}/" in text, scope
    assert f"ds.loop.pass{cfg.total_ut_steps}" not in text
    assert not re.search(rf"[\[,]{seq},{seq}\]", text)
    from deepspeed_tpu.ops.chunked_ce import seq_chunk
    sc = seq_chunk(seq, cfg.ce_chunk_size // cfg.total_ut_steps, cfg.vocab_size)
    assert f"f32[{rows * cfg.total_ut_steps * sc},{cfg.vocab_size}]" in text


def plan_of(seq: int, drop_names: bool, layers: int = 8, **over) -> dict:
    """The cell's step at ``seq`` tokens compiled for the described v5e: what
    it plans beside 12 B a parameter."""
    from jax.experimental import topologies
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    kda_aot.steer_to_the_chip(pytest.MonkeyPatch().setattr,
                              in_use=mla.V5E_BYTES_LIMIT if drop_names else IN_USE)
    cfg, rows, seq = cell_config(layers, seq, **over)
    traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
    compiled = traced.lower().compile()
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(compiled)]
    return {"rows": rows, "seq": seq, "n_params": n_params,
            "kernels": {n: names.count(n) for n in sorted(set(names))},
            "plan": next(iter(remat._PLANS.values()), None),
            # the compiler's own peak, the donated state included (the
            # analysis's ``temp_size`` adds up the passes' loops' buffers as
            # if none shared an address: 14.1 GB for a step that peaks at 15.8)
            "temporaries": (compiled.memory_analysis().peak_memory_in_bytes
                            - 12 * n_params),
            "kept": kept_residual_bytes(traced.jaxpr),
            "seq_x_seq": len(re.findall(rf"[\[,]{seq},{seq}\]", compiled.as_text()))}


if __name__ == "__main__":
    # the whole cell by hand:
    # python tests/unit/ops/test_tpu_aot_compile_ouro.py [seq ...] [none] [unrolled] [layers=6]
    import time
    sys.path.insert(0, str(ROOT))
    jax.config.update("jax_enable_compilation_cache", False)
    words = sys.argv[1:]
    over = {"scan_layers": False} if "unrolled" in words else {}
    layers = next((int(w.split("=")[1]) for w in words if w.startswith("layers=")), 8)
    for seq in [int(a) for a in words if a.isdigit()] or [16384]:
        t0 = time.monotonic()
        p = plan_of(seq, "none" in words, layers, **over)
        print(p["kernels"])
        print("plan:", len(p["plan"] or ()), "applications;", (p["plan"] or [None])[0])
        print(f"{p['rows']} x {p['seq']}: {p['n_params']} parameters, 12 B each "
              f"{12 * p['n_params'] / 1e9:.3f} GB, temporaries {p['temporaries'] / 1e9:.3f} GB, "
              f"kept residuals {p['kept'] / 1e9:.3f} GB, together "
              f"{(12 * p['n_params'] + p['temporaries']) / 1e9:.3f} GB of "
              f"{mla.V5E_BYTES_LIMIT / 1e9:.3f} GB; arrays of seq x seq: {p['seq_x_seq']}; "
              f"{time.monotonic() - t0:.0f} s", flush=True)
