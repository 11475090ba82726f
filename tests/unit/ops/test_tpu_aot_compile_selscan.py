"""Mamba-1's selective scan kernels (``selscan_fwd``, ``selscan_bwd``) and
differential attention's one stacked call of the two-width kernels, compiled
for a described (not attached) TPU v5e at Phi-4-mini-flash's published widths
and the cell's 1 x 16,384 tokens, in the engine's fused step: no chip time,
nothing runs.

A file of its own beside ``test_tpu_aot_compile_kda.py`` (a worker's whole
share under ``--dist loadfile``), whose ``mla.step_of`` spells the step out:
the cell's configuration cut to THREE layers (the Mamba layer that hands on its
scan output, the full attention layer, the GMU that reads the first), compiled
ONCE for the module. The whole six-layer
cell by hand before a chip call: ``python tests/unit/ops/
test_tpu_aot_compile_selscan.py [seq] [none]`` (its temporaries beside 12 B a
parameter are in PERF.md; ``none``: every kept name dropped, the fallback's
question).
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

import test_tpu_aot_compile_mla as mla

ROOT = pathlib.Path(__file__).parents[3]
CELL = "train-phi4flash-1chip-sambay-seq16k"
INNER, STATES, HEADS, KV_HEADS, D = 5120, 16, 40, 20, 64
# what the chip holds at rest when the six-layer cell's step is first traced:
# 12 B for each of its 697,094,272 parameters
IN_USE = 8_365_131_264


def cell_config(layers=None, seq=None):
    """The cell's configuration, whole or cut to the published layers
    ``layers`` (indices into the kept six; a reader keeps its source)."""
    sys.path.insert(0, str(ROOT))
    bench = ROOT / "benchmark"
    workload = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    if layers is not None:
        at = {old: new for new, old in enumerate(layers)}
        specs = tuple(dataclasses.replace(
            cfg.layer_specs[i],
            kv_from=at.get(cfg.layer_specs[i].kv_from, -1),
            memory_from=at.get(cfg.layer_specs[i].memory_from, -1)) for i in layers)
        cfg = dataclasses.replace(cfg, num_hidden_layers=len(layers), layer_specs=specs)
    return cfg, workload["traffic"]["global_batch"], seq or workload["traffic"]["seq_len"]


def steer_to_the_chip(setattr_, in_use=IN_USE):
    mla.steer_to_the_chip(setattr_)
    from deepspeed_tpu.ops import remat
    setattr_(remat, "device_memory", lambda: (mla.V5E_BYTES_LIMIT, in_use))
    remat.forget_plans()


def kernels_of(compiled) -> dict:
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(compiled)]
    return {n: names.count(n) for n in sorted(set(names))}


@pytest.fixture(scope="module")
def step():
    """Published layers 16, 17 and 18 (Mamba-1 handing on its memory, full
    differential attention, the GMU) at the published widths and the cell's
    batch, traced and compiled for a described v5e, once."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    try:
        # three layers leave the described chip room: the scan is kept
        steer_to_the_chip(patch.setattr, in_use=4_500_000_000)
        cfg, rows, seq = cell_config((2, 3, 4))
        traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
        yield {"cfg": cfg, "rows": rows, "seq": seq, "traced": traced,
               "n_params": n_params, "compiled": traced.lower().compile()}
    finally:
        patch.undo()
        from deepspeed_tpu.ops import remat
        remat.forget_plans()
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_the_scan_runs_once_a_step_and_what_is_handed_on_is_kept_by_name(step):
    """Under whole-layer recomputation a Mamba-1 layer whose plan keeps
    ``ds.selscan.scan`` runs ``selscan_fwd`` once (its output and block states
    are handed to the recomputed layer's backward) and ``selscan_bwd`` once;
    the attention layer runs ONE ``mla_fwd`` (the stacked call) and one fused
    backward; the kept bytes hold the scan and the memory handed on."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.ops.selective_scan import BLOCK, scan_bytes
    kernels = kernels_of(step["compiled"])
    assert kernels["selscan_fwd"] == 1 and kernels["selscan_bwd"] == 1, kernels
    assert kernels["mla_fwd"] == 1 and kernels["mla_bwd"] == 1, kernels
    assert kernels["causal_conv_bwd"] == 1, kernels
    plan = next(iter(remat._PLANS.values()))
    assert remat.SELSCAN_SCAN in plan[0], plan
    rows, seq = step["rows"], step["seq"]
    scan = scan_bytes(rows, seq, INNER, STATES, 2)
    assert scan == rows * INNER * (seq * 2 + (seq // BLOCK) * STATES * 4)

    def kept_without(*names):
        return kept_residual_bytes(step["traced"].jaxpr, tuple(
            n for n in remat.KEPT_NAMES if n not in names))

    kept = kept_residual_bytes(step["traced"].jaxpr)
    assert kept - kept_without(remat.SELSCAN_SCAN) == scan
    assert kept - kept_without(remat.SHARED_MEMORY) == rows * seq * INNER * 2
    # the cut keeps no reader of the attention layer's keys and values: a
    # source nobody reads hands on (and names) nothing
    assert kept - kept_without(remat.SHARED_KV) == 0
    assert step["cfg"].shared_sources() == (None, None, 0)


def test_the_kernels_shapes_are_what_the_cost_files_read_and_the_scopes_stand(step):
    """``selscan_fwd`` writes ``y [rows, seq, channels / 128, 128]`` first and
    the block states in float32, ``selscan_bwd`` writes ``dx`` first
    (``benchmark/selscan_cost.py`` reads the first result); the attention call
    is the two-width kernel at 64 | 128 with 40 stacked query heads in groups
    of 2; ``ds.selscan.dt``, ``ds.diffattn.combine`` and ``ds.gmu.gate`` are on
    the ops around the kernels, closed before their call (the instructions
    keep their names); no array of ``seq x seq`` is in the program; the step's
    temporaries and 12 B a parameter fit the chip."""
    sys.path.insert(0, str(ROOT))
    from benchmark import selscan_cost
    rows, seq = step["rows"], step["seq"]
    calls = {line.split(" = ")[0].split("%")[-1].split(".")[0]: line
             for line in mla.custom_calls(step["compiled"])}
    y = f"bf16[{rows},{seq},{INNER // 128},128]"
    fwd = calls["selscan_fwd"].split("custom-call(")[0]
    assert fwd.index(y) < fwd.index(f"f32[{rows},{seq // 128},{STATES},{INNER // 128},128]")
    assert calls["selscan_bwd"].split("custom-call(")[0].index(y) > 0
    assert f"bf16[{rows * KV_HEADS},2,{seq},{2 * D}]" in calls["mla_fwd"].split(
        "custom-call(")[0]
    config = {"mamba_d_state": STATES}
    values = rows * seq * INNER
    states = 4 * rows * (seq // 128) * INNER * STATES
    for name, want in (("selscan_fwd", values * 8 + 4 * rows * seq * 32 + states),
                       ("selscan_bwd", values * 14 + 4 * rows * seq * 64 + states)):
        hlo = "%" + calls[name].split("%", 1)[1]
        assert selscan_cost.call_bytes(hlo, config) == want, hlo[:200]
    text = step["compiled"].as_text()
    assert not re.search(rf"\[(\d+,)*{seq},{seq}\]", text)
    for scope in ("ds.step.loss", "ds.selscan.dt", "ds.diffattn.combine", "ds.gmu.gate",
                  "ds.head.loss"):
        assert f"/{scope}/" in text, scope
    temporaries = step["compiled"].memory_analysis().temp_size_in_bytes
    assert temporaries + 12 * step["n_params"] <= mla.V5E_BYTES_LIMIT - 0.8e9


if __name__ == "__main__":
    import time
    from jax.experimental import topologies
    sys.path.insert(0, str(ROOT))
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    in_use = mla.V5E_BYTES_LIMIT if "none" in sys.argv[2:] else IN_USE
    steer_to_the_chip(pytest.MonkeyPatch().setattr, in_use=in_use)
    cfg, rows, seq = cell_config(None, int(sys.argv[1]) if len(sys.argv) > 1 else None)
    t0 = time.monotonic()
    traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
    compiled = traced.lower().compile()
    mem = compiled.memory_analysis()
    print(kernels_of(compiled))
    print("plan:", next(iter(remat._PLANS.values()), None))
    print(f"{rows} x {seq}: {n_params} parameters, 12 B each {12 * n_params / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, kept residuals "
          f"{kept_residual_bytes(traced.jaxpr) / 1e9:.3f} GB, together "
          f"{(12 * n_params + mem.temp_size_in_bytes) / 1e9:.3f} GB of "
          f"{mla.V5E_BYTES_LIMIT / 1e9:.3f} GB; arrays of seq x seq: "
          f"{len(re.findall(rf'[\\[,]{seq},{seq}\\]', compiled.as_text()))}; "
          f"{time.monotonic() - t0:.0f} s")
