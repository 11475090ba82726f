"""Gated DeltaNet's kernels (``gdn_chunk_fwd``, ``gdn_chunk_bwd``) and the
gated softmax attention at head 256 compiled for a described (not attached)
TPU v5e at Qwen3-Next's published widths, in the engine's fused step: no chip
time, nothing runs.

A file of its own beside ``test_tpu_aot_compile_kda.py`` (a worker's whole
share under ``--dist loadfile``): the cell's LAST two layers (a GDN layer and
the attention layer, each with its expert block) at 6,144 tokens, compiled
ONCE for the module. The whole four-layer cell by hand
before a chip call, which is the evidence for the sequence the cell runs:
``python tests/unit/ops/test_tpu_aot_compile_gdn.py [seq] [none]`` prints the
plan (``none`` drops every kept name; PERF.md section 4 has both lengths).
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
CELL = "train-qwen3next-1chip-gdn-longseq"
K_HEADS, V_HEADS, D, CHUNK = 16, 32, 128, 64
# what the chip holds at rest when the four-layer cell's step is first traced:
# 12 B for each of its 625,667,136 parameters
IN_USE = 7_508_005_632


def cell_config(first: int, layers: int, seq=None):
    sys.path.insert(0, str(ROOT))
    bench = ROOT / "benchmark"
    workload = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    return (dataclasses.replace(cfg, num_hidden_layers=layers,
                                layer_specs=cfg.layer_specs[first:first + layers]),
            workload["traffic"]["global_batch"], seq or workload["traffic"]["seq_len"])


@pytest.fixture(scope="module")
def step():
    """Published layers 2 and 3 at the published widths and 6,144 tokens (no width
    of the model is 6,144: an array of seq x seq is told from one of seq x
    channels),
    traced and compiled for a described v5e, once."""
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
        # two layers leave the described chip room: the GDN layer keeps its scan
        kda_aot.steer_to_the_chip(patch.setattr, in_use=3_000_000_000)
        cfg, rows, seq = cell_config(2, 2, 6144)
        traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
        yield {"cfg": cfg, "rows": rows, "seq": seq, "traced": traced,
               "n_params": n_params, "compiled": traced.lower().compile()}
    finally:
        patch.undo()
        from deepspeed_tpu.ops import remat
        remat.forget_plans()
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_the_gdn_layer_scans_once_and_head_256_goes_through_the_flash_kernels(step):
    """Under whole-layer recomputation the GDN layer whose plan keeps
    ``ds.gdn.scan`` runs ``gdn_chunk_fwd`` once and ``gdn_chunk_bwd`` once, its
    ONE convolution forward and backward; the attention layer's head of 256
    resolves to the flash kernels (one forward a step); the kept bytes hold
    the scan."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.ops.gdn import scan_bytes
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(step["compiled"])]
    kernels = {n: names.count(n) for n in set(names)}
    assert kernels["gdn_chunk_fwd"] == 1 and kernels["gdn_chunk_bwd"] == 1, kernels
    assert kernels["causal_conv_bwd"] == 1, kernels
    assert kernels["flash_fwd"] == 1, kernels
    # one backward call, the fused kernel, and neither kernel of the pair
    assert kernels["flash_dkdv_dq"] == 1, kernels
    assert not {"flash_dq", "flash_dkdv"} & set(kernels), kernels
    plan = next(iter(remat._PLANS.values()))
    assert remat.GDN_SCAN in plan[0], plan
    rows, seq = step["rows"], step["seq"]
    scan = scan_bytes(rows, seq, V_HEADS, D, D, CHUNK, 2)
    assert scan == rows * seq * V_HEADS * D * 2 + rows * (seq // CHUNK) * V_HEADS * D * D * 4
    kept = kept_residual_bytes(step["traced"].jaxpr)
    assert kept - kept_residual_bytes(
        step["traced"].jaxpr, tuple(n for n in remat.KEPT_NAMES if n != remat.GDN_SCAN)
    ) == scan


def test_the_gate_is_tokens_x_heads_and_the_cost_file_reads_the_kernels_shapes(step):
    """``gdn_chunk_fwd`` writes the mixer's output ``[rows, seq, 32 * 128]``
    first, ``gdn_chunk_bwd`` writes ``dq [rows, seq, 16 * 128]`` first
    (``benchmark/gdn_cost.py`` reads the first result and the file's
    ``linear_*`` keys, never ``head_dim``); the kernels read ``g`` and ``beta``
    a head a lane (``f32[rows, seq, 128]``); no float32 array of ``[tokens,
    heads x 128]`` is under ``ds.gdn.*`` in any phase, and no array of ``seq x
    seq`` is in the program."""
    sys.path.insert(0, str(ROOT))
    from benchmark import gdn_cost
    rows, seq = step["rows"], step["seq"]
    calls = {line.split(" = ")[0].split("%")[-1].split(".")[0]: line
             for line in mla.custom_calls(step["compiled"])}
    fwd, bwd = (calls[n].split("custom-call(") for n in ("gdn_chunk_fwd", "gdn_chunk_bwd"))
    assert fwd[0].index(f"bf16[{rows},{seq},{V_HEADS * D}]") < fwd[0].index(
        f"f32[{rows},{seq // CHUNK},{D},{V_HEADS * D}]")
    assert bwd[0].lstrip().split(" = ")[1].startswith(f"(bf16[{rows},{seq},{K_HEADS * D}]")
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "qwen3-next-80b-a3b-ep16-train1.json").read_text())
    assert config["head_dim"] == 256
    for name in ("gdn_chunk_fwd", "gdn_chunk_bwd"):
        hlo = "%" + calls[name].split("%", 1)[1]
        cost = gdn_cost.call_cost(hlo, config)
        assert cost is not None and cost["flops"] > 0 and cost["bytes"] > 0, hlo[:200]
    text = step["compiled"].as_text()
    assert not re.search(rf"\[(\d+,)*{seq},{seq}\]", text)
    scoped = [line for line in text.splitlines() if "/ds.gdn." in line]
    assert any("/ds.gdn.gates/" in line for line in scoped)
    wide = rf"f32\[{rows},{seq},({K_HEADS * D}|{V_HEADS * D})\]|f32\[{rows},{seq},\d+,{D}\]"
    assert not [line[:160] for line in scoped if re.search(wide, line.split(" = ")[1][:80])]
    for scope in ("ds.step.loss", "ds.gdn.gates", "ds.gdn.split", "ds.attn.gate",
                  "ds.moe.route", "ds.moe.shared", "ds.head.loss"):
        assert f"/{scope}/" in text, scope


def plan_of(seq: int, drop_names: bool) -> dict:
    """The four-layer cell's step at ``seq`` tokens compiled for the described
    v5e: what it plans beside 12 B a parameter."""
    from jax.experimental import topologies
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    kda_aot.steer_to_the_chip(pytest.MonkeyPatch().setattr,
                              in_use=mla.V5E_BYTES_LIMIT if drop_names else IN_USE)
    cfg, rows, seq = cell_config(0, 4, seq)
    traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
    compiled = traced.lower().compile()
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(compiled)]
    return {"rows": rows, "seq": seq, "n_params": n_params,
            "kernels": {n: names.count(n) for n in sorted(set(names))},
            "plan": next(iter(remat._PLANS.values()), None),
            "temporaries": compiled.memory_analysis().temp_size_in_bytes,
            "kept": kept_residual_bytes(traced.jaxpr),
            "seq_x_seq": len(re.findall(rf"[\[,]{seq},{seq}\]", compiled.as_text()))}


if __name__ == "__main__":
    # the whole cell by hand:
    # python tests/unit/ops/test_tpu_aot_compile_gdn.py [seq ...] [none]
    import time
    sys.path.insert(0, str(ROOT))
    jax.config.update("jax_enable_compilation_cache", False)
    drop = "none" in sys.argv[1:]
    for seq in [int(a) for a in sys.argv[1:] if a != "none"] or [32768, 16384]:
        t0 = time.monotonic()
        p = plan_of(seq, drop)
        print(p["kernels"])
        print("plan:", p["plan"])
        print(f"{p['rows']} x {p['seq']}: {p['n_params']} parameters, 12 B each "
              f"{12 * p['n_params'] / 1e9:.3f} GB, temporaries {p['temporaries'] / 1e9:.3f} GB, "
              f"kept residuals {p['kept'] / 1e9:.3f} GB, together "
              f"{(12 * p['n_params'] + p['temporaries']) / 1e9:.3f} GB of "
              f"{mla.V5E_BYTES_LIMIT / 1e9:.3f} GB; arrays of seq x seq: {p['seq_x_seq']}; "
              f"{time.monotonic() - t0:.0f} s", flush=True)
