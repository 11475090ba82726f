"""Kimi Delta Attention's kernels (``kda_chunk_fwd``, ``kda_chunk_bwd``)
compiled for a described (not attached) TPU v5e at Ling-3.0-flash's published
widths and the cell's 1 x 32,768 tokens, in the engine's fused step: no chip
time, nothing runs.

A file of its own beside ``test_tpu_aot_compile_mla.py`` (a worker's whole
share under ``--dist loadfile``), whose ``step_of`` spells the step out: two
layers of the cell's configuration (a KDA layer with the dense FFN and one with
experts), compiled ONCE for the module. The whole six-layer cell by hand
before a chip call: ``python tests/unit/ops/test_tpu_aot_compile_kda.py
[layers] [seq]`` (its temporaries beside 12 B a parameter are in PERF.md).
"""

import dataclasses
import importlib
import json
import pathlib
import re
import sys

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import test_tpu_aot_compile_mla as mla

ROOT = pathlib.Path(__file__).parents[3]
CELL = "train-ling3flash-1chip-kda-longseq"
HEADS, D, CHUNK = 32, 128, 64
# what the chip holds at rest when the six-layer cell's step is first traced:
# 12 B for each of its 767,009,056 parameters
IN_USE = 9_204_108_672
# the two-layer step's temporaries as the tree before PR 53 (XLA's norms and
# beta products around the kernels) compiled them for the described v5e, the
# ``step`` fixture's way (before PR 49, one head a grid step: 6,560,886,272)
PARENT_TEMPORARIES = 6_560_854_528


def cell_config(layers: int, seq=None):
    sys.path.insert(0, str(ROOT))
    bench = ROOT / "benchmark"
    workload = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    return (dataclasses.replace(cfg, num_hidden_layers=layers,
                                layer_specs=cfg.layer_specs[:layers]),
            workload["traffic"]["global_batch"], seq or workload["traffic"]["seq_len"])


def steer_to_the_chip(setattr_, in_use=IN_USE):
    mla.steer_to_the_chip(setattr_)
    from deepspeed_tpu.ops import remat
    setattr_(remat, "device_memory", lambda: (mla.V5E_BYTES_LIMIT, in_use))
    remat.forget_plans()


@pytest.fixture(scope="module")
def step():
    """Two layers at the published widths and the cell's batch, traced and
    compiled for a described v5e, once."""
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
        # two layers leave the described chip room: both keep their scan
        steer_to_the_chip(patch.setattr, in_use=3_000_000_000)
        cfg, rows, seq = cell_config(2)
        traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
        yield {"cfg": cfg, "rows": rows, "seq": seq, "traced": traced,
               "n_params": n_params, "compiled": traced.lower().compile()}
    finally:
        patch.undo()
        from deepspeed_tpu.ops import remat
        remat.forget_plans()
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_every_kda_layer_scans_once_a_step_and_keeps_what_its_backward_reads(step):
    """Under whole-layer recomputation a layer whose plan keeps ``ds.kda.scan``
    runs ``kda_chunk_fwd`` once (its output and chunk states are handed to the
    recomputed layer's backward) and ``kda_chunk_bwd`` once; the three
    convolutions run forward and backward; the kept bytes hold the scans."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.ops.kda import scan_bytes
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(step["compiled"])]
    kernels = {n: names.count(n) for n in set(names)}
    assert kernels["kda_chunk_fwd"] == 2 and kernels["kda_chunk_bwd"] == 2, kernels
    assert kernels["causal_conv_bwd"] == 6, kernels
    plan = next(iter(remat._PLANS.values()))
    assert all(remat.KDA_SCAN in names for names in plan), plan
    rows, seq = step["rows"], step["seq"]
    scans = 2 * scan_bytes(rows, seq, HEADS, D, D, CHUNK, 2)
    assert scans == 2 * (rows * seq * HEADS * D * 2 + rows * (seq // CHUNK) * HEADS * D * D * 4)
    kept = kept_residual_bytes(step["traced"].jaxpr)
    assert kept - kept_residual_bytes(
        step["traced"].jaxpr, tuple(n for n in remat.KEPT_NAMES if n != remat.KDA_SCAN)
    ) == scans


def test_the_kernels_shapes_are_what_the_cost_file_reads_and_the_scopes_stand(step):
    """``kda_chunk_fwd`` writes the mixer's output ``[rows, seq, heads *
    128]`` first and the chunk states ``[rows, seq / 64, 128, heads * 128]``
    in float32, ``kda_chunk_bwd`` writes ``dq`` first (``benchmark/kda_cost.py``
    reads the first result); ``ds.kda.gates`` is on the small ops around the
    kernels (beta, the lanes, the sums of what the kernels return for them),
    closed before their call (the instructions keep their names); no array of
    ``seq x seq`` is in the program."""
    sys.path.insert(0, str(ROOT))
    from benchmark import kda_cost
    rows, seq = step["rows"], step["seq"]
    calls = {line.split(" = ")[0].split("%")[-1].split(".")[0]: line
             for line in mla.custom_calls(step["compiled"])}
    o = f"bf16[{rows},{seq},{HEADS * D}]"
    fwd = calls["kda_chunk_fwd"].split("custom-call(")[0]
    assert fwd.index(o) < fwd.index(f"f32[{rows},{seq // CHUNK},{D},{HEADS * D}]")
    assert o in calls["kda_chunk_bwd"].split("custom-call(")[0]
    config = {"kda_chunk_size": CHUNK, "head_dim": D}
    for name in ("kda_chunk_fwd", "kda_chunk_bwd"):
        hlo = "%" + calls[name].split("%", 1)[1]
        cost = kda_cost.call_cost(hlo, config)
        assert cost is not None and cost["flops"] > 0 and cost["bytes"] > 0, hlo[:200]
    text = step["compiled"].as_text()
    assert not re.search(rf"\[(\d+,)*{seq},{seq}\]", text)
    for scope in ("ds.step.loss", "ds.kda.gates", "ds.moe.route", "ds.head.loss"):
        assert f"/{scope}/" in text, scope
    temporaries = step["compiled"].memory_analysis().temp_size_in_bytes
    assert temporaries + 12 * step["n_params"] <= mla.V5E_BYTES_LIMIT - 0.8e9


def test_no_pass_over_tokens_x_inner_is_left_under_the_mixers_scopes(step):
    """PR 53: the L2 norms of q and k, ``beta k``, ``beta v``, the gated
    output norm and the mean decay are made on the tiles the kernels hold. No
    XLA instruction under ``ds.kda.norm`` (nothing is, where the kernels run)
    or ``ds.kda.gates`` reads or writes an array of ``[rows, seq, heads *
    128]`` in any type, in any phase; what is left under ``ds.kda.gates`` is
    ``[rows, seq, heads]`` or a lane's row wide, plus the sum over the head
    blocks of beta's gradient (``[rows, heads / 4, seq, 128]`` float32: a
    quarter of one bf16 pass)."""
    rows, seq = step["rows"], step["seq"]
    text = step["compiled"].as_text()
    assert "/ds.kda.norm/" not in text
    scoped = [line for line in text.splitlines() if "/ds.kda.gates/" in line]
    assert scoped
    full = rf"\[{rows},{seq},{HEADS * D}\]|\[{rows},{seq},{HEADS},{D}\]"
    # results: none is tokens x inner; the largest is beta, a head a lane
    assert not [line[:160] for line in scoped if re.search(full, line.split(" = ")[1][:80])]
    largest = max(int(np.prod([int(n) for n in dims.split(",")]))
                  for line in scoped
                  for dims in re.findall(r" = (?:f32|bf16)\[([\d,]+)\]", line))
    assert largest == rows * seq * 128, largest
    # operands: no instruction under the scope reads one that is
    wide = set(re.findall(rf"(%[\w.\-]+) = \w+(?:{full})", text))
    assert len(wide) > 20
    reads = {name for line in scoped
             for name in re.findall(r"%[\w.\-]+", line.split(" = ", 1)[1].split(", metadata=")[0])}
    assert not reads & wide, sorted(reads & wide)[:5]
    # what the backward kernel hands the scope: beta's gradient, a block of
    # heads a slab
    bwd = next(line for line in mla.custom_calls(step["compiled"]) if "kda_chunk_bwd" in line)
    assert f"f32[{rows},{HEADS // 4},{seq},128]" in bwd.split("custom-call(")[0]


def test_a_block_of_heads_a_grid_step_moves_neither_the_cost_nor_the_memory(step):
    """PR 49: a grid step of both kernels is a chunk of FOUR heads at the
    cell's 32 heads of 128 (``kernel_dispatch.choose_kda_heads``), 2,048 steps
    a call where 8,192 were; what ``benchmark/kda_cost.py`` reads of the two
    lines (the first result's shape) gives the operations and bytes it gave
    before, and the step's temporaries for the described v5e are no larger
    than what the tree before PR 53 compiled to the same way (the block
    changes VMEM, not HBM; the norms' and products' arrays left: 6.11 GB
    where 6.56 were)."""
    from jax._src import core
    sys.path.insert(0, str(ROOT))
    from benchmark import kda_cost
    from deepspeed_tpu.ops import kda
    rows, seq = step["rows"], step["seq"]
    chunks = seq // CHUNK
    assert kda.grid_of(rows, seq, HEADS, D, CHUNK, 2) == (4, rows * HEADS // 4 * chunks)
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.setdefault(eqn.params["name"], set()).add(
                    tuple(eqn.params["grid_mapping"].grid))
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(step["traced"].jaxpr.jaxpr)
    assert grids["kda_chunk_fwd"] == grids["kda_chunk_bwd"] == {(rows, HEADS // 4, chunks)}
    calls = {line.split(" = ")[0].split("%")[-1].split(".")[0]: "%" + line.split("%", 1)[1]
             for line in mla.custom_calls(step["compiled"])}
    config = {"kda_chunk_size": CHUNK, "head_dim": D}
    values, betas = rows * seq * HEADS * D, rows * seq * HEADS
    states = 4.0 * rows * chunks * HEADS * D * D
    fwd = rows * seq * HEADS * (2.0 * CHUNK * 5 * D + 6.0 * D * D)
    assert kda_cost.call_cost(calls["kda_chunk_fwd"], config) == {
        "flops": fwd, "bytes": 2 * (5 * values + betas) + states}
    assert kda_cost.call_cost(calls["kda_chunk_bwd"], config) == {
        "flops": 2.0 * fwd, "bytes": 2 * (9 * values + 2 * betas) + states}
    temporaries = step["compiled"].memory_analysis().temp_size_in_bytes
    assert temporaries <= PARENT_TEMPORARIES, temporaries


if __name__ == "__main__":
    # the whole cell by hand:
    # python tests/unit/ops/test_tpu_aot_compile_kda.py [layers] [seq] [names]
    # (names "none": every candidate dropped, the fallback's question)
    import time
    from jax.experimental import topologies
    sys.path.insert(0, str(ROOT))
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    in_use = mla.V5E_BYTES_LIMIT if "none" in sys.argv[3:] else IN_USE
    steer_to_the_chip(pytest.MonkeyPatch().setattr, in_use=in_use)
    cfg, rows, seq = cell_config(int(sys.argv[1]) if len(sys.argv) > 1 else 6,
                                 int(sys.argv[2]) if len(sys.argv) > 2 else None)
    t0 = time.monotonic()
    traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
    compiled = traced.lower().compile()
    mem = compiled.memory_analysis()
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(compiled)]
    print({n: names.count(n) for n in sorted(set(names))})
    print("plan:", next(iter(remat._PLANS.values()), None))
    print(f"{rows} x {seq}: {n_params} parameters, 12 B each {12 * n_params / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, kept residuals "
          f"{kept_residual_bytes(traced.jaxpr) / 1e9:.3f} GB, together "
          f"{(12 * n_params + mem.temp_size_in_bytes) / 1e9:.3f} GB of "
          f"{mla.V5E_BYTES_LIMIT / 1e9:.3f} GB; arrays of seq x seq: "
          f"{len(re.findall(rf'[\\[,]{seq},{seq}\\]', compiled.as_text()))}; "
          f"{time.monotonic() - t0:.0f} s")
