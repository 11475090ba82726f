"""Learned sparse attention's kernels (``dsa_index``, ``dsa_fwd`` and the
one-walk backward ``dsa_bwd``, which the cell's shape resolves to: a KV head's
float32 dK and dV of all 32,768 keys in VMEM) compiled for a described (not
attached) TPU v5e at Keye-VL-2.0-30B-A3B's published widths and the cell's
1 x 32,768 tokens, in the engine's fused step: no chip time, nothing runs.

A file of its own beside ``test_tpu_aot_compile_mla.py`` (a worker's whole
share under ``--dist loadfile``), whose ``step_of`` spells the step out: two
layers of the cell's configuration, compiled ONCE for the module. The whole
six-layer cell by hand before a chip call:
``python tests/unit/ops/test_tpu_aot_compile_dsa.py`` (its temporaries beside
12 B a parameter are in PERF.md).
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
CELL = "train-keyevl2-1chip-dsa-seq32k"
HEADS, KV, D, HI, DI, TOPK = 32, 4, 128, 16, 64, 2048
# what the chip reported in use when the six-layer cell's step was first
# traced (my chip run, PR 45): 12 B for each of its 659,190,016 parameters
IN_USE = 7_913_383_936


def cell_config(layers: int):
    sys.path.insert(0, str(ROOT))
    bench = ROOT / "benchmark"
    workload = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    return (dataclasses.replace(cfg, num_hidden_layers=layers),
            workload["traffic"]["global_batch"], workload["traffic"]["seq_len"])


def steer_to_the_chip(setattr_):
    mla.steer_to_the_chip(setattr_)
    from deepspeed_tpu.ops import remat
    setattr_(remat, "device_memory", lambda: (mla.V5E_BYTES_LIMIT, IN_USE))
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
        steer_to_the_chip(patch.setattr)
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


def test_every_layer_chooses_and_attends_once_a_step(step):
    """Under whole-layer recomputation each layer's ``dsa_index`` and
    ``dsa_fwd`` run once (the thresholds, the output and the log-sum-exp are
    kept by name for the recomputed layer's backward) and its backward is the
    one walk ``dsa_bwd``, no pair; with the described chip's room both layers
    keep their mask
    (``ds.dsa.mask``, first in the walk: no ``dsa_mask`` call makes it
    again) and the kept bytes hold it; no ``flash_*`` call is in the
    program."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in mla.custom_calls(step["compiled"])]
    kernels = {n: names.count(n) for n in set(names)}
    for name in ("dsa_index", "dsa_fwd", "dsa_bwd"):
        assert kernels.pop(name) == 2, (name, names)
    assert not any(n.startswith("dsa_bwd_") for n in kernels), names
    # two layers' shares, either branch of their cond: the program's own
    assert (kernels.pop("moe_gmm_rows"), kernels.pop("moe_gmm_d_rows"),
            kernels.pop("moe_gmm_weights")) == (4 * (3 + 3), 4 * 3, 4 * 3), names
    assert all(n.startswith("moe_rows_to_tokens") for n in kernels), names
    rows, seq = step["rows"], step["seq"]
    plan = next(iter(remat._PLANS.values()))
    assert [names[2] for names in plan] == [remat.DSA_MASK] * 2, plan
    # o, lse; a layer that keeps its mask has no reader for tau and tie
    always = 2 * rows * seq * HEADS * (D * 2 + 4)
    masks = 2 * rows * seq * seq // 8
    kept = kept_residual_bytes(step["traced"].jaxpr)
    assert kept >= always + masks
    assert kept - kept_residual_bytes(
        step["traced"].jaxpr, tuple(n for n in remat.KEPT_NAMES if n != remat.DSA_MASK)
    ) == masks


def kernel_operands(closed_jaxpr):
    """{kernel name: the shapes of its operands, as ``bf16[1,4,...]``} of a
    traced program's Pallas calls."""
    from jax._src import core
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = [v.aval.str_short(short_dtypes=True)
                                             for v in eqn.invars]
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return found


def test_the_attention_kernels_take_the_mask_and_none_of_the_indexers_operands(step):
    """The indexer's scores are made in ``dsa_index`` alone: ``dsa_fwd`` takes
    q, k, v and the words ``i32[rows, key tiles, seq / 32, 512]``, the one
    walk its table of live tiles (two ``i32[4160]`` a KV head at its query
    tile of 256), then those with dO, the log-sum-exp and delta laid out by
    that tile: no qi ``bf16[rows, 16, seq, 64]``, ki, w or threshold; and in
    the compiled step ``dsa_index`` writes the words after its first results
    (``tau``, ``tie``) and ``dsa_bwd`` dQ ``bf16[rows, 4, 8, seq, 128]``
    before dK and dV (the first result is where ``benchmark/dsa_cost.py``
    reads a call's shape)."""
    rows, seq, group = step["rows"], step["seq"], HEADS // KV
    words = f"i32[{rows},{seq // 512},{seq // 32},512]"
    q = f"bf16[{rows},{KV},{group},{seq},{D}]"
    kv = f"bf16[{rows},{KV},{seq},{D}]"
    stat = f"f32[{rows},{KV},{seq // 128},1,{group * 128}]"
    operands = kernel_operands(step["traced"].jaxpr)
    assert operands["dsa_fwd"] == [q, kv, kv, words]
    live = f"i32[{sum(i // 2 + 1 for i in range(seq // 256))}]"
    wide = f"f32[{rows},{KV},{seq // 256},1,{group * 256}]"
    assert operands["dsa_bwd"] == [live, live, q, kv, kv, q, wide, wide, words]
    assert "dsa_bwd_dq" not in operands and "dsa_bwd_dkdv" not in operands
    assert operands["dsa_index"] == [f"bf16[{rows},{HI},{seq},{DI}]",
                                     f"bf16[{rows},{seq},{DI}]", f"f32[{rows},{seq},{HI}]"]
    results = next(line for line in mla.custom_calls(step["compiled"])
                   if "%dsa_index" in line.split(" = ")[0]).split("custom-call(")[0]
    assert (results.index(f"s32[{rows},{seq},1]")
            < results.index(f"s32[{rows},{seq // 512},{seq // 32},512]"))
    results = next(line for line in mla.custom_calls(step["compiled"])
                   if "%dsa_bwd" in line.split(" = ")[0]).split("custom-call(")[0]
    assert results.split(" = ")[1].lstrip("( ").startswith(q), results
    assert results.count(f"bf16[{rows},{KV},{seq},{D}]") == 2


def test_the_calls_have_the_blocks_dispatch_chose_and_their_own_vmem(step):
    from deepspeed_tpu.ops import kernel_dispatch as kd
    rows, seq = step["rows"], step["seq"]
    sig = kd.make_sig((rows, seq, HEADS, D), KV, seq, "bfloat16", True, None, None,
                      pattern=f"dsa{TOPK}")
    assert kd.choose_dsa_blocks(sig, HI, DI) == (128, 512)
    calls = {line.split(" = ")[0].split("%")[-1].split(".")[0]: line
             for line in mla.custom_calls(step["compiled"])}
    grouped = f"bf16[{rows},{KV},{HEADS // KV},{seq},{D}]"
    assert f"s32[{rows},{seq},1]" in calls["dsa_index"].split("custom-call(")[0]
    assert f"bf16[{rows},{HI},{seq},{DI}]" in calls["dsa_index"].split("custom-call(")[1]
    fwd = calls["dsa_fwd"].split("custom-call(")[0]
    assert grouped in fwd and f"f32[{rows},{KV},{seq // 128},1,{HEADS // KV * 128}]" in fwd
    assert kd.resolve_dsa_bwd(sig, (128, 512)) == (kd.IMPL_FUSED, 256)
    assert grouped in calls["dsa_bwd"].split("custom-call(")[0]
    for name, leg in (("dsa_index", "index"), ("dsa_fwd", "fwd"), ("dsa_bwd", "fused")):
        need = kd.dsa_vmem_bytes(leg, *((1, 1, DI) if leg == "index" else (KV, HEADS // KV, D)),
                                 2, 256 if leg == "fused" else 128, 512, seq, HI)
        asked = re.findall(r'scoped_memory_configs":\[([^\]]*)\]', calls[name])[0]
        assert int(re.search(r'"size":"(\d+)"', asked).group(1)) == kd.vmem_limit_bytes(need)


def test_no_score_matrix_is_in_hbm_the_scopes_stand_and_it_fits(step):
    """No array of ``seq x seq`` in the compiled step; ``ds.dsa.index`` is on
    the ops before the kernels (closed before their call: the instructions
    above keep their names; on this path the whole selection is inside
    ``dsa_index``, and XLA folds the one transpose ``ds.dsa.select`` held
    into the kernel's operand layout); two layers' temporaries
    beside their state at rest are far under the chip's ``bytes_limit``."""
    text = step["compiled"].as_text()
    seq = step["seq"]
    assert not re.search(rf"\[(\d+,)*{seq},{seq}\]", text)
    for scope in ("ds.step.loss", "ds.dsa.index", "ds.rope",
                  "ds.moe.route", "ds.head.loss"):
        assert f"/{scope}/" in text, scope
    assert "/self_attn/" in text and "ds.dsa.index/indexer_q_proj/" in text
    temporaries = step["compiled"].memory_analysis().temp_size_in_bytes
    assert temporaries + IN_USE <= mla.V5E_BYTES_LIMIT - 0.8e9


if __name__ == "__main__":
    # the whole cell by hand: python tests/unit/ops/test_tpu_aot_compile_dsa.py [layers]
    import time
    from jax.experimental import topologies
    sys.path.insert(0, str(ROOT))
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    steer_to_the_chip(pytest.MonkeyPatch().setattr)
    cfg, rows, seq = cell_config(int(sys.argv[1]) if len(sys.argv) > 1 else 6)
    t0 = time.monotonic()
    traced, n_params = mla.step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
    compiled = traced.lower().compile()
    mem = compiled.memory_analysis()
    print(f"{rows} x {seq}: {n_params} parameters, 12 B each {12 * n_params / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, kept residuals "
          f"{kept_residual_bytes(traced.jaxpr) / 1e9:.3f} GB, together "
          f"{(12 * n_params + mem.temp_size_in_bytes) / 1e9:.3f} GB of "
          f"{mla.V5E_BYTES_LIMIT / 1e9:.3f} GB; arrays of seq x seq: "
          f"{len(re.findall(rf'[\\[,]{seq},{seq}\\]', compiled.as_text()))}; "
          f"{time.monotonic() - t0:.0f} s")
