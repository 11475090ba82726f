"""Latent attention's kernels (``mla_fwd`` / ``mla_bwd``: q and k 192 wide,
v and o 128) compiled for a described (not attached) TPU v5e at Kimi-VL-A3B's
published widths, in the engine's fused step: no chip time, nothing runs.

A file of its own beside ``test_tpu_aot_compile.py`` (a worker's whole share
under ``--dist loadfile``): one dense and one expert layer of the cell's
configuration at the cell's batch, compiled ONCE for the module. ``step_of``
is also what compiles the whole six-layer cell by hand before a chip call
(``python tests/unit/ops/test_tpu_aot_compile_mla.py``): its temporaries
beside 12 B a parameter are in PERF.md.
"""

import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = pathlib.Path(__file__).parents[3]
CELL = "train-kimivl-1chip-seq8k"
V5E_BYTES_LIMIT = 16_909_336_064
HEADS, D_QK, D_V = 16, 192, 128
# what the chip reported in use when the six-layer cell's step was first
# traced (my chip runs, PR 41): the engine at rest, 12 B for each of its
# 668,890,432 parameters, and the batch
IN_USE = 8_113_522_688


def cell_config(layers: int):
    """-> (the cell's ``LlamaConfig`` cut to its first ``layers`` layers,
    rows, sequence length)."""
    import dataclasses
    import importlib
    sys.path.insert(0, str(ROOT))
    bench = ROOT / "benchmark"
    workload = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    cfg = dataclasses.replace(cfg, num_hidden_layers=layers,
                              layer_specs=cfg.layer_specs[:layers])
    return cfg, workload["traffic"]["global_batch"], workload["traffic"]["seq_len"]


def step_of(cfg, rows: int, seq: int, sharding):
    """The engine's fused step spelled out over abstract parameters (cast,
    loss and gradient with the sown counters, global norm, AdamW over
    float32 masters) -> (its trace, the parameter count). The caller has
    steered the model to the chip."""
    import optax
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.engine import _as_apply_fns, _step_scope
    from deepspeed_tpu.runtime.optimizers import build_optimizer

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    model = llama.LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: llama.unbox_params(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    params = jax.tree_util.tree_map(lambda s: sds(s.shape, jnp.float32), shapes)
    tx, _ = build_optimizer("AdamW", {"lr": 1e-5})
    opt_state = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                       jax.eval_shape(tx.init, params))
    args = (sds((rows, seq), jnp.int32), sds((rows, seq), jnp.int32))
    _, apply_with_stats = _as_apply_fns(model)

    def train_step(params, opt_state, args):
        with _step_scope("cast"):
            compute = jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)

        def loss_of(p):
            out, stats = apply_with_stats(p, *args)
            return out.astype(jnp.float32), stats

        with _step_scope("loss"):
            (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(compute)
        with _step_scope("grad_norm"):
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            gnorm = optax.global_norm(grads)
        with _step_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return loss, params, opt_state, gnorm, stats

    traced = jax.jit(train_step, donate_argnums=(0, 1)).trace(params, opt_state, args)
    return traced, sum(p.size for p in jax.tree_util.tree_leaves(params))


def steer_to_the_chip(setattr_):
    """Code that asks "is this a TPU" sees the CPU here, and conftest turns
    interpret mode on: steer both, the kernels are the subject. The described
    chip reports the memory the cell's chip reported (``ops/remat.py`` chooses
    what a recomputed layer keeps against it)."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.ops import remat
    setattr_(remat, "device_memory", lambda: (V5E_BYTES_LIMIT, IN_USE))
    remat.forget_plans()
    setattr_(llama, "on_tpu", lambda: True)
    setattr_(llama, "interpret_kernels", lambda: False)
    setattr_("deepspeed_tpu.ops.attention.use_pallas", lambda force=None: True)
    setattr_("deepspeed_tpu.ops.grouped_matmul.on_tpu", lambda: True)
    setattr_("deepspeed_tpu.ops.grouped_matmul.interpret_kernels", lambda: False)


def custom_calls(compiled):
    """The compiled program's lines that call a Pallas kernel."""
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.fixture(scope="module")
def step():
    """One dense and one expert layer at the published widths and the cell's
    batch, traced and compiled for a described v5e, once."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    try:
        steer_to_the_chip(patch.setattr)
        cfg, rows, seq = cell_config(2)
        traced, n_params = step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
        yield {"cfg": cfg, "rows": rows, "seq": seq, "traced": traced,
               "n_params": n_params, "compiled": traced.lower().compile()}
    finally:
        patch.undo()
        from deepspeed_tpu.ops import remat
        remat.forget_plans()
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_every_layers_attention_is_the_mla_kernels_once_a_step(step):
    """Under whole-layer recomputation each layer's ``mla_fwd`` runs once (its
    output and log-sum-exp are kept by name for the recomputed layer's
    backward) and its backward is the one ``mla_bwd``; no ``flash_*`` call is
    in the program, and ``kept_residual_bytes`` (what ``ds_remat_kept_bytes``
    publishes) is the two layers' outputs at 128 and log-sum-exps."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    names = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             for line in custom_calls(step["compiled"])]
    kernels = {n: names.count(n) for n in set(names)}
    assert kernels.pop("mla_fwd") == 2 and kernels.pop("mla_bwd") == 2, names
    # the one MoE layer's share, either branch of its cond: the program's own
    assert (kernels.pop("moe_gmm_rows"), kernels.pop("moe_gmm_d_rows"),
            kernels.pop("moe_gmm_weights")) == (2 * (3 + 3), 2 * 3, 2 * 3), names
    assert all(n.startswith("moe_rows_to_tokens") for n in kernels), names
    tokens = step["rows"] * step["seq"]
    assert kept_residual_bytes(step["traced"].jaxpr) == (
        2 * tokens * HEADS * (D_V * 2 + 4) + sum(_kept_of_two_layers(tokens).values()))


def _kept_of_two_layers(tokens):
    """The candidates ``ops/remat.py`` admits to the two layers beside what
    the six-layer cell's chip reported in use, with their bytes from the
    published widths at bf16: all but the last of the walk, ``o_proj``'s
    output (2,048 deep, the hidden size: after the input projections), which
    no longer fits."""
    q_kva_kvb = HEADS * D_QK + (512 + 64) + HEADS * (128 + D_V)
    return {
        # float32 logits over 64 experts, the top-6 and its float32 weights
        "route, layer 1": tokens * (64 + 2 * 6) * 4,
        "gate and up of the dense FFN (11,264)": tokens * 2 * 11264 * 2,
        "gate and up of the shared expert (2 x 1,408)": tokens * 2 * 2816 * 2,
        "q_proj, kv_a_proj_with_mqa, kv_b_proj, both layers": 2 * tokens * q_kva_kvb * 2,
    }


def test_no_kept_producers_matmul_is_made_again_and_the_one_left_is(step):
    """In the compiled program no ``gate_proj`` / ``up_proj``, router ``gate``,
    ``q_proj`` or ``kv_b_proj`` matmul sits inside a recomputation; ``o_proj``,
    whose output is not kept, does."""
    text = step["compiled"].as_text()

    def recomputed(producer):
        return len(re.findall(
            rf'op_name="[^"]*rematted_computation[^"]*/{producer}/dot_general', text))

    for producer in ("layers_0/mlp/gate_proj", "layers_0/mlp/up_proj",
                     "layers_1/block_sparse_moe/shared_expert/gate_proj",
                     "layers_1/block_sparse_moe/gate", "layers_0/self_attn/q_proj",
                     "layers_1/self_attn/q_proj", "layers_0/self_attn/kv_b_proj"):
        assert not recomputed(producer), producer
    assert recomputed("layers_0/self_attn/o_proj")


def test_the_kernels_take_both_widths_with_the_blocks_dispatch_chose(step):
    """The forward reads q and k 192 wide and v 128 wide and writes o 128
    wide; the backward writes dK at 192 and dV at 128: no operand is padded
    in HBM. The blocks are ``kernel_dispatch``'s for (192, 128) at 8,192 keys."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    rows, seq = step["rows"], step["seq"]
    sig = kd.make_sig((rows, seq, HEADS, D_QK), HEADS, seq, "bfloat16", True, None, None,
                      v_dim=D_V)
    fwd, bwd = kd.resolve(sig)
    assert (fwd.impl, fwd.block_q, fwd.block_k) == ("pallas", 1024, 512)
    assert (bwd.impl, bwd.block_q, bwd.block_k) == ("fused", 512, 512)
    assert sig.v_dim == D_V and sig.head_dim == D_QK
    bh = rows * HEADS
    calls = {line.split(" = ")[0].split("%")[-1].split(".")[0]: line
             for line in custom_calls(step["compiled"])}
    assert f"bf16[{bh},1,{seq},{D_V}]" in calls["mla_fwd"].split("custom-call(")[0]
    operands = calls["mla_fwd"].split("custom-call(")[1]
    assert operands.count(f"bf16[{bh},1,{seq},{D_QK}]") == 1     # q
    assert operands.count(f"bf16[{bh},{seq},{D_QK}]") == 1       # k
    assert operands.count(f"bf16[{bh},{seq},{D_V}]") == 1        # v
    # both walk their live tiles (PR 60): the tables are the first operands
    walks = [kd.walked(sig, dec, leg) for dec, leg in ((fwd, "fwd"), (bwd, "bwd"))]
    assert [(w.tiles, w.grid, w.table) for w in walks] == [(72, 128, True),
                                                           (136, 256, True)]
    assert calls["mla_fwd"].count("s32[72]{0}") == 3
    assert calls["mla_bwd"].count("s32[136]{0}") == 4
    results = calls["mla_bwd"].split("custom-call(")[0]
    for shape in (f"bf16[{bh},{seq},{D_QK}]", f"bf16[{bh},{seq},{D_V}]",
                  f"bf16[{bh},1,{seq},{D_QK}]"):                 # dK, dV, dQ
        assert shape in results, (shape, results)


def test_the_programs_scopes_are_around_the_kernels_and_it_fits(step):
    """``ds.mla.assemble``, ``ds.rope`` and ``ds.moe.shared`` are on the ops
    around the kernels (closed before each call: the instructions above keep
    their names), and two layers' temporaries beside their state at rest are
    far under the chip's ``bytes_limit``."""
    text = step["compiled"].as_text()
    for scope in ("ds.step.loss", "ds.mla.assemble", "ds.rope", "ds.moe.shared",
                  "ds.moe.route", "ds.head.loss"):
        assert f"/{scope}/" in text, scope
    assert not re.search(r"ds\.mla\.assemble[^\n\"]*mla_(fwd|bwd)", text)
    temporaries = step["compiled"].memory_analysis().temp_size_in_bytes
    assert temporaries + IN_USE <= V5E_BYTES_LIMIT - 0.8e9


if __name__ == "__main__":
    # the whole cell by hand: python tests/unit/ops/test_tpu_aot_compile_mla.py [rows]
    import time
    from jax.experimental import topologies
    sys.path.insert(0, str(ROOT))
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    patch = pytest.MonkeyPatch()
    steer_to_the_chip(patch.setattr)
    cfg, rows, seq = cell_config(6)
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else rows
    t0 = time.monotonic()
    traced, n_params = step_of(cfg, rows, seq, SingleDeviceSharding(topo.devices[0]))
    mem = traced.lower().compile().memory_analysis()
    print(f"{rows} x {seq}: {n_params} parameters, 12 B each {12 * n_params / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, kept residuals "
          f"{kept_residual_bytes(traced.jaxpr) / 1e9:.3f} GB, together "
          f"{(12 * n_params + mem.temp_size_in_bytes) / 1e9:.3f} GB of "
          f"{V5E_BYTES_LIMIT / 1e9:.3f} GB; {time.monotonic() - t0:.0f} s")
