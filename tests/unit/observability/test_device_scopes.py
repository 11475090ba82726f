"""The names the compiled training step carries (docs/observability.md,
"Device scopes"): the engine's ``ds.step.*`` regions through its one helper,
``ds.head.loss``, ``ds.moe.*`` and ``ds.rope`` at the call sites, and JAX's
own marks that tell a module's forward, backward and recomputed ops apart.
Read from the compiled programs' text, as ``benchmark/scope_time.py`` reads
them from a trace."""

import re

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import reset_mesh_context
from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
from deepspeed_tpu.models import LlamaConfig, init_llama

STEP_SCOPES = ("ds.step.cast", "ds.step.loss", "ds.step.grad_norm",
               "ds.step.optimizer")
MODELS = {
    "dense": dict(),
    "remat": dict(remat=True),
    "moe": dict(num_local_experts=4, num_experts_per_tok=2,
                router_aux_loss_coef=0.01),
    "moe_share": dict(num_local_experts=8, moe_experts_held=2,
                      num_experts_per_tok=2, moe_scoring="sigmoid", remat=True),
}
SEQ = 16


def build(model="dense", devices=1, rows=2, **ds_over):
    reset_mesh_context()
    if devices == 1:    # eight host devices would not divide a batch of 2
        set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    cfg = LlamaConfig.tiny(num_hidden_layers=2, ce_chunk_size=64, **MODELS[model])
    module, params = init_llama(cfg, seed=0)
    config = {"train_batch_size": rows, "steps_per_print": 0,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}, "gradient_clipping": 1.0, **ds_over}
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config=config)
    return engine


def op_names(jitted, *args):
    text = jitted.lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def fused_step_names(engine, rows=2):
    ids = jnp.zeros((rows, SEQ), jnp.int32)
    return op_names(engine._train_step_fused, engine.params, engine.opt_state,
                    engine.scale_state, (ids, ids), {}, ())


def scopes_of(names):
    """Every ``ds.*`` scope on any op's path, out of JAX's marks."""
    return {m for n in names for m in re.findall(r"ds\.[a-z_.]+[a-z]", n)}


@pytest.fixture(autouse=True)
def _fresh_mesh():
    yield
    reset_mesh_context()


@pytest.mark.parametrize("model,also", [
    ("dense", {"ds.head.loss", "ds.rope"}),
    ("remat", {"ds.head.loss", "ds.rope"}),
    ("moe", {"ds.head.loss", "ds.rope", "ds.moe.route", "ds.moe.dispatch",
             "ds.moe.combine"}),
    ("moe_share", {"ds.head.loss", "ds.rope", "ds.moe.route", "ds.moe.dispatch",
                   "ds.moe.combine"}),
])
def test_the_fused_step_carries_every_scope(model, also):
    names = fused_step_names(build(model))
    assert scopes_of(names) == set(STEP_SCOPES) | also
    # the engine's regions hold what they say: the cast, the norm's
    # reduction, the optimizer's arithmetic
    assert any(n.endswith("ds.step.cast/convert_element_type") for n in names)
    assert any("/ds.step.grad_norm/" in n and "reduce_sum" in n for n in names)
    assert any("/ds.step.optimizer/" in n for n in names)
    # everything of the model is under the loss scope, nothing of the update
    assert not any("ds.step.optimizer" in n and "ds.step.loss" in n for n in names)
    assert all("ds.step.loss" in n for n in names if "ds.head.loss" in n)


def test_forward_backward_and_recomputed_ops_of_one_module_are_told_apart():
    from benchmark import scope_time
    names = fused_step_names(build("remat"))
    mine = [n for n in names if "/layers_1/mlp/up_proj/" in n]
    by_phase = {}
    for n in mine:
        by_phase.setdefault(scope_time.phase_of(n), set()).add(n)
    assert set(by_phase) == {"fwd", "bwd", "recompute"}, by_phase
    assert all(scope_time.part_of(n) == "ffn" for n in mine)
    assert all("transpose(" in n and "rematted_computation" not in n
               for n in by_phase["bwd"])
    assert all("rematted_computation" in n for n in by_phase["recompute"])
    assert all("jvp(" in n and "transpose(" not in n for n in by_phase["fwd"])
    # without remat nothing is recomputed
    plain = fused_step_names(build("dense"))
    assert not any(scope_time.phase_of(n) == "recompute" for n in plain)
    # and the engine's own work is neither pass of the model
    assert {scope_time.phase_of(n) for n in plain if "ds.step.optimizer" in n} \
        == {"update"}
    assert {scope_time.phase_of(n) for n in plain if "/ds.step.cast/" in n} == {"prep"}


def test_the_moe_gathers_keep_their_scope_through_the_custom_vjp():
    from benchmark import scope_time
    names = fused_step_names(build("moe"))
    for scope in ("ds.moe.dispatch", "ds.moe.combine", "ds.moe.route"):
        phases = {scope_time.phase_of(n) for n in names if f"/{scope}/" in n}
        assert {"fwd", "bwd"} <= phases, (scope, phases)
        assert all(scope_time.part_of(n) == "moe" for n in names if f"/{scope}/" in n)
    # the grouped matmuls between them keep the block's own scope (the CPU
    # decomposes ragged_dot to a masked dot_general)
    assert any(n.endswith("/block_sparse_moe/dot_general") for n in names)
    assert not any("ds.moe." in n and n.endswith("dot_general") for n in names)


@pytest.mark.parametrize("program", ["train_steps", "train_batch_steps",
                                     "apply_step", "fwd_bwd"])
def test_the_other_step_programs_carry_the_same_names(program):
    """One helper names the regions of all four builders, so they cannot drift:
    the K-step scan and the accumulating batch step hold all four, the split
    ``forward``/``backward`` path its half each."""
    ids = jnp.zeros((2, SEQ), jnp.int32)
    if program == "train_steps":
        e = build()
        stacked = jnp.zeros((3, 2, SEQ), jnp.int32)
        names = op_names(e._train_steps_fused, e.params, e.opt_state, e.scale_state,
                         (stacked, stacked), {}, ())
        want = set(STEP_SCOPES)
    elif program == "train_batch_steps":
        e = build(rows=4, gradient_accumulation_steps=2)
        stacked = jnp.zeros((2, 2, SEQ), jnp.int32)
        names = op_names(e._train_batch_fused, e.params, e.opt_state, e.scale_state,
                         (stacked, stacked), ())
        want = set(STEP_SCOPES)
    elif program == "apply_step":
        e = build()
        names = op_names(e._apply_step, e.params, e._ensure_grad_acc(), e.opt_state,
                         e.scale_state)
        want = {"ds.step.grad_norm", "ds.step.optimizer"}
    else:
        e = build()
        names = op_names(e._fwd_bwd, e.params, e._ensure_grad_acc(), jnp.float32(1.0),
                         (ids, ids), {}, ())
        want = {"ds.step.cast", "ds.step.loss"}
    assert {s for s in scopes_of(names) if s.startswith("ds.step.")} == want
    if "ds.step.loss" in want:
        assert any("ds.step.loss" in n and "transpose(" in n for n in names)


@pytest.mark.world_size(8)
@pytest.mark.parametrize("quantized", [False, True], ids=["zero3", "zero3_qwz"])
def test_zero3_on_the_host_devices(quantized):
    """ZeRO-3 over the eight host devices: GSPMD's form casts the shards
    outside the differentiated function; with quantized weights the explicit
    int8-wire gather runs inside it, under ``ds.step.gather``."""
    zero = {"stage": 3, "stage3_param_persistence_threshold": 0}
    if quantized:
        zero["zero_quantized_weights"] = True
    engine = build(devices=8, rows=8, zero_optimization=zero)
    assert engine.mesh_ctx.mesh.size == 8
    names = fused_step_names(engine, rows=8)
    want = set(STEP_SCOPES) | ({"ds.step.gather"} if quantized else set())
    assert {s for s in scopes_of(names) if s.startswith("ds.step.")} == want
    if quantized:
        # differentiated through, so under JAX's mark: forward work
        assert any("jvp(ds.step.gather)" in n for n in names)


def test_named_scope_goes_through_one_helper_in_the_engine():
    """``grep -rn named_scope deepspeed_tpu``: the engine's helper and the
    call sites of the functions modules call that are not modules."""
    import os
    root = os.path.dirname(deepspeed_tpu.__file__)
    found = {}
    for folder, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    hits = re.findall(r'named_scope\(([^)]*)\)', fh.read())
                if hits:
                    found[os.path.relpath(os.path.join(folder, f), root)] = hits
    assert found == {
        "runtime/engine.py": ['"ds.step." + region'],
        "models/llama.py": ['"ds.rope"', '"ds.attn.gate"', '"ds.diffattn.combine"',
                            '"ds.diffattn.combine"',
                            '"ds.dsa.index"', '"ds.mla.assemble"', '"ds.rope"',
                            '"ds.mla.assemble"', '"ds.mla.gate"', '"ds.kda.gates"',
                            '"ds.gdn.split"', '"ds.gdn.split"', '"ds.gdn.gates"',
                            '"ds.moe.route"', '"ds.moe.shared"',
                            # a looped stack: a scope a pass, the exit gate
                            'f"ds.loop.pass{t}"', '"ds.loop.exit"',
                            '"ds.head.loss"', '"ds.head.loss"',
                            # the exit loss: p, the head's sweep, the entropy
                            '"ds.loop.exit"', '"ds.head.loss"', '"ds.loop.exit"',
                            '"ds.selscan.dt"', '"ds.gmu.gate"'],
        "ops/dsa_attention.py": ['"ds.dsa.select"', '"ds.dsa.select"'],
        # the kernels' small operands and what comes back for them; XLA's
        # norms and mean decay around the recurrence where no kernel runs
        "ops/kda.py": ['"ds.kda.gates"', '"ds.kda.norm"', '"ds.kda.norm"',
                       '"ds.kda.gates"', '"ds.kda.gates"', '"ds.kda.gates"'],
        # the same around the Gated DeltaNet kernels: the gates' sums of what
        # the backward kernel returns, the mean decay, XLA's norms where no
        # kernel runs, the lane layout of g and beta
        "ops/gdn.py": ['"ds.gdn.gates"', '"ds.gdn.gates"', '"ds.gdn.norm"',
                       '"ds.gdn.norm"', '"ds.gdn.gates"'],
        "ops/selective_scan.py": ['"ds.selscan.dt"'],
        "ops/grouped_matmul.py": ['"ds.moe.dispatch"', '"ds.moe.combine"',
                                  '"ds.moe.dispatch"', '"ds.moe.combine"',
                                  '"ds.moe.dispatch"'],
    }
