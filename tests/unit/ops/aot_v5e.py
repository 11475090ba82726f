"""What the described-chip compile tests share (``test_tpu_aot_compile*.py``,
a file a model family so that ``--dist loadfile`` can spread them): the
fixtures that describe a TPU v5e, the readers of a compiled program's Mosaic
calls, and the two checks that run once a family: one layer of a cell's kind
under the program's device scopes, and a recomputing cell's whole step.

The TPU's compiler is installed with jax and compiles for a topology that
is only described, so these tests guard what interpret mode cannot see — a
kernel that asks for more VMEM than the chip has, or a block the tiling
refuses — at no chip time. Nothing runs; numerics are ``chip_smoke.py``'s.

A topology is described inside a fixture only: one process at a time may
load the TPU's library (the driver's test command lifts that with
``ALLOW_MULTIPLE_LIBTPU_LOAD``), so the call must not happen while any
module is imported (every xdist worker imports every test file), and the
compile stays in the test's process.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _custom_calls(compiled):
    """The compiled program's lines that call a Pallas kernel."""
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _custom_call_names(compiled):
    """Instruction names of the program's Mosaic kernels: what the device
    trace's "XLA Ops" line calls them."""
    return [line.split(" = ")[0].split("%")[-1]
            for line in _custom_calls(compiled)]


def _grouped_matmuls(compiled):
    """The compiled program's grouped matmuls by pass, ``{"rows": n, "d_rows":
    n, "weights": n}`` of ``moe_gmm_*`` calls (a pass that has none left out):
    on one chip at these shapes every one is the program's kernel, and XLA's
    (``%ragged-dot-none*``) is in no line."""
    text = compiled.as_text()
    assert not re.findall(r"%(ragged-dot-none[.\d]*) = ", text)
    names = [n.split(".")[0] for n in _custom_call_names(compiled)]
    return {leg: names.count(f"moe_gmm_{leg}") for leg in ("rows", "d_rows", "weights")
            if f"moe_gmm_{leg}" in names}


def _other_kernels(compiled):
    """Counts of the program's Mosaic kernels by name, the experts' own
    (``moe_gmm_*``, ``moe_rows_to_tokens``: counted by their own tests) left
    out."""
    names = [n.split(".")[0] for n in _custom_call_names(compiled)
             if not n.startswith(("moe_gmm", "moe_rows_to_tokens"))]
    return {k: names.count(k) for k in set(names)}


def _pallas_calls(jaxpr, found=None):
    """The ``pallas_call`` equations of ``jaxpr`` and of every jaxpr an
    equation holds, in program order (a jitted body's at each equation that
    calls it)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value, ):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, found)
    return found


def _pallas_call_sites(jaxpr):
    """Call sites of each Pallas kernel in a traced program, by the kernel's
    name."""
    names = [eqn.params["name"] for eqn in _pallas_calls(jaxpr)]
    return {name: names.count(name) for name in names}


@contextlib.contextmanager
def _mosaic_lowerings():
    """The times jax lowered a ``pallas_call`` through Mosaic inside the
    block, by the kernel's name: its own lowering rule's calls (what a
    program's lowering pays in Python, a call site or a cache's hit apart)."""
    from jax._src.pallas.mosaic import pallas_call_registration as registration
    rule, counts = registration.pallas_call_tpu_lowering_rule, {}

    def counting(ctx, *args, **params):
        counts[params["name"]] = counts.get(params["name"], 0) + 1
        return rule(ctx, *args, **params)

    registration.pallas_call_tpu_lowering_rule = counting
    try:
        yield counts
    finally:
        registration.pallas_call_tpu_lowering_rule = rule


def _steer_the_model_to_the_chip(monkeypatch):
    """Code that asks "is this a TPU" sees the CPU here, and conftest turns
    interpret mode on: steer both in the test, the kernels are the subject."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.ops import grouped_matmul
    for module in (llama, grouped_matmul):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
        monkeypatch.setattr(module, "interpret_kernels", lambda: False)
    monkeypatch.setattr("deepspeed_tpu.ops.attention.use_pallas",
                        lambda force=None: True)


# a whole layer of each training cell's kind at its widths (hidden 2048, the
# vocabulary cut: the head has no kernel), remat as the LFM2 and Granite cells
# run it: what each kernel's instruction must still be called when the
# program's own scopes (docs/observability.md, "Device scopes") are around it
_SCOPED_LAYERS = {
    "attention_rope_dense": (
        dict(num_attention_heads=16, num_key_value_heads=4, head_dim=128,
             qk_norm="head", intermediate_size=8192), 4096,
        # whole-layer recomputation keeps the kernel's output: one forward
        {"flash_fwd": 1, "flash_dkdv_dq": 1}, {}),
    "conv_moe_share": (
        dict(num_attention_heads=32, num_key_value_heads=8, head_dim=64,
             num_local_experts=64, moe_experts_held=8, num_experts_per_tok=4,
             moe_scoring="sigmoid", moe_selection_bias=True,
             moe_renorm_eps=1e-6, operator="conv", ffn="moe", ffn_width=1536),
        # either branch of the share's cond: the forward's three, a
        # recomputed forward's three, the six gradients
        4096, {"short_conv_fwd": 2, "short_conv_bwd": 1},
        {"rows": 2 * (3 + 3), "d_rows": 2 * 3, "weights": 2 * 3}),
    "mamba_dense": (
        dict(num_attention_heads=32, num_key_value_heads=8, head_dim=64,
             mamba_n_heads=64, pos_embedding="none", residual_multiplier=0.22,
             operator="mamba", ffn="dense", ffn_width=8192), 4096,
        {"ssd_chunk_fwd": 2, "ssd_chunk_bwd": 1, "causal_conv_fwd": 2,
         "causal_conv_bwd": 1}, {}),
    "attention_moe": (
        dict(num_attention_heads=16, num_key_value_heads=16, head_dim=128,
             qk_norm=True, num_local_experts=64, num_experts_per_tok=8,
             moe_renormalize=False, router_aux_loss_coef=0.01,
             intermediate_size=1024, remat=False), 4096,
        {"flash_fwd": 1, "flash_dkdv_dq": 1}, {"rows": 3, "d_rows": 3, "weights": 3}),
}


def _layers_step(one_chip, monkeypatch, kind, layers=1):
    """``layers`` layers of a cell's kind (``_SCOPED_LAYERS``) steered to the
    chip: the loss and its gradient under the engine's ``ds.step.loss``, with
    the abstract parameters and ids to trace it over, and the configuration."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.engine import _step_scope
    over, seq = (dict(_SCOPED_LAYERS[kind][0]), _SCOPED_LAYERS[kind][1])
    spec = {k: over.pop(k) for k in ("operator", "ffn", "ffn_width") if k in over}
    cfg = llama.LlamaConfig(**{**dict(
        vocab_size=2048, hidden_size=2048, num_hidden_layers=layers,
        max_position_embeddings=seq, ce_chunk_size=2048, remat=True,
        layer_specs=(llama.LayerSpec(**spec), ) * layers if spec else None), **over})
    _steer_the_model_to_the_chip(monkeypatch)
    model = llama.LlamaForCausalLM(cfg)
    ids = _sds((1, seq), jnp.int32, one_chip)
    shapes = jax.eval_shape(
        lambda: {"params": llama.unbox_params(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))["params"]})
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), shapes)

    def step(params, ids):
        def loss(p):
            out = model.apply(p, ids, ids, mutable=["aux_loss", "moe_stats"])
            return out[0].astype(jnp.float32)
        with _step_scope("loss"):
            return jax.value_and_grad(loss)(params)

    return step, params, ids, cfg


def kernels_keep_their_names_under_the_programs_scopes(one_chip, monkeypatch, kind):
    """Hazard (i) of the device scopes: an instruction is named by the
    innermost name scope of the frame that holds it, and twelve admitted
    metrics match kernels by instruction name. One layer of each cell's kind,
    the loss and its gradient under the engine's ``ds.step.loss`` with
    ``ds.rope``, ``ds.moe.*`` and ``ds.head.loss`` inside: every kernel is
    still called what its reader matches, the scopes are on the ops around
    them, and the recomputed forward's kernels are there (count 2; the
    attention kernel's forward once: its output is kept for the backward)."""
    _, _, kernels, grouped = _SCOPED_LAYERS[kind]
    step, params, ids, cfg = _layers_step(one_chip, monkeypatch, kind)
    spec = cfg.layer_specs
    compiled = _compile(step, params, ids)
    assert _other_kernels(compiled) == kernels, _custom_call_names(compiled)
    assert _grouped_matmuls(compiled) == grouped, _custom_call_names(compiled)
    text = compiled.as_text()
    for scope in (["ds.step.loss", "ds.head.loss"]
                  + ["ds.rope"] * (cfg.pos_embedding == "rope" and not spec)
                  + ["ds.moe.route", "ds.moe.dispatch", "ds.moe.combine"]
                  * bool(grouped)):
        assert f"/{scope}/" in text, scope


# the three cells that train under whole-layer recomputation: (attention
# kernel, attention layers, bytes of their kernels' outputs and log-sum-exps
# kept a step: tokens x heads x (head_dim x 2 + 4) a layer; what the chip
# reported in use when the step was first traced (my chip runs, PR 41: the
# engine at rest, 12 B a parameter, and the batch); the candidates the rule
# then admits (ops/remat.py), by name the layers that keep it; and of the
# producers left, one whose matmul is still made again)
_T = 32768          # tokens a step of the SDAR and LFM2 cells; Granite half
_RECOMPUTING_CELLS = {
    "train-sdar-1chip-bd4-seq8k": (
        "bdattn", 6, 6 * _T * 32 * (128 * 2 + 4), 7_750_149_632,
        {"ds.mixer.out": range(6), "ds.mixer.in": range(3)}, "layers_3/self_attn/q_proj"),
    "train-lfm2moe-1chip-seq8k": (
        "flash", 1, _T * 32 * (64 * 2 + 4), 5_860_270_592,
        {"ds.moe.route": range(1, 5), "ds.ffn.in": [0], "ds.mixer.in": range(5),
         "ds.mixer.out.narrow": range(5), "ds.mixer.kernel": [0, 2, 3, 4]}, None),
    "train-granite4hm-1chip-longseq": (
        "flash", 1, _T // 2 * 32 * (64 * 2 + 4), 9_267_765_248,
        # the nine Mamba layers' out_proj (4,096 deep; layer 5 is attention)
        {"ds.mixer.out": [0, 1, 2, 3, 4, 6, 7, 8, 9], "ds.ffn.in": range(2)},
        "layers_2/mlp/gate_proj"),
}
V5E_BYTES_LIMIT = 16_909_336_064


def _candidate_bytes(cfg, tokens, name, layer):
    """Bytes of ``name`` in layer ``layer`` of a cell's model at bf16, written
    out from the configuration's widths: what the rule's price list, read
    off the traced shapes, has to agree with."""
    spec = cfg.layer_specs[layer] if cfg.layer_specs else None    # None: attention, MoE
    h = cfg.hidden_size
    if name == "ds.moe.route":      # float32 logits, the top-k and its float32 weights
        assert cfg.moe_selection_bias
        return tokens * (cfg.num_local_experts + 2 * cfg.num_experts_per_tok) * 4
    if name in ("ds.mixer.out", "ds.mixer.out.narrow"):
        return tokens * h * 2
    if name == "ds.ffn.in":         # gate and up
        return tokens * 2 * spec.ffn_width * 2
    if name == "ds.mixer.kernel":   # the gated convolution's y
        assert spec.operator == "conv"
        return tokens * h * 2
    assert name == "ds.mixer.in"
    if spec is not None and spec.operator == "conv":     # B | C | u
        return tokens * 3 * h * 2
    assert spec is None or spec.operator == "attention"
    return tokens * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) \
        * cfg.head_dim_ * 2


def _recomputed_matmuls(text, producer):
    """Matmuls of ``producer`` (``layers_N/<module>/<projection>``) that the
    compiled program makes inside a recomputation."""
    return len(re.findall(
        rf'op_name="[^"]*rematted_computation[^"]*/{producer}/dot_general', text))


def a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, monkeypatch, cell):
    """The cell's whole step at its own configuration and batch (the model
    the benchmark's runner builds from ``benchmark/configs``, the engine's
    fused step spelled out: cast, loss and gradient with the sown counters,
    global norm, AdamW over float32 masters), compiled for the described
    v5e that reports the memory in use the chip reported: ONE forward
    attention kernel an attention layer (their outputs are kept for the
    recomputed layers' backward); the rule (``ops/remat.py``) admits the
    candidates listed above, ``kept_residual_bytes`` (what
    ``ds_remat_kept_bytes`` publishes) is their bytes, reckoned from the
    configuration's widths, and the kernels' 1.64 / 0.14 / 0.07 GB; no
    matmul of a kept producer is made inside a recomputation and one left
    out still is; and the program's temporaries beside an engine at rest
    (12 B a parameter: master and two moments, no accumulation buffer) stay
    0.8 GB (5%) under the chip's ``bytes_limit``."""
    import importlib
    import json
    import pathlib
    import optax
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.runtime.engine import _as_apply_fns, _step_scope
    from deepspeed_tpu.runtime.optimizers import build_optimizer
    kernel, layers, residuals, in_use, admitted, left = _RECOMPUTING_CELLS[cell]
    bench = pathlib.Path(__file__).parents[3] / "benchmark"
    workload = json.loads((bench / "workloads" / f"{cell}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    assert cfg.remat and cfg.remat_policy is None
    rows, seq = workload["traffic"]["global_batch"], workload["traffic"]["seq_len"]
    _steer_the_model_to_the_chip(monkeypatch)
    monkeypatch.setattr(remat, "device_memory", lambda: (V5E_BYTES_LIMIT, in_use))
    remat.forget_plans()
    model = llama.LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: llama.unbox_params(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, jnp.float32, one_chip), shapes)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert 0 <= in_use - 12 * n_params < 250e6
    tx, _ = build_optimizer("AdamW", {"lr": 1e-4})
    opt_state = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), jax.eval_shape(tx.init, params))
    ids = lambda n: _sds((rows, n), jnp.int32, one_chip)        # noqa: E731
    if cfg.block_diffusion_:
        args = (ids(2 * seq), ids(seq))
        kwargs = {"loss_weights": _sds((rows, seq), jnp.float32, one_chip)}
    else:
        args, kwargs = (ids(seq), ids(seq)), {}
    _, apply_with_stats = _as_apply_fns(model)

    def train_step(params, opt_state, args, kwargs):
        with _step_scope("cast"):
            compute = jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)

        def loss_of(p):
            out, stats = apply_with_stats(p, *args, **kwargs)
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

    traced = jax.jit(train_step, donate_argnums=(0, 1)).trace(
        params, opt_state, args, kwargs)
    remat.forget_plans()
    tokens = rows * seq * (2 if cfg.block_diffusion_ else 1)
    reckoned = sum(_candidate_bytes(cfg, tokens, name, layer)
                   for name, kept_in in admitted.items() for layer in kept_in)
    assert kept_residual_bytes(traced.jaxpr) == residuals + reckoned
    compiled = traced.lower().compile()
    text = compiled.as_text()
    outs = ("self_attn/o_proj", "conv/out_proj", "mamba/out_proj")
    modules = {"ds.mixer.out": outs, "ds.mixer.out.narrow": outs,
               "ds.ffn.in": ("mlp/gate_proj", "mlp/up_proj"),
               "ds.mixer.in": ("self_attn/q_proj", "conv/in_proj", "mamba/in_proj"),
               "ds.moe.route": ("block_sparse_moe/gate", )}
    for name, kept_in in admitted.items():
        for layer in kept_in:
            for module in modules.get(name, ()):
                assert not _recomputed_matmuls(text, f"layers_{layer}/{module}"), (
                    name, layer, module)
    if left:
        assert _recomputed_matmuls(text, left)
    names = [n.split(".")[0] for n in _custom_call_names(compiled)]
    assert names.count(f"{kernel}_fwd") == layers, names
    other = "flash" if kernel == "bdattn" else "bdattn"
    assert not any(n.startswith(other) for n in names), names
    # every grouped matmul of the step is the program's own, in both branches
    # of each expert layer's cond: 24 call sites a layer
    expert_layers = (sum(spec.ffn == "moe" for spec in cfg.layer_specs) if cfg.layer_specs
                     else cfg.num_hidden_layers * (cfg.num_local_experts > 0))
    assert sum(_grouped_matmuls(compiled).values()) == 24 * expert_layers, names
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries + 12 * n_params <= V5E_BYTES_LIMIT - 0.8e9, (temporaries, n_params)
