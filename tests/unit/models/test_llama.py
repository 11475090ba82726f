"""Llama model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM, init_llama, cross_entropy_loss


def test_forward_shapes():
    cfg = LlamaConfig.tiny()
    model, params = init_llama(cfg)
    ids = jnp.ones((2, 16), dtype=jnp.int32)
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_loss_contract():
    cfg = LlamaConfig.tiny()
    model, params = init_llama(cfg)
    ids = jnp.ones((2, 16), dtype=jnp.int32)
    loss = model.apply({"params": params}, ids, labels=ids)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    # fresh init loss ≈ ln(vocab) (lecun-init logits add ~1 nat of variance)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 2.0


def test_ignore_index_masking():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.array([[1, 2, -100, 3]])
    loss = cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)


def test_scan_vs_loop_equivalence():
    """scan_layers is a compile-time layout choice, not a numerics change."""
    cfg_loop = LlamaConfig.tiny(scan_layers=False)
    model_l, params_l = init_llama(cfg_loop)
    cfg_scan = LlamaConfig.tiny(scan_layers=True)
    model_s, params_s = init_llama(cfg_scan)
    # stack the loop params into scan layout and compare forward
    ids = jnp.ones((1, 8), dtype=jnp.int32)
    import jax.tree_util as jtu
    stacked = jtu.tree_map(lambda *xs: jnp.stack(xs),
                           params_l["model"]["layers_0"], params_l["model"]["layers_1"])
    params_s2 = {"model": {**{k: v for k, v in params_l["model"].items()
                              if not k.startswith("layers_")},
                           "layers": {"layer": stacked}}}
    out_l = model_l.apply({"params": params_l}, ids)
    out_s = model_s.apply({"params": params_s2}, ids)
    np.testing.assert_allclose(np.asarray(out_l), np.asarray(out_s), rtol=2e-2, atol=2e-2)


def test_gqa_heads():
    cfg = LlamaConfig.tiny(num_attention_heads=4, num_key_value_heads=1)
    model, params = init_llama(cfg)
    k = params["model"]["layers_0"]["self_attn"]["k_proj"]["kernel"]
    assert k.shape[-1] == cfg.head_dim_ * 1


@pytest.mark.world_size(8)
def test_llama_trains_with_engine():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model, params = init_llama(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "mesh": {"fsdp": 8}})
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(8):
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, 16)), dtype=jnp.int32)
        loss = engine.forward(ids, labels=ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_llama_memorizes_fixed_batch():
    """Convergence beyond loss-goes-down: a tiny llama must MEMORIZE a fixed
    batch (CE under 0.1 from ~5.5) through the full engine stack — ZeRO-3,
    bf16 params with fp32 master, fused step (parity target: reference
    tests/model convergence checks, cut to CI size)."""
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=64,
                           intermediate_size=160, dtype=jnp.float32)
    model, params = init_llama(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 3}})
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, 32)), jnp.int32)
    first = last = None
    for i in range(60):
        loss = float(engine.fused_train_step(ids, labels=ids))
        first = first if first is not None else loss
        last = loss
    assert first > 3.0, f"initial CE should be near ln(vocab): {first}"
    assert last < 0.1, f"failed to memorize: {first} -> {last}"


def test_remat_policy_selective():
    """remat_policy (selective remat: jax.checkpoint_policies name) must
    produce identical loss/grads to no-remat, and unknown names must raise."""
    import jax
    # float32: at bf16 XLA keeps excess precision inside whatever it fuses,
    # so two compiled programs of one model differ in the last bit
    cfg_kw = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=160,
                  dtype=jnp.float32)
    base = LlamaConfig.tiny(**cfg_kw)
    sel = LlamaConfig.tiny(**cfg_kw, remat=True, remat_policy="dots_saveable")
    model_a, params = init_llama(base, seed=0)
    model_b = LlamaForCausalLM(sel)     # the same parameters: remat adds none
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, base.vocab_size, size=(2, 32)), jnp.int32)

    def loss_and_grad_of(m):    # one compiled program a model, not op by op
        return jax.jit(jax.value_and_grad(
            lambda p: m.apply({"params": p}, ids, labels=ids)))(params)

    (la, ga), (lb, gb) = loss_and_grad_of(model_a), loss_and_grad_of(model_b)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=1e-5), ga, gb)

    import pytest
    bad = LlamaConfig.tiny(**cfg_kw, remat=True, remat_policy="no_such_policy")
    with pytest.raises(ValueError, match="remat_policy"):
        init_llama(bad)


def test_chunked_ce_and_selective_remat_under_zero3_mesh():
    """The chunked-CE scan and remat_policy must compile and train inside
    the fused step under a ZeRO-3 dp x fsdp mesh (multi-chip protection for
    the two new perf paths), with loss matching the dense-CE engine."""
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, 256, size=(8, 32)), dtype=jnp.int32)
    losses = {}
    for name, extra in (("dense", {}),
                        ("chunked", dict(ce_chunk_size=96,
                                         remat=True,
                                         remat_policy="dots_saveable"))):
        reset_mesh_context()
        cfg = LlamaConfig.tiny(dtype=jnp.float32, **extra)
        model, params = init_llama(cfg, seed=5)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3},
                    "mesh": {"data": 2, "fsdp": 4}})
        first = float(engine.fused_train_step(ids, labels=ids))
        second = float(engine.fused_train_step(ids, labels=ids))
        assert np.isfinite(first) and second < first
        losses[name] = first
    np.testing.assert_allclose(losses["chunked"], losses["dense"], rtol=1e-4)


def _loop_bodies(hlo):
    """The lines of every computation a ``while`` body of the compiled
    program reaches, by body: ``{body name: [lines]}``."""
    import re
    comps, cur = {}, None
    for line in hlo.split("\n"):
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    out = {}
    for body in set(re.findall(r"body=%?([\w.\-]+)", hlo)):
        seen, todo = set(), [body]
        while todo:
            c = todo.pop()
            if c not in seen and c in comps:
                seen.add(c)
                todo += re.findall(
                    r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)",
                    "\n".join(comps[c]))
        out[body] = [line for c in seen for line in comps[c]]
    return out


@pytest.mark.world_size(8)
def test_chunked_ce_sweeps_each_devices_own_rows_under_zero3():
    """With the batch sharded over ``data x fsdp`` the head's scan runs in a
    ``shard_map`` over those axes: each device sweeps ``[B/8 * sc, V]`` logits
    of its own sequences, ``x`` is never gathered, nothing crosses devices
    inside the loop (left to GSPMD, ``dw`` was all-reduced there once a
    chunk: it is summed over the devices after the loop)."""
    import re
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from deepspeed_tpu.ops.chunked_ce import seq_chunk
    reset_mesh_context()
    B, S = 8, 64
    cfg = LlamaConfig.tiny(dtype=jnp.float32, ce_chunk_size=64)
    V, H = cfg.vocab_size, cfg.hidden_size
    sc = seq_chunk(S, cfg.ce_chunk_size, V)
    assert (V, H, sc) == (256, 64, 16)
    model, params = init_llama(cfg, seed=5)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": B,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "mesh": {"data": 2, "fsdp": 4}})
    ids = jnp.asarray(np.random.default_rng(7).integers(0, V, size=(B, S)),
                      dtype=jnp.int32)
    ids = jax.device_put(ids, engine.zero_plan.batch_sharding((ids, ))[0])
    hlo = engine._train_step_fused.lower(
        engine.params, engine.opt_state, engine.scale_state, (ids, ),
        {"labels": ids}, ()).compile().as_text()

    def shapes(op, lines):
        return [tuple(int(d) for d in dims.split(","))
                for dims in re.findall(
                    r"= \(?\w+\[([\d,]+)\]\S* " + op + r"\(", "\n".join(lines))]

    # the logits are the one matmul whose result has a vocabulary axis and no
    # hidden axis (dw is [V, H], dx [rows, H]); the loop that holds it is the
    # head's
    head = [lines for lines in _loop_bodies(hlo).values()
            if any(V in sh and H not in sh for sh in shapes("dot", lines))]
    assert len(head) == 1, "the head's sweep is not one loop"
    dots = shapes("dot", head[0])
    assert sorted(dots) == sorted([(B // 8 * sc, V), (B // 8 * sc, H), (V, H)])
    for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute"):
        assert not shapes(op, head[0]), f"{op} inside the head's loop"
    for sh in shapes("all-gather", hlo.split("\n")):
        assert int(np.prod(sh)) < B * S * H, f"all-gather of {sh}: x whole?"


# the classes of step program a trainer's memory knobs select between, as
# overrides of one four-layer config
STEP_PROGRAM_CLASSES = {
    "scan": dict(scan_layers=True),
    "scan-dots_saveable": dict(scan_layers=True, remat=True,
                               remat_policy="dots_saveable"),
    "scan-full_remat": dict(scan_layers=True, remat=True),
    "scan-8_heads": dict(scan_layers=True, num_attention_heads=8,
                         num_key_value_heads=8),
    "scan-chunks_of_2": dict(scan_layers=True, scan_chunk_size=2),
    "unrolled": dict(scan_layers=False),
}


@pytest.mark.parametrize("program", STEP_PROGRAM_CLASSES)
def test_step_program_class_compiles_and_learns(program):
    """Each class builds under ``param_cast: model`` (the fp32 masters go
    into apply and the model casts at each use site, per scan chunk) with
    the async step pipeline and the chunked cross-entropy, and two fused
    steps on one batch lower the loss."""
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    reset_mesh_context()
    cfg = LlamaConfig(**dict(
        dict(vocab_size=256, hidden_size=128, intermediate_size=256,
             num_hidden_layers=4, num_attention_heads=16,
             num_key_value_heads=16, max_position_embeddings=128,
             ce_chunk_size=100), **STEP_PROGRAM_CLASSES[program]))
    model, params = init_llama(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "param_cast": "model",
                "async_pipeline": {"enabled": True, "sync_interval": 16},
                "steps_per_print": 0})
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, 64)), jnp.int32)
    l0 = float(engine.fused_train_step(ids, labels=ids))
    l1 = float(engine.fused_train_step(ids, labels=ids))
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0  # same batch twice: the step must actually learn


def _kernel_calls(closed_jaxpr):
    """{kernel name: calls} of a traced program's ``pallas_call`` equations,
    a ``scan`` body's counted ``length`` times (interpreted kernels lower to
    no custom call on a CPU: the equations are what can be counted here)."""
    from jax._src import core
    calls = {}

    def walk(jaxpr, times):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                calls[name] = calls.get(name, 0) + times
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub, times * (eqn.params["length"]
                                   if eqn.primitive.name == "scan" else 1))

    walk(closed_jaxpr.jaxpr, 1)
    return calls


# float32: at bf16 XLA keeps excess precision inside whatever it fuses, so two
# programs of one model differ in the last bit on any backend
_KEPT_CASES = {
    "unrolled": (dict(), "flash_fwd", "flash_dkdv_dq"),
    "scan": (dict(scan_layers=True), "flash_fwd", "flash_dkdv_dq"),
    "block_diffusion": (dict(objective="block_diffusion", diffusion_mask_id=255),
                        "bdattn_fwd", "bdattn_bwd"),
}


@pytest.mark.parametrize("case", _KEPT_CASES)
def test_whole_layer_recomputation_keeps_what_the_attention_kernel_gave(case):
    """``remat=True`` with no policy named keeps each attention kernel's
    output and log-sum-exp (``ops/attention.py::RESIDUAL_NAMES``) for the
    layer's backward: the gradient of a two-layer model holds ONE forward
    kernel a layer, two under ``remat_policy="nothing_saveable"``, and the
    loss and every gradient leaf are the same bits as without recomputation.
    ``kept_residual_bytes`` reads what is kept off the same trace."""
    import dataclasses
    from deepspeed_tpu.models.llama import unbox_params
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    over, fwd, bwd = _KEPT_CASES[case]
    layers, rows, seq, heads, hd = 2, 2, 256, 4, 16
    base = LlamaConfig.tiny(num_hidden_layers=layers, max_position_embeddings=seq,
                            attn_impl="flash", dtype=jnp.float32, **over)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 255, (rows, seq)), jnp.int32)
    if base.block_diffusion_:
        t = jnp.linspace(0.1, 1.0, rows * seq, dtype=jnp.float32).reshape(rows, seq)
        args = (jnp.concatenate([jnp.where(t > 0.5, 255, ids), ids], axis=1), ids)
        kwargs, positions = dict(loss_weights=1.0 / t), 2 * seq
    else:
        args, kwargs, positions = (ids, ids), {}, seq
    params = unbox_params(LlamaForCausalLM(base).init(jax.random.PRNGKey(0), ids))

    def program(**remat):
        model = LlamaForCausalLM(dataclasses.replace(base, **remat))
        return jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, *args, **kwargs).astype(jnp.float32)))

    kept = program(remat=True)
    nothing = program(remat=True, remat_policy="nothing_saveable")
    plain = program()
    assert _kernel_calls(kept.trace(params).jaxpr) == {fwd: layers, bwd: layers}
    assert _kernel_calls(nothing.trace(params).jaxpr) == {fwd: 2 * layers, bwd: layers}
    assert _kernel_calls(plain.trace(params).jaxpr) == {fwd: layers, bwd: layers}
    # the output as the model computes with it and the log-sum-exp WITHOUT the
    # kernels' unit lane: a float32 a row and head
    a_layer = rows * positions * heads * (hd * 4 + 4)
    assert kept_residual_bytes(kept.trace(params).jaxpr) == layers * a_layer
    assert kept_residual_bytes(nothing.trace(params).jaxpr) == 0
    assert kept_residual_bytes(plain.trace(params).jaxpr) == 0
    want = plain(params)
    for got in (kept(params), nothing(params)):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            got, want)


def _tiny_kinds():
    from deepspeed_tpu.models.llama import LayerSpec
    moe = dict(num_local_experts=4, num_experts_per_tok=2)
    return {
        "dense": dict(head_dim=32),      # o_proj 128 deep, over the hidden 64: a candidate
        "scan": dict(scan_layers=True),
        "conv": dict(layer_specs=(LayerSpec("conv", "dense", 128),
                                  LayerSpec("conv", "moe", 32)),
                     moe_experts_held=2, moe_scoring="sigmoid", moe_selection_bias=True,
                     moe_renormalize=True, moe_renorm_eps=1e-6, **moe),
        "mamba": dict(layer_specs=(LayerSpec("mamba", "dense", 128),
                                   LayerSpec("attention", "dense", 128)),
                      mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                      mamba_chunk_size=64, pos_embedding="none"),
        "moe": dict(shared_expert_intermediate_size=32, router_aux_loss_coef=0.01, **moe),
        "latent": dict(layer_specs=(LayerSpec("latent", "dense", 128),
                                    LayerSpec("latent", "moe", 32)),
                       kv_lora_rank=32, v_head_dim=16, head_dim=24, rotary_dim=8,
                       num_key_value_heads=4, moe_scoring="sigmoid",
                       moe_selection_bias=True, shared_expert_intermediate_size=32,
                       shared_expert_gated=False, **moe),
    }


def _value_and_grad_program(cfg, ids):
    model = LlamaForCausalLM(cfg)

    def loss(p):
        out = model.apply(p, ids, ids, mutable=["moe_stats", "ssm_stats", "mla_stats",
                                                "aux_loss"])
        return out[0].astype(jnp.float32)

    return jax.jit(jax.value_and_grad(loss))


@pytest.mark.parametrize("kind", sorted(_tiny_kinds()))
def test_a_layer_that_keeps_every_candidate_gives_nothing_saveables_bits(kind, monkeypatch):
    """With a generous budget forced (a chip that reports memory to spare)
    ``remat=True`` keeps every named candidate of every layer
    (``ops/remat.py``: router, output projection, FFN, input projections,
    kernel outputs), ``kept_residual_bytes`` counts them all, and loss and
    gradients are ``remat_policy="nothing_saveable"``'s bit for bit: the same
    values, kept instead of made twice. Without a memory report the program
    keeps what it kept before: the attention kernels' residuals alone."""
    import dataclasses
    from deepspeed_tpu.models.llama import unbox_params
    from deepspeed_tpu.observability.xla import named_residual_bytes
    from deepspeed_tpu.ops import remat
    rows, seq = 2, 256
    base = LlamaConfig.tiny(max_position_embeddings=seq, attn_impl="flash",
                            dtype=jnp.float32, **_tiny_kinds()[kind])
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 255, (rows, seq)), jnp.int32)
    params = {"params": unbox_params(LlamaForCausalLM(base).init(
        jax.random.PRNGKey(0), ids))["params"]}
    recomputing = dataclasses.replace(base, remat=True)
    todays = _value_and_grad_program(recomputing, ids).trace(params)
    kept_today, offered = named_residual_bytes(todays.jaxpr)
    attention = sum(s is None or s.operator != "mamba" and s.operator != "conv"
                    for s in (base.layer_specs or (None, None)))
    heads, width = base.num_attention_heads, base.v_head_dim or base.head_dim_
    assert kept_today == attention * rows * seq * heads * (width * 4 + 4)
    want = _value_and_grad_program(
        dataclasses.replace(recomputing, remat_policy="nothing_saveable"), ids)(params)
    monkeypatch.setattr(remat, "device_memory", lambda: (1 << 40, 0))
    remat.forget_plans()
    try:
        generous = _value_and_grad_program(recomputing, ids)
        kept, offered_again = named_residual_bytes(generous.trace(params).jaxpr)
        got = generous(params)
    finally:
        remat.forget_plans()
    assert offered_again == offered and kept == offered > kept_today
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), got, want)


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_without_recomputation_the_names_change_nothing_in_the_program(kind, monkeypatch):
    """``remat=False``: a name is the identity, so the lowered program is the
    one the same model gives with every ``ops/remat.py::keep`` taken out
    (the parent's program), to the letter."""
    import re
    from deepspeed_tpu.models.llama import unbox_params
    from deepspeed_tpu.ops import remat
    cfg = LlamaConfig.tiny(max_position_embeddings=128, attn_impl="xla",
                           **_tiny_kinds()[kind])
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 255, (2, 128)), jnp.int32)
    params = {"params": unbox_params(LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), ids))["params"]}
    def lowered():      # less the counter jax appends to its private functions' names
        text = _value_and_grad_program(cfg, ids).lower(params).as_text()
        return re.sub(r"(@[A-Za-z_]+?)_\d+\b", r"\1", text)

    named = lowered()
    monkeypatch.setattr(remat, "keep", lambda x, name: x)
    assert lowered() == named
