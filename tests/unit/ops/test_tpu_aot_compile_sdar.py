"""Block-diffusion attention (``bdattn_fwd`` / ``bdattn_bwd``) compiled for a
described (not attached) TPU v5e at the ``train-sdar-1chip-bd4-seq8k`` cell's
widths: the kernels at the cell's call, one SDAR layer under the program's
scopes, and the cell's whole recomputing step. ``aot_v5e.py`` has what these
files share.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import (_compile, _custom_call_names, _custom_calls, _sds,  # noqa: F401
                     _grouped_matmuls, _other_kernels, _steer_the_model_to_the_chip,
                     a_recomputing_cells_step_runs_each_attention_forward_once_and_fits,
                     no_compile_cache, one_chip, topo)

D = 128     # SDAR-30B-A3B's head size


def test_block_diffusion_kernels_compile_at_the_sdar_cells_shape(one_chip,
                                                                 no_compile_cache):
    """``train-sdar-1chip-bd4-seq8k``'s call, ``[2, 16384, 32/4, 128]`` in
    blocks of 4: the forward and the one backward kernel at the (128, 512)
    tiles ``kernel_dispatch`` picks (2,048 folded rows a step: both copies x
    8 heads x 128 queries), each under its own name and none under a
    ``flash`` name, on operands laid out ``[rows * kv, 2, group, L, d]``;
    the backward asks for the VMEM its clean keys' float32 dK and dV need."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    from deepspeed_tpu.ops.attention import block_diffusion_attention
    sig = kd.make_sig((2, 16384, 32, D), 4, 16384, "bfloat16", False, None, None,
                      pattern="bd4")
    for leg in ("fwd", "bwd"):
        assert kd.choose_block_diffusion_blocks(sig, leg, 4) == (128, 512)
    need = kd.bdattn_vmem_bytes("bwd", 8, D, 2, 128, 512, 8192)
    assert kd.VMEM_SCOPED_DEFAULT_BYTES < need < kd.FUSED_VMEM_CAP_BYTES

    q = _sds((2, 16384, 32, D), jnp.bfloat16, one_chip)
    k = _sds((2, 16384, 4, D), jnp.bfloat16, one_chip)
    compiled = _compile(
        jax.grad(lambda q, k, v: jnp.sum(block_diffusion_attention(
            q, k, v, 4, force_pallas=True).astype(jnp.float32)), argnums=(0, 1, 2)),
        q, k, k)
    calls, names = _custom_calls(compiled), _custom_call_names(compiled)
    assert sorted(n.split(".")[0] for n in names) == ["bdattn_bwd", "bdattn_fwd"], names
    assert not any("flash" in n for n in names)
    for call in calls:
        assert f"bf16[8,2,8,8192,{D}]" in call.split(" custom-call(")[0], call
    bwd, = [c for c in calls if "%bdattn_bwd" in c.split(" = ")[0]]
    asked = re.findall(r'scoped_memory_configs":\[([^\]]*)\]', bwd)[0]
    assert int(re.search(r'"size":"(\d+)"', asked).group(1)) == kd.vmem_limit_bytes(need)


def test_the_block_diffusion_layer_keeps_its_kernels_names_under_the_scopes(
        one_chip, no_compile_cache, monkeypatch):
    """One SDAR layer at its widths (16 of 128 experts held, the vocabulary
    cut) under the block-diffusion objective, the weighted loss and its
    gradient under the engine's ``ds.step.loss``: the attention is the
    ``bdattn`` pair (the forward ONCE: the recomputed layer takes the kept
    output) and no ``flash`` call, the share's grouped matmuls are the
    program's own (``moe_gmm_*``, in either branch of the ``cond``), and the
    program's scopes are on the ops around them."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.engine import _step_scope
    seq = 4096
    cfg = llama.LlamaConfig(
        vocab_size=2048, hidden_size=2048, num_hidden_layers=1, intermediate_size=768,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128, qk_norm="head",
        num_local_experts=128, moe_experts_held=16, num_experts_per_tok=8,
        rope_theta=1e6, rms_norm_eps=1e-6, max_position_embeddings=seq,
        ce_chunk_size=2048, remat=True, objective="block_diffusion")
    _steer_the_model_to_the_chip(monkeypatch)
    model = llama.LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: {"params": llama.unbox_params(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]})
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), shapes)
    ids = _sds((1, 2 * seq), jnp.int32, one_chip)
    targets = _sds((1, seq), jnp.int32, one_chip)
    weights = _sds((1, seq), jnp.float32, one_chip)

    def step(params, ids, targets, weights):
        def loss(p):
            out = model.apply(p, ids, targets, loss_weights=weights,
                              mutable=["moe_stats", "diffusion_stats"])
            return out[0].astype(jnp.float32)
        with _step_scope("loss"):
            return jax.value_and_grad(loss)(params)

    compiled = _compile(step, params, ids, targets, weights)
    assert _other_kernels(compiled) == {"bdattn_fwd": 1, "bdattn_bwd": 1}, \
        _custom_call_names(compiled)
    assert _grouped_matmuls(compiled) == {
        "rows": 2 * (3 + 3), "d_rows": 2 * 3, "weights": 2 * 3}
    text = compiled.as_text()
    for scope in ("ds.step.loss", "ds.head.loss", "ds.rope", "ds.moe.route",
                  "ds.moe.dispatch", "ds.moe.combine"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("cell", ['train-sdar-1chip-bd4-seq8k'])
def test_a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, no_compile_cache, monkeypatch, cell):
    a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, monkeypatch, cell)
