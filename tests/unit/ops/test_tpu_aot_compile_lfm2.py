"""LFM2's kernels compiled for a described (not attached) TPU v5e at the
``train-lfm2moe-1chip-seq8k`` cell's widths: the gated short convolution, a
share of the experts through XLA's grouped-matmul kernel and its rows back
to tokens, one conv + MoE-share layer under the program's scopes, and the
cell's whole recomputing step. ``aot_v5e.py`` has what these files share.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import (_compile, _custom_call_names, _custom_calls, _sds,  # noqa: F401
                     _grouped_matmuls, _layers_step, _mosaic_lowerings, _pallas_call_sites,
                     a_recomputing_cells_step_runs_each_attention_forward_once_and_fits,
                     kernels_keep_their_names_under_the_programs_scopes,
                     no_compile_cache, one_chip, topo)


def test_short_conv_kernels_compile_and_keep_their_names(one_chip, no_compile_cache):
    """LFM2's gated short convolution a sequence of the cell: ``[1, 8192, 3 x
    2048]`` in bf16 with three taps, forward and backward, as Mosaic kernels
    named for what they are (``benchmark/conv_cost.py`` matches
    ``%short_conv_fwd*`` and ``%short_conv_bwd*`` in a trace)."""
    from deepspeed_tpu.ops.short_conv import short_conv
    bcx = _sds((1, 8192, 3 * 2048), jnp.bfloat16, one_chip)
    taps = _sds((3, 2048), jnp.float32, one_chip)

    def loss(x, w):
        return jnp.sum(short_conv(x, w, use_kernel=True).astype(jnp.float32))

    names = _custom_call_names(_compile(jax.grad(loss, argnums=(0, 1)), bcx, taps))
    assert [n.split(".")[0] for n in sorted(names)] == ["short_conv_bwd"], names
    names = _custom_call_names(_compile(
        lambda x, w: short_conv(x, w, use_kernel=True), bcx, taps))
    assert [n.split(".")[0] for n in names] == ["short_conv_fwd"], names


def test_a_share_of_the_experts_compiles_to_the_native_kernel_at_lfm2_widths(
        one_chip, no_compile_cache):
    """The LFM2 cell's expert layer a step: 32,768 tokens x top-4 over a
    router of 64, 8 experts of 2048 x 1536 held. Both branches of the
    ``cond`` (the static 32,768-row array, and the exact pass over all
    131,072 rows, which since PR 37 walks them a window of 32,768 at a time)
    lower to the chip's grouped-matmul kernel, no array of all the rows at
    an expert's width exists, and the program fits beside the state."""
    from deepspeed_tpu.ops.grouped_matmul import moe_grouped_mlp_share
    T, HID, F, E, HELD, K = 32768, 2048, 1536, 64, 8, 4
    sds = functools.partial(_sds, sharding=one_chip)
    args = [sds((T, HID), jnp.bfloat16), sds((HELD, HID, F), jnp.bfloat16),
            sds((HELD, HID, F), jnp.bfloat16), sds((HELD, F, HID), jnp.bfloat16),
            sds((T, K), jnp.int32), sds((T, K), jnp.bfloat16)]

    def loss(*a):
        y, rows, fell = moe_grouped_mlp_share(*a, first_expert=0, num_experts=E)
        return jnp.sum(y.astype(jnp.float32) ** 2), (rows, fell)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 5), has_aux=True), *args)
    names = [n for n in _custom_call_names(compiled)
             if n.startswith("ragged-dot") and "metadata" not in n]
    # in each branch three forward and, in the backward, the recomputed
    # forward's three and the six gradients
    assert len(names) == 2 * (3 + 3 + 6), names
    text = compiled.as_text()
    assert "bf16[32768,1536]" in text and "bf16[131072,1536]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_the_shares_rows_go_back_to_tokens_through_the_kernel_on_one_chip(
        one_chip, no_compile_cache, monkeypatch):
    """As above where the kernel runs (a TPU, one device): the branch over
    the static 32,768 rows sums token-sorted rows in ``moe_rows_to_tokens``
    (the combine's forward, also recomputed, and the dispatch's transpose);
    the exact branch over all 131,072 keeps its gathers, and the grouped
    matmuls of both branches are the program's ``moe_gmm_*``: in each the
    forward's three and the recomputed forward's, and three of each
    gradient."""
    from deepspeed_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "_kernel_here", lambda: True)
    monkeypatch.setattr(gm, "interpret_kernels", lambda: False)
    T, HID, F, E, HELD, K = 32768, 2048, 1536, 64, 8, 4
    sds = functools.partial(_sds, sharding=one_chip)
    args = [sds((T, HID), jnp.bfloat16), sds((HELD, HID, F), jnp.bfloat16),
            sds((HELD, HID, F), jnp.bfloat16), sds((HELD, F, HID), jnp.bfloat16),
            sds((T, K), jnp.int32), sds((T, K), jnp.bfloat16)]

    def loss(*a):
        y, rows, fell = gm.moe_grouped_mlp_share(*a, first_expert=0, num_experts=E)
        return jnp.sum(y.astype(jnp.float32) ** 2), (rows, fell)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 5), has_aux=True), *args)
    names = _custom_call_names(compiled)
    assert _grouped_matmuls(compiled) == {
        "rows": 2 * (3 + 3), "d_rows": 2 * 3, "weights": 2 * 3}, names
    kernels = [n for n in names if not n.startswith("moe_gmm")]
    assert 2 <= len(kernels) <= 4 and all(
        n.startswith("moe_rows_to_tokens") for n in kernels), names
    # the kernel's blocks: 128 sorted rows of 2,048 in, 128 tokens out
    call, = [line for line in _custom_calls(compiled)
             if "%moe_rows_to_tokens" in line.split(" = ")[0]][:1]
    assert "bf16[32768,2048]" in call
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def test_a_shares_step_lowers_each_pass_and_shape_through_mosaic_once(
        one_chip, no_compile_cache, monkeypatch):
    """Two LFM2 layers' loss and gradient (whole-layer recomputation, the
    share's ``cond`` with the windows' ``scan`` in its exact branch, a
    ``jax.checkpoint`` in each): the grouped matmuls stand at 24 call sites a
    layer, 12 a branch (the forward's three, the recomputed forward's three,
    three of each gradient), and the program's lowering takes each kernel
    through Mosaic once a distinct (pass, shape): ``[2048 -> 1536]`` for w1
    and w3 and ``[1536 -> 2048]`` for w2, six in all, whatever the layers and
    the sites (every site calls the one jitted body of its pass, so they hold
    the same equation and jax lowers it once)."""
    layers = 2
    step, params, ids, _ = _layers_step(one_chip, monkeypatch, "conv_moe_share", layers)
    traced = jax.jit(step).trace(params, ids)
    sites = _pallas_call_sites(traced.jaxpr)
    assert {k: v for k, v in sites.items() if k.startswith("moe_gmm")} == {
        "moe_gmm_rows": layers * 2 * (3 + 3), "moe_gmm_d_rows": layers * 2 * 3,
        "moe_gmm_weights": layers * 2 * 3}, sites
    with _mosaic_lowerings() as lowered:
        traced.lower()
    assert {k: v for k, v in lowered.items() if k.startswith("moe_gmm")} == {
        "moe_gmm_rows": 2, "moe_gmm_d_rows": 2, "moe_gmm_weights": 2}, lowered


@pytest.mark.parametrize("kind", ['conv_moe_share'])
def test_kernels_keep_their_names_under_the_programs_scopes(
        one_chip, no_compile_cache, monkeypatch, kind):
    kernels_keep_their_names_under_the_programs_scopes(one_chip, monkeypatch, kind)


@pytest.mark.parametrize("cell", ['train-lfm2moe-1chip-seq8k'])
def test_a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, no_compile_cache, monkeypatch, cell):
    a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, monkeypatch, cell)
