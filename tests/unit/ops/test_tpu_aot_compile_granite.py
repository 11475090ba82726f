"""Granite-4.0-H's kernels compiled for a described (not attached) TPU v5e
at the ``train-granite4hm-1chip-longseq`` cell's widths: the state-space scan
and its convolution, one Mamba-2 layer under the program's scopes, and the
cell's whole recomputing step. ``aot_v5e.py`` has what these files share.
"""

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import (_compile, _custom_call_names, _sds,  # noqa: F401
                     a_recomputing_cells_step_runs_each_attention_forward_once_and_fits,
                     kernels_keep_their_names_under_the_programs_scopes,
                     no_compile_cache, one_chip, topo)


def test_state_space_kernels_compile_and_keep_their_names(one_chip, no_compile_cache):
    """Granite-4.0-H-Micro's Mamba-2 mixer, the cell's sequence: the scan at
    ``[1, 16384, 64 heads x 64]`` with a state of 128 in chunks of 256, and
    the convolution before it at ``[1, 16384, 4352]`` with four taps and a
    bias, bf16, forward and backward, as Mosaic kernels named for what they
    are (``benchmark/ssd_cost.py`` matches ``%ssd_chunk_fwd*``,
    ``%ssd_chunk_bwd*``, ``%causal_conv_fwd*``, ``%causal_conv_bwd*``)."""
    from deepspeed_tpu.ops.short_conv import causal_conv
    from deepspeed_tpu.ops.ssd import ssd_scan
    scan = [_sds((1, 16384, 64, 64), jnp.bfloat16, one_chip),
            _sds((1, 16384, 64), jnp.float32, one_chip), _sds((64, ), jnp.float32, one_chip),
            _sds((1, 16384, 128), jnp.bfloat16, one_chip),
            _sds((1, 16384, 128), jnp.bfloat16, one_chip), _sds((64, ), jnp.float32, one_chip)]

    def scan_loss(*a):
        return jnp.sum(ssd_scan(*a, 256, use_kernel=True).astype(jnp.float32))

    names = _custom_call_names(_compile(jax.grad(scan_loss, argnums=tuple(range(6))), *scan))
    assert sorted(n.split(".")[0] for n in names) == ["ssd_chunk_bwd", "ssd_chunk_fwd"], names
    names = _custom_call_names(_compile(
        lambda *a: ssd_scan(*a, 256, use_kernel=True, with_state_absmax=True), *scan))
    assert [n.split(".")[0] for n in names] == ["ssd_chunk_fwd"], names
    conv = [_sds((1, 16384, 4352), jnp.bfloat16, one_chip),
            _sds((4, 4352), jnp.float32, one_chip), _sds((4352, ), jnp.float32, one_chip)]

    def conv_loss(*a):
        return jnp.sum(causal_conv(*a, use_kernel=True).astype(jnp.float32))

    names = _custom_call_names(_compile(jax.grad(conv_loss, argnums=(0, 1, 2)), *conv))
    assert [n.split(".")[0] for n in sorted(names)] == ["causal_conv_bwd"], names
    names = _custom_call_names(_compile(
        lambda *a: causal_conv(*a, use_kernel=True), *conv))
    assert [n.split(".")[0] for n in names] == ["causal_conv_fwd"], names


@pytest.mark.parametrize("kind", ['mamba_dense'])
def test_kernels_keep_their_names_under_the_programs_scopes(
        one_chip, no_compile_cache, monkeypatch, kind):
    kernels_keep_their_names_under_the_programs_scopes(one_chip, monkeypatch, kind)


@pytest.mark.parametrize("cell", ['train-granite4hm-1chip-longseq'])
def test_a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, no_compile_cache, monkeypatch, cell):
    a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, monkeypatch, cell)
