"""``ops/selective_scan.py``: the Pallas kernels (interpreted here) against
the token-by-token recurrence, forward and every gradient, and the things the
wrapper decides (padding, the statistic, the names a recomputation keeps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import remat
from deepspeed_tpu.ops.selective_scan import (BLOCK, TILE, scan_bytes, selective_scan,
                                              selective_scan_reference, vmem_bytes)

NAMES = ("x", "dt", "A", "B", "C", "D")


def operands(b, s, E, N, seed=0, dt_at=-2.0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, E)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, E)) + dt_at)
    A = -jnp.exp(jax.random.normal(ks[2], (E, N)) * 0.5)
    B, C = jax.random.normal(ks[3], (b, s, N)), jax.random.normal(ks[4], (b, s, N))
    return x, dt, A, B, C, jax.random.normal(ks[5], (E, ))


def kernels(*args, block=16, **kw):
    return selective_scan(*args, use_kernel=False, interpret=True, block=block, **kw)


# one block and several; a length no block divides; channels short of a tile,
# a tile and a bit; a batch; 16 states as the family has them
SHAPES = [(1, 16, 128, 4, 16), (1, 48, 256, 4, 16), (2, 40, 1100, 16, 16),
          (1, 21, 96, 8, 8)]


@pytest.mark.parametrize("b,s,E,N,block", SHAPES)
def test_forward_and_the_largest_state_are_the_recurrences(b, s, E, N, block):
    args = operands(b, s, E, N)
    y, top = kernels(*args, block=block, with_state_absmax=True)
    want, top_want = selective_scan_reference(*args, with_state_absmax=True,
                                              stat_every=block)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    # the kernels see the states at the blocks' ends; a padded tail leaves
    # the last real token's state as it is
    np.testing.assert_allclose(top, top_want, rtol=1e-6)
    assert y.shape == (b, s, E) and y.dtype == args[0].dtype


@pytest.mark.parametrize("b,s,E,N,block", SHAPES[1:3])
def test_every_gradient_is_the_recurrences(b, s, E, N, block):
    args = operands(b, s, E, N, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (b, s, E))
    got = jax.grad(lambda *a: jnp.sum(kernels(*a, block=block) * w),
                   argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a) * w),
                    argnums=range(6))(*args)
    for name, g, r in zip(NAMES, got, want):
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 2e-6, (name, err)


@pytest.mark.parametrize("dt_at", [-7.0, 2.5])
def test_both_ends_of_the_step_sizes_range(dt_at):
    """``dt`` near 1e-3 (the state hardly decays and sums hundreds of tokens)
    and near 2.5 (``exp(dt A)`` underflows towards 0 within a token or two)."""
    args = operands(1, 64, 128, 16, seed=2, dt_at=dt_at)
    np.testing.assert_allclose(kernels(*args), selective_scan_reference(*args),
                               atol=5e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) ** 2), argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a) ** 2),
                    argnums=range(6))(*args)
    for name, g, r in zip(NAMES, got, want):
        assert float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r)) < 5e-6, name
        assert bool(jnp.isfinite(g).all())


def test_bf16_operands_keep_their_type_and_the_state_stays_float32():
    args = operands(1, 32, 128, 4, seed=3, dtype=jnp.bfloat16)
    y = kernels(*args)
    assert y.dtype == jnp.bfloat16
    want = selective_scan_reference(*args)
    np.testing.assert_allclose(y.astype(jnp.float32), want.astype(jnp.float32),
                               atol=2e-2, rtol=2e-2)
    dx = jax.grad(lambda x: jnp.sum(kernels(x, *args[1:]).astype(jnp.float32)))(args[0])
    assert dx.dtype == jnp.bfloat16


def test_off_the_chip_the_recurrence_runs_and_shapes_are_refused_by_name():
    args = operands(1, 24, 64, 4)
    np.testing.assert_array_equal(
        selective_scan(*args, use_kernel=False),
        selective_scan_reference(*args))
    with pytest.raises(ValueError, match="want x, dt"):
        selective_scan(args[0], args[1][:, :-1], *args[2:], use_kernel=False)
    with pytest.raises(ValueError, match="at most 64 states"):
        kernels(*operands(1, 16, 64, 80))


def test_a_recomputation_keeps_the_scan_by_name_or_runs_it_again():
    """Under ``jax.checkpoint`` with the program's policy the output and the
    block states carry ``ds.selscan.scan`` where the layer keeps it and the
    name no policy knows where it does not."""
    args = operands(1, 32, 128, 4)

    def names(keep):
        f = jax.checkpoint(lambda *a: jnp.sum(kernels(*a, keep=keep)),
                           policy=remat.KEPT_POLICY)
        text = str(jax.make_jaxpr(jax.grad(f))(*args))
        return remat.SELSCAN_SCAN + remat.AGAIN in text, f"name={remat.SELSCAN_SCAN}]" in text

    assert names(True) == (False, True) and names(False) == (True, False)
    assert remat.SELSCAN_SCAN in remat.CANDIDATE_NAMES
    assert set(remat.SHARED) <= set(remat.KEPT_NAMES)


def test_the_bytes_kept_and_the_vmem_asked_for_at_the_cells_shape():
    # 16,384 tokens of 5,120 channels in bf16 and 128 blocks' states of 16
    assert scan_bytes(1, 16384, 5120, 16, 2) == 5120 * (16384 * 2 + 128 * 16 * 4)
    assert scan_bytes(1, 100, 1000, 4, 2, block=16) == 1024 * (112 * 2 + 7 * 4 * 4)
    assert (BLOCK, TILE) == (128, 1024)
    # the backward holds a block's states: 8 MiB of its estimate at 16 states
    assert vmem_bytes("bwd", 16, BLOCK, 2) - vmem_bytes("fwd", 16, BLOCK, 2) > 8 * 2**20
    assert vmem_bytes("bwd", 16, BLOCK, 2) < 32 * 2**20
