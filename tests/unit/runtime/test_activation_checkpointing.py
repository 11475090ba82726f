"""Activation checkpointing tests (parity target: reference
``tests/unit/runtime/activation_checkpointing/test_activation_checkpointing.py``
— checkpointed forward/backward equals non-checkpointed)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ckpt


def mlp(params, x):
    for w in params:
        x = jnp.tanh(x @ w)
    return jnp.sum(x**2)


@pytest.fixture
def setup():
    rng = np.random.default_rng(0)
    params = [jnp.asarray(rng.normal(size=(16, 16)) * 0.3, jnp.float32) for _ in range(3)]
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    return params, x


def test_checkpoint_matches_plain(setup):
    params, x = setup
    ckpt.configure(partition_activations=False, checkpoint_in_cpu=False)
    ref, ref_g = jax.value_and_grad(mlp)(params, x)
    out = ckpt.checkpoint(mlp, params, x)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-6)
    g = jax.grad(lambda p: ckpt.checkpoint(mlp, p, x))(params)
    for a, b in zip(g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_checkpoint_forces_remat(setup):
    params, x = setup
    ckpt.configure(partition_activations=False)
    # the remat primitive must appear in the grad jaxpr
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: ckpt.checkpoint(mlp, p, x)))(params)
    assert "remat" in str(jaxpr) or "checkpoint" in str(jaxpr)


def test_named_policy(setup):
    params, x = setup
    ckpt.configure(partition_activations=False)
    ckpt._CONFIG["policy"] = "dots_saveable"
    try:
        out = ckpt.checkpoint(mlp, params, x)
        ref = mlp(params, x)
        np.testing.assert_allclose(float(out), float(ref), rtol=1e-6)
    finally:
        ckpt._CONFIG["policy"] = None


@pytest.mark.parametrize("policy,forwards", [(None, 1), ("nothing_saveable", 2)])
def test_no_policy_keeps_what_an_attention_kernel_gave(policy, forwards):
    """``checkpoint`` of a function that reaches the flash kernels: with no
    policy named their output and log-sum-exp are kept (one forward kernel in
    the gradient), ``nothing_saveable`` recomputes them (two)."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops.attention import flash_attention
    q = jnp.asarray(np.random.default_rng(0).normal(size=(1, 128, 2, 16)), jnp.float32)

    def attend(q):
        return jnp.sum(flash_attention(jnp.tanh(q), q, q, causal=True,
                                       interpret=True) ** 2)

    ckpt.configure(partition_activations=False, checkpoint_in_cpu=False)
    ckpt._CONFIG["policy"] = policy
    try:
        traced = jax.jit(jax.grad(lambda q: ckpt.checkpoint(attend, q))).trace(q)
    finally:
        ckpt._CONFIG["policy"] = None
    assert str(traced.jaxpr).count("name=flash_fwd") == forwards
    assert kept_residual_bytes(traced.jaxpr) == (
        q.size * 4 + 128 * 2 * 4 if policy is None else 0)


# a price list of three layers (name, bytes): a dense layer, an expert layer
# with a shared expert, a layer that offers its kernel's output
_PRICES = (
    (("ds.mixer.in", 300), ("ds.mixer.out", 100), ("ds.ffn.in", 400), ("ds.ffn.in", 400)),
    (("ds.mixer.in", 300), ("ds.mixer.out", 100), ("ds.moe.route", 10), ("ds.ffn.in", 50)),
    (("ds.mixer.in", 200), ("ds.mixer.kernel", 70), ("ds.mixer.out.narrow", 100)),
)


def _walk(prices):
    """The (name, layer) pairs in the order ``choose_kept`` takes them."""
    from deepspeed_tpu.ops import remat
    return [(name, i) for name in remat.CANDIDATE_NAMES
            for i, layer in enumerate(prices) if any(n == name for n, _ in layer)]


@pytest.mark.parametrize("available", [None, 0, -5, 9])
def test_with_nothing_to_spend_a_layer_keeps_todays_names(available):
    """No budget (a backend that reports no memory gives None), none left,
    or less than the cheapest candidate: ``RESIDUAL_NAMES`` in every layer."""
    from deepspeed_tpu.ops import remat
    plan = remat.choose_kept(available, _PRICES)
    assert plan == (remat.RESIDUAL_NAMES, ) * len(_PRICES)
    assert remat.kept_bytes(plan, _PRICES) == 0


@pytest.mark.parametrize("same", [False, True], ids=["unrolled", "scan"])
def test_the_kept_set_grows_with_the_budget_in_the_lists_order(same):
    """Over every budget from nothing to everything: the kept bytes never
    pass the budget, the kept set only grows, and it is a prefix of the walk
    (router, deep output projection, FFN, input projections, narrow output
    projection, kernels; layers from the first). A ``scan_layers`` body keeps a name in all layers or in none."""
    from deepspeed_tpu.ops import remat
    prices = (_PRICES[0], ) * 3 if same else _PRICES
    walk, before = _walk(prices), set()
    everything = sum(b for layer in prices for _, b in layer)
    for available in range(0, everything + 50, 10):
        plan = remat.choose_kept(available, prices, same_in_all_layers=same)
        assert all(names[:2] == remat.RESIDUAL_NAMES for names in plan)
        kept = {(n, i) for i, names in enumerate(plan) for n in names[2:]}
        assert remat.kept_bytes(plan, prices) <= available
        assert before <= kept
        assert kept == set(walk[:len(kept)])
        if same:
            assert len(set(plan)) == 1
        before = kept
    assert len(before) == len(walk) and remat.kept_bytes(plan, prices) == everything


@pytest.mark.parametrize("available,masks", [(999, 0), (1000, 1), (2999, 2), (3000, 3),
                                             (3010, 3), (3500, 3)])
def test_a_sparse_attentions_mask_is_the_walks_first_name(available, masks):
    """Three layers that each offer ``ds.dsa.mask`` (1,000 B) before the
    names they offered: the masks are taken first, layer by layer; a budget
    that fits some and not all keeps those and nothing after them (the kept
    set is a prefix of the walk); with all three in, what is left is spent
    as a program without the name spends it."""
    from deepspeed_tpu.ops import remat
    assert remat.CANDIDATE_NAMES[0] == remat.DSA_MASK == "ds.dsa.mask"
    prices = tuple(((remat.DSA_MASK, 1000), ) + layer for layer in _PRICES)
    plan = remat.choose_kept(available, prices)
    assert [remat.DSA_MASK in names for names in plan] == [True] * masks + [False] * (3 - masks)
    assert all(names[2] == remat.DSA_MASK for names in plan[:masks])
    without = remat.choose_kept(available - 3000 if masks == 3 else 0, _PRICES)
    assert tuple(tuple(n for n in names if n != remat.DSA_MASK) for names in plan) == without
    assert remat.kept_bytes(plan, prices) == 1000 * masks + remat.kept_bytes(without, _PRICES)


def test_a_backend_without_a_memory_report_makes_no_plan():
    """The CPU here reports no ``bytes_limit``: no plan, the price list is
    never asked for, and ``checkpoint`` of a function that names candidates
    keeps none of them; with a budget forced it keeps what fits."""
    from deepspeed_tpu.observability.xla import named_residual_bytes
    from deepspeed_tpu.ops import remat

    def never():
        raise AssertionError("no budget: nothing to price")

    assert remat.device_memory() is None
    assert remat.plan_for("k", never, rows=1, layer_input_bytes=0) is None
    w = jnp.ones((16, 16), jnp.float32)

    def fn(x):
        h = remat.keep(x @ w, remat.MIXER_IN)
        return jnp.sum(remat.keep(jnp.tanh(h) @ w, remat.MIXER_OUT) ** 2)

    x = jnp.ones((4, 16), jnp.float32)
    assert remat.price_list(fn, x) == ((remat.MIXER_IN, 256), (remat.MIXER_OUT, 256))
    ckpt.configure(partition_activations=False, checkpoint_in_cpu=False)
    grad = lambda: jax.jit(jax.grad(lambda x: ckpt.checkpoint(fn, x))).trace(x)  # noqa: E731
    assert named_residual_bytes(grad().jaxpr) == (0, 512)
    patch = pytest.MonkeyPatch()
    try:
        # 33% of 10,000 B is the reserve: 500 B are left, enough for one of the two
        patch.setattr(remat, "device_memory", lambda: (10000, 6200))
        remat.forget_plans()
        assert named_residual_bytes(grad().jaxpr) == (256, 512)
        patch.setattr(remat, "device_memory", lambda: (10000, 0))
        assert named_residual_bytes(grad().jaxpr) == (256, 512)     # the plan is remembered
        remat.forget_plans()
        assert named_residual_bytes(grad().jaxpr) == (512, 512)
    finally:
        patch.undo()
        remat.forget_plans()


def test_unknown_policy_raises(setup):
    params, x = setup
    ckpt._CONFIG["policy"] = "not_a_policy"
    try:
        with pytest.raises(ValueError):
            ckpt.checkpoint(mlp, params, x)
    finally:
        ckpt._CONFIG["policy"] = None


def test_partition_activations_under_mesh(setup):
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    params, x = setup
    reset_mesh_context()
    dist.init_distributed(mesh_axes={"model": 4, "data": 2})
    ckpt.configure(partition_activations=True)
    try:
        out = ckpt.checkpoint(mlp, params, x)
        # sharded reductions reorder float adds: tolerance reflects that
        np.testing.assert_allclose(float(out), float(mlp(params, x)), rtol=1e-4)
        g = jax.grad(lambda p: ckpt.checkpoint(mlp, p, x))(params)
        ref_g = jax.grad(mlp)(params, x)
        for a, b in zip(g, ref_g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5)
    finally:
        ckpt.configure(partition_activations=False)
        reset_mesh_context()


class TestRNGTracker:

    def test_add_fork_deterministic(self):
        t = ckpt.RNGStatesTracker()
        t.add("stream", 123)
        k1 = t.fork("stream")
        k2 = t.fork("stream")
        assert not np.array_equal(np.asarray(k1), np.asarray(k2))
        # same seed → same sequence
        t2 = ckpt.RNGStatesTracker()
        t2.add("stream", 123)
        np.testing.assert_array_equal(np.asarray(t2.fork("stream")), np.asarray(k1))

    def test_duplicate_add_raises(self):
        t = ckpt.RNGStatesTracker()
        t.add("s", 1)
        with pytest.raises(Exception):
            t.add("s", 2)

    def test_missing_fork_raises(self):
        with pytest.raises(Exception):
            ckpt.RNGStatesTracker().fork("nope")

    def test_model_parallel_seed_distinct_per_rank(self):
        from jax.sharding import Mesh
        import jax.numpy as jnp
        base, mp_key = ckpt.model_parallel_rng_seed(7)
        devs = np.array(jax.devices()[:4]).reshape(4)
        with Mesh(devs, ("model", )):
            from jax.experimental.shard_map import shard_map
            from jax.sharding import PartitionSpec as P
            keys = shard_map(lambda: mp_key().reshape(1, 2),
                             mesh=Mesh(devs, ("model", )), in_specs=(),
                             out_specs=P("model"))()
        keys = np.asarray(keys)
        assert len({tuple(k) for k in keys}) == 4  # all ranks distinct
