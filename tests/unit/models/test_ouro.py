"""Ouro (a looped language model) on the CPU at a small size: the program
against ``benchmark/reference/ouro.py`` on seeded weights (loss, every pass's
logits, every gradient leaf, the gate's), ``total_ut_steps`` 1 as the
``sandwich_norm`` model it was, the scanned stack against the unrolled one, the
tie test (an untied stack of ``T x N`` layers holding copies of the weights
gives, summed over the copies, the looped model's gradients), the loss without
an entropy weight, and what a module called once a pass sows."""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ouro as reference  # noqa: E402
from deepspeed_tpu.models import sown  # noqa: E402
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM,  # noqa: E402
                                        RMSNorm, cross_entropy_loss, exit_distribution,
                                        init_llama)

T, N, SEQ, VOCAB = 4, 2, 24, 96
FILE = {"num_hidden_layers": N, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "total_ut_steps": T,
        "exit_entropy_weight": 0.05}


def config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128, num_hidden_layers=N,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16, rms_norm_eps=1e-6,
        rope_theta=1e6, max_position_embeddings=64, sandwich_norm=True, total_ut_steps=T,
        exit_gate=True, exit_entropy_weight=0.05, dtype=jnp.float32, attn_impl="xla"), **over})


@pytest.fixture(scope="module")
def small():
    cfg = config()
    model, params = init_llama(cfg, seed=11)
    # weights that tell the parts apart: norms off 1, a gate with a bias
    rng = np.random.default_rng(2)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.2 * rng.standard_normal(a.shape).astype(np.float32)
        if "norm" in jax.tree_util.keystr(path) or "early_exit_gate" in jax.tree_util.keystr(path)
        else a, params)
    ids = jnp.asarray(rng.integers(0, VOCAB, (2, SEQ)), jnp.int32)
    return cfg, model, params, ids


def stacked(params):
    """The unrolled tree's layers as the scanned model's ONE ``layers/layer``."""
    m = params["model"]
    layers = [m[f"layers_{i}"] for i in range(N)]
    rest = {k: v for k, v in m.items() if not k.startswith("layers_")}
    return {"model": {**rest, "layers": {"layer": jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *layers)}}}


def test_the_program_matches_the_reference_loss_logits_and_every_gradient(small):
    cfg, model, params, ids = small
    at = np.stack([np.arange(0, SEQ - 1, 3)] * 2)
    want = reference.step_parts(params, ids, FILE, at)
    (loss, mods), grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, labels=ids, mutable=["loop_stats"]),
        has_aux=True)(params)
    np.testing.assert_allclose(loss, want["ce"], rtol=2e-6)
    loop = mods["loop_stats"]
    np.testing.assert_allclose(loop["ce"], want["ce_pass"], rtol=2e-6)
    np.testing.assert_allclose(loop["exit_mass"], want["exit_mass"], rtol=2e-6)
    np.testing.assert_allclose(loop["exit_entropy"], want["exit_entropy"], rtol=2e-6)
    assert abs(float(np.sum(loop["exit_mass"])) - 1) < 1e-6
    for row in range(2):
        logits = model.apply({"params": params}, ids[row:row + 1],
                             logits_to_keep=jnp.asarray(at[row]), all_passes=True)[0]
        assert logits.shape == (T, at.shape[1], VOCAB)
        np.testing.assert_allclose(np.asarray(logits)[[0, -1]], want["logits"][row], rtol=2e-4, atol=2e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want["grads"])):
        assert np.any(w), jax.tree_util.keystr(path)
        err = np.linalg.norm(g - w) / np.linalg.norm(w)
        assert err < 2e-4, (jax.tree_util.keystr(path), err)
    assert {"kernel", "bias"} == set(grads["model"]["early_exit_gate"])
    # the last pass's logits are what the model hands back without labels
    np.testing.assert_allclose(
        model.apply({"params": params}, ids),
        model.apply({"params": params}, ids, all_passes=True)[:, -1], rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def sound(small):
    _, _, params, ids = small
    return reference.step_parts(params, ids[:1], FILE, np.arange(4)[None])


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_reference_is_another_model(small, sound, wrong):
    _, _, params, ids = small
    other = reference.step_parts(params, ids[:1], FILE, np.arange(4)[None], wrong={wrong})
    gate = [np.linalg.norm(a - b) for a, b in zip(
        jax.tree_util.tree_leaves(sound["grads"]["model"]["early_exit_gate"]),
        jax.tree_util.tree_leaves(other["grads"]["model"]["early_exit_gate"]))]
    assert abs(sound["ce"] - other["ce"]) > 1e-5 or max(gate) > 1e-6


# a seeded two-layer ``sandwich_norm`` model at the commit before the loop: its
# loss, the sum of its gradients' magnitudes and of its logits, as hex floats
BEFORE_THE_LOOP = {False: ("0x1.7992720000000p+2", "0x1.934f480000000p+10", "0x1.a814e60000000p+5"),
                   True: ("0x1.7e6cf80000000p+2", "0x1.605f1c0000000p+10", "0x1.418e400000000p+5")}


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
def test_one_pass_without_a_gate_is_the_sandwich_norm_model_bit_for_bit(scan):
    cfg = LlamaConfig.tiny(sandwich_norm=True, num_hidden_layers=2, dtype=jnp.float32,
                           scan_layers=scan, total_ut_steps=1)
    assert not cfg.looped_
    model, params = init_llama(cfg, seed=3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    loss, grads = jax.value_and_grad(lambda p: model.apply({"params": p}, ids, labels=ids))(params)
    got = (float(loss).hex(),
           float(sum(jnp.abs(g).sum() for g in jax.tree_util.tree_leaves(grads))).hex(),
           float(model.apply({"params": params}, ids).sum()).hex())
    assert got == BEFORE_THE_LOOP[scan]
    # and ONE pass through the looped path (a gate asked for, none to read) is
    # the same numbers: the loop adds nothing of its own
    one = LlamaForCausalLM(dataclasses.replace(cfg, exit_gate=True, exit_entropy_weight=0.05))
    assert one.config.looped_
    np.testing.assert_array_equal(one.apply({"params": params}, ids, labels=ids), loss)
    np.testing.assert_array_equal(one.apply({"params": params}, ids),
                                  model.apply({"params": params}, ids))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
def test_the_scanned_stack_equals_the_unrolled_one(small, remat):
    cfg, _, params, ids = small
    cfg = dataclasses.replace(cfg, remat=remat, ce_chunk_size=32)
    unrolled = LlamaForCausalLM(cfg)
    scanned = LlamaForCausalLM(dataclasses.replace(cfg, scan_layers=True))
    loss_u, grads_u = jax.value_and_grad(
        lambda p: unrolled.apply({"params": p}, ids, labels=ids))(params)
    loss_s, grads_s = jax.value_and_grad(
        lambda p: scanned.apply({"params": p}, ids, labels=ids))(stacked(params))
    np.testing.assert_allclose(loss_s, loss_u, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(stacked(grads_u)),
                    jax.tree_util.tree_leaves(grads_s)):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)
    shapes = jax.tree_util.tree_map(jnp.shape, init_llama(scanned.config, seed=0)[1])
    assert shapes == jax.tree_util.tree_map(jnp.shape, stacked(params))


def test_the_tie_an_untied_stack_of_copies_sums_to_the_looped_models_gradients(small):
    """``T x N`` layers, T final norms, T - 1 gates, each holding a COPY of
    the looped model's weights, applied with the layer and norm modules
    themselves: the loss is the looped model's and the copies' gradients add up
    to its gradients, leaf by leaf."""
    cfg, model, params, ids = small
    m = params["model"]
    copies = {"layers": [[m[f"layers_{i}"] for i in range(N)] for _ in range(T)],
              "norm": [m["norm"]] * T, "gate": [m["early_exit_gate"]] * (T - 1),
              "embed": m["embed_tokens"]["embedding"], "head": m["lm_head"]["kernel"]}
    positions = jnp.broadcast_to(jnp.arange(SEQ)[None], ids.shape)
    from deepspeed_tpu.models.llama import precompute_rope
    cos, sin = precompute_rope(cfg.head_dim_, cfg.max_position_embeddings, cfg.rope_theta)

    def untied(c):
        x, streams, gates = c["embed"][ids], [], []
        for t in range(T):
            for i in range(N):
                x = LlamaDecoderLayer(cfg, i).apply({"params": c["layers"][t][i]},
                                                    x, cos, sin, positions)
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype).apply({"params": c["norm"][t]}, x)
            streams.append(x)
            if t < T - 1:
                gates.append(x @ c["gate"][t]["kernel"][:, 0] + c["gate"][t]["bias"][0])
        p, entropy = exit_distribution(jnp.stack(gates, axis=1))
        logits = jnp.einsum("btsh,hv->btsv", jnp.stack(streams, axis=1), c["head"])
        gold = jnp.take_along_axis(logits[:, :, :-1], ids[:, None, 1:, None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits[:, :, :-1], axis=-1) - gold
        return ((p[:, :, :-1] * nll).sum(axis=1) - 0.05 * entropy[:, :-1]).mean()

    loss, grads = jax.value_and_grad(lambda p: model.apply({"params": p}, ids, labels=ids))(params)
    loss_c, grads_c = jax.value_and_grad(untied)(copies)
    np.testing.assert_allclose(loss_c, loss, rtol=1e-6)
    add = lambda trees: jax.tree_util.tree_map(lambda *a: sum(a), *trees)     # noqa: E731
    summed = {"embed_tokens": {"embedding": grads_c["embed"]},
              "lm_head": {"kernel": grads_c["head"]}, "norm": add(grads_c["norm"]),
              "early_exit_gate": add(grads_c["gate"]),
              **{f"layers_{i}": add([grads_c["layers"][t][i] for t in range(T)])
                 for i in range(N)}}
    for (path, g), c in zip(jax.tree_util.tree_flatten_with_path(grads["model"])[0],
                            jax.tree_util.tree_leaves({k: summed[k] for k in sorted(summed)})):
        np.testing.assert_allclose(c, g, rtol=2e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # one copy's share is NOT the whole: the passes all carry gradient
    one = grads_c["layers"][0][0]["mlp"]["down_proj"]["kernel"]
    assert np.linalg.norm(one) < 0.9 * np.linalg.norm(
        grads["model"]["layers_0"]["mlp"]["down_proj"]["kernel"])


@pytest.mark.parametrize("chunk", [None, 32], ids=["dense", "chunked"])
def test_without_an_entropy_weight_the_loss_is_the_last_passs_ce(small, chunk):
    cfg, _, params, ids = small
    cfg = dataclasses.replace(cfg, exit_entropy_weight=None, ce_chunk_size=chunk)
    model = LlamaForCausalLM(cfg)
    (loss, mods), grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, labels=ids, mutable=["loop_stats"]),
        has_aux=True)(params)
    want = cross_entropy_loss(model.apply({"params": params}, ids), ids)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert not mods.get("loop_stats")       # no exit distribution, nothing sown of it
    gate = grads["model"]["early_exit_gate"]
    assert not np.any(gate["kernel"]) and not np.any(gate["bias"])
    # a model with no gate at all has no such leaf and the same loss
    bare = LlamaForCausalLM(dataclasses.replace(cfg, exit_gate=False))
    rest = {"model": {k: v for k, v in params["model"].items() if k != "early_exit_gate"}}
    np.testing.assert_array_equal(bare.apply({"params": rest}, ids, labels=ids), loss)
    assert "early_exit_gate" not in init_llama(bare.config, seed=0)[1]["model"]


@pytest.mark.parametrize("call,said", [
    (dict(loss_weights=True), "loss_weights are not its argument"),
    (dict(all_passes=True), "for a call without labels"),
    (dict(logits_to_keep=True), "for a call without labels")])
def test_the_exit_loss_refuses_what_it_would_drop(small, call, said):
    """Token weights beside the exit distribution's own, or a request for
    logits beside labels: refused, not answered with another loss."""
    cfg, model, params, ids = small
    assert cfg.exit_loss_
    given = {"loss_weights": jnp.ones(ids.shape, jnp.float32),
             "logits_to_keep": jnp.arange(4), "all_passes": True}
    with pytest.raises(ValueError, match=said):
        model.apply({"params": params}, ids, labels=ids,
                    **{name: given[name] for name in call})


def test_the_exit_distribution_sums_to_one_and_survives_a_saturated_gate():
    gates = jnp.asarray([[[-40.0, 0.0, 40.0], [0.0, 0.0, 40.0], [3.0, -3.0, 0.0]]])  # [1, 3, 3]
    p, entropy = exit_distribution(gates)
    assert p.shape == (1, 4, 3) and np.all(np.isfinite(p)) and np.all(np.isfinite(entropy))
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[0, :, 2], [1.0, 0.0, 0.0, 0.0], atol=1e-12)
    want = reference.exit_distribution(gates[0])
    np.testing.assert_allclose(p[0], want, rtol=1e-5, atol=1e-12)
    grads = jax.grad(lambda g: exit_distribution(g)[1].sum())(gates)
    assert np.all(np.isfinite(grads))


class _Sower(nn.Module):
    """Sows one value a call under each kind of reduction."""
    @nn.compact
    def __call__(self, x):
        sown.sow(self, "ssm", {"state_absmax": x, "dt_mean": x})      # MAX, MEAN
        sown.sow(self, "moe", {"rows_held": x})                       # SUM
        return x


class _FourCalls(nn.Module):
    @nn.compact
    def __call__(self, values):
        one = _Sower(name="one")
        with sown.repeated(len(values)):
            for v in values:
                one(v)
        return values


def test_four_calls_of_one_module_reduce_by_within_as_the_family_says():
    values = jnp.asarray([2.0, 8.0, 4.0, 6.0])
    _, mods = _FourCalls().apply({}, values, mutable=["ssm_stats", "moe_stats"])
    got = {**mods["ssm_stats"]["one"], **mods["moe_stats"]["one"]}
    assert float(got["state_absmax"]) == 8.0        # a largest value: the calls' largest
    assert float(got["dt_mean"]) == 5.0             # a mean: the calls' mean, not their sum
    assert float(got["rows_held"]) == 20.0          # a count: the calls' sum
    assert sown.MEAN.averaged and not sown.MAX.averaged and not sown.SUM.averaged


def test_attn_stats_of_an_attention_module_called_four_times_is_the_passes_mean():
    """Gated softmax attention under the loop: ``gate_mean`` of a layer is the
    mean over its four calls (each pass's own mean by hand: a model of t passes
    sows the mean of the first t), never their sum."""
    base = config(attn_output_gate="elementwise", exit_gate=False, exit_entropy_weight=None)
    _, params = init_llama(base, seed=5)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, (2, SEQ)), jnp.int32)
    running = []
    for t in range(1, T + 1):
        model = LlamaForCausalLM(dataclasses.replace(base, total_ut_steps=t))
        _, mods = model.apply({"params": params}, ids, mutable=["attn_stats"])
        running.append(np.asarray([mods["attn_stats"]["model"][f"layers_{i}"]["self_attn"]
                                   ["gate_mean"] for i in range(N)]))
    each = [running[0]] + [(t + 1) * running[t] - t * running[t - 1] for t in range(1, T)]
    assert all(np.all((0.0 < e) & (e < 1.0)) for e in each)         # a sigmoid's means
    assert max(np.ptp([e[i] for e in each]) for i in range(N)) > 1e-4   # the passes differ
    np.testing.assert_allclose(running[-1], np.mean(each, axis=0), rtol=1e-5)
    step = sown.FAMILIES["attn"].stats["gate_mean"].across(list(running[-1]))
    assert 0.0 < float(step) < 1.0
