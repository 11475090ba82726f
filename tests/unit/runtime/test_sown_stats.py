"""The seam between what an operator sows for the host and the training
engine (``deepspeed_tpu/models/sown.py``): a family is declared once, beside
the operator, and the engine's mutable collections, its reductions, its
publish and ``engine.sown_stats(family)`` read the declaration and name
nobody."""

import dataclasses
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import MeshContext, reset_mesh_context, set_mesh_context
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM, init_llama, sown
from deepspeed_tpu.observability import get_registry

REPO = os.path.join(os.path.dirname(__file__), "..", "..", "..")
FAMILIES = list(sown.FAMILIES.values())
GAUGES = [gauge for family in FAMILIES for gauge in family.gauges]


def _engine(model, params, rows):
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": rows, "steps_per_print": 0,
                "optimizer": {"type": "SGD", "params": {"lr": 1e-2}}})
    return engine


# ---- (a) a family the engine has never heard of ---------------------------

TOY = sown.Family(
    "toy",
    stats={"peak": sown.MAX, "level": sown.MEAN, "each": sown.A_LAYER},
    gauges=(sown.Gauge("ds_toy_peak", "Largest |y| of the toy layers", "peak",
                       sown.steps_max),
            sown.Gauge("ds_toy_level", "Mean y of the toy layers", "level",
                       sown.steps_mean),
            sown.Gauge("ds_toy_seen_total", "Sums of y, added up", "each",
                       sown.steps_total)))


class ToyOperator(nn.Module):
    @nn.compact
    def __call__(self, x):
        y = x * self.param("w", nn.initializers.ones, (x.shape[-1], ))
        if sown.wanted(self, TOY):
            sown.sow(self, TOY, {"each": jnp.sum(y * y), "level": jnp.mean(y),
                                 "peak": jnp.max(jnp.abs(y))})
        return y


class ToyModel(nn.Module):
    sown_families = (TOY, )

    @nn.compact
    def __call__(self, x):
        x = ToyOperator(name="layers_0")(x)
        x = ToyOperator(name="layers_1")(3.0 * x)
        return jnp.mean(x * x)


def test_a_family_declared_in_a_test_is_collected_reduced_published_and_returned():
    x = np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32)
    model = ToyModel()
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    reg = get_registry()
    seen = reg.counter("ds_toy_seen_total").value
    try:
        engine = _engine(model, params, rows=4)
        engine.train_batch(iter([(jnp.asarray(x), )]))
        got = engine.sown_stats("toy")
        assert set(got) == {"peak", "level", "each"}
        np.testing.assert_allclose(got["peak"], 3.0 * np.abs(x).max(), rtol=1e-6)
        np.testing.assert_allclose(got["level"], 2.0 * x.mean(), rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got["each"], [(x * x).sum(), 9.0 * (x * x).sum()],
                                   rtol=1e-5)
        # another family's name is nobody's here, and the toy's is not the router's
        assert engine.sown_stats("moe") is None and engine.moe_stats() is None
        assert engine.sown_stats("no_such_family") is None
        engine.train_batch(iter([(jnp.asarray(x), )]))      # publishes the step before
        assert reg.get("ds_toy_peak").value == pytest.approx(float(got["peak"]))
        assert reg.get("ds_toy_level").value == pytest.approx(float(got["level"]))
        assert reg.counter("ds_toy_seen_total").value - seen == pytest.approx(
            10.0 * float((x * x).sum()), rel=1e-5)
    finally:
        reset_mesh_context()


def test_a_statistic_the_family_does_not_declare_is_refused():
    class Wrong(nn.Module):
        @nn.compact
        def __call__(self, x):
            sown.sow(self, TOY, {"peak": jnp.max(x), "trough": jnp.min(x)})
            return x

    with pytest.raises(KeyError, match="trough"):
        Wrong().apply({}, jnp.ones((2, )), mutable=[TOY.collection])


def test_a_module_that_declares_nothing_sows_nothing_and_keeps_its_aux_loss():
    from deepspeed_tpu.runtime.engine import _as_apply_fns

    class Plain(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.sow("aux_loss", "term", jnp.float32(0.25), reduce_fn=lambda a, b: a + b,
                     init_fn=lambda: jnp.float32(0.0))
            self.sow("toy_stats", "peak", jnp.max(x))
            return jnp.sum(x)

    out, stats = _as_apply_fns(Plain())[1]({}, jnp.ones((2, )))
    assert float(out) == 2.25 and stats == {}


# ---- (b) one list of collections -------------------------------------------

@pytest.fixture(scope="module")
def collections_asked_for(request):
    """What a scanned model's ``nn.scan`` was told to carry and what the
    engine's apply made mutable, from one abstract trace."""
    from deepspeed_tpu.runtime.engine import _as_apply_fns
    monkeypatch = pytest.MonkeyPatch()
    request.addfinalizer(monkeypatch.undo)
    seen = {}
    scan, apply = nn.scan, nn.Module.apply

    def scan_seen(body, *args, **kwargs):
        seen["variable_axes"] = set(kwargs["variable_axes"])
        return scan(body, *args, **kwargs)

    def apply_seen(self, variables, *args, mutable=False, **kwargs):
        if isinstance(self, LlamaForCausalLM):
            seen["mutable"] = list(mutable)
        return apply(self, variables, *args, mutable=mutable, **kwargs)

    monkeypatch.setattr(nn, "scan", scan_seen)
    monkeypatch.setattr(nn.Module, "apply", apply_seen)
    model = LlamaForCausalLM(LlamaConfig.tiny(dtype=jnp.float32, scan_layers=True))
    ids = jnp.zeros((1, 8), jnp.int32)
    jax.eval_shape(lambda: _as_apply_fns(model)[1](
        model.init(jax.random.PRNGKey(0), ids)["params"], ids, labels=ids))
    return seen


@pytest.mark.parametrize("family", FAMILIES, ids=lambda family: family.name)
def test_the_engines_mutable_list_the_scans_axes_and_the_table_are_one_set(
        family, collections_asked_for):
    seen = collections_asked_for
    assert family in LlamaForCausalLM.sown_families
    assert family.collection == family.name + "_stats"
    assert family.collection in seen["mutable"]
    assert family.collection in seen["variable_axes"]
    declared = {"aux_loss", *(f.collection for f in FAMILIES)}
    assert set(seen["mutable"]) == declared and len(seen["mutable"]) == len(declared)
    assert seen["variable_axes"] == declared | {"params"}


def test_the_engine_names_no_family_and_does_not_import_the_models():
    with open(os.path.join(REPO, "deepspeed_tpu", "runtime", "engine.py")) as f:
        lines = f.read().splitlines()
    named = [line.strip() for line in lines
             if re.search(r"kda|gdn|selscan|diffattn|ssm_|mla_|dsa_", line)]
    assert sorted(named) == sorted(
        f'{name}_stats = partialmethod(sown_stats, "{name}")'
        for name in ("ssm", "kda", "gdn", "selscan", "diffattn", "mla", "dsa"))
    assert not [line for line in lines if re.search(r"import.*\bmodels\b|\.models\b", line)]


# ---- (c) every declared series is documented -------------------------------

@pytest.mark.parametrize("gauge", GAUGES, ids=lambda gauge: gauge.name)
def test_every_declared_gauge_and_counter_stands_in_the_observability_catalog(gauge):
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        catalog = f.read()
    row = [line for line in catalog.splitlines()
           if line.startswith("|") and f"`{gauge.name}`" in line.split("|")[1]]
    assert len(row) == 1, gauge.name
    assert row[0].split("|")[2].strip() == ("counter" if gauge.counter else "gauge")
    assert gauge.name.endswith("_total") == gauge.counter
    assert gauge.help and gauge.help == " ".join(gauge.help.split())


# ---- (d) the reductions, by hand --------------------------------------------

A, B = np.array([1.0, -4.0], np.float32), np.array([2.5, 0.5], np.float32)
ROUTED = np.array([[3, 0, 1], [1, 1, 2]], np.int32)


@pytest.mark.parametrize("how,unscanned,scanned,want", [
    (sown.MAX, [A[0], B[0]], [np.stack([A[0], B[0]])], 2.5),
    (sown.MEAN, [A[0], B[0]], [np.stack([A[0], B[0]])], 1.75),
    (sown.SUM, [A[1], B[1]], [np.stack([A[1], B[1]])], -3.5),
    (sown.A_LAYER, [A[1], B[1]], [np.stack([A[1], B[1]])], [-4.0, 0.5]),
    (sown.SUM_LAST_KEPT, [ROUTED[0], ROUTED[1]], [ROUTED], [4, 1, 3]),
], ids=["max", "mean", "sum", "a_layer", "sum_last_kept"])
def test_the_reductions_over_the_layers(how, unscanned, scanned, want):
    """A leaf a layer, and one leaf with the layers as its leading axis (a
    layer scan): the same value of the step."""
    for leaves in (unscanned, scanned):
        got = how.across([jnp.asarray(leaf) for leaf in leaves])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want, got.dtype))
    # two calls of one module fold as the layers do
    folded = how.within(jnp.asarray(unscanned[0]), jnp.asarray(unscanned[1]))
    np.testing.assert_array_equal(
        np.asarray(folded), np.maximum(*unscanned) if how is sown.MAX
        else unscanned[0] + unscanned[1])


@pytest.mark.parametrize("over_steps,want", [
    (sown.steps_max, 2.5), (sown.steps_mean, (-1.5 + 1.5) / 2),
    (sown.last_step, 1.5), (sown.steps_total, 0.0),
], ids=["max", "mean", "last", "total"])
def test_the_reductions_over_the_steps_of_a_publish(over_steps, want):
    """Two dispatches: one step's vector a layer, and a K-step dispatch's
    ``[K]``."""
    assert over_steps([A, B]) == pytest.approx(want)


def test_a_k_step_dispatchs_expert_counts_are_summed_over_its_steps():
    moe = sown.FAMILIES["moe"]
    steps = [{"expert_counts": ROUTED, "rows_held": np.array([2, 3], np.int32)},   # K = 2
             {"expert_counts": ROUTED[0], "rows_held": np.int32(1)}]               # a step
    load = moe.derive(steps)
    # counts [7, 1, 4]: the busiest over the mean, and 6 rows held of 12
    assert load == {"load_max_over_mean": pytest.approx(7 / 4),
                    "rows_held_share": pytest.approx(0.5)}
    assert sown.steps_total([s["expert_counts"] for s in steps]) == 12.0
    assert "rows_held_share" not in moe.derive([{"expert_counts": ROUTED}])


def test_the_derived_views_of_the_sparse_attention_and_the_diffusion_families():
    step = {"chosen_pairs": np.array([3, 5], np.int32),
            "causal_pairs": np.array([10.0, 10.0], np.float32),
            "kth_score_mean": np.float32(0.25), "masks_kept": np.int32(1)}
    view = sown.FAMILIES["dsa"].derive([step])
    assert view == {"chosen_pairs": 8, "causal_pairs": 20, "chosen_pairs_by_layer": [3, 5],
                    "chosen_share": 0.4, "kth_score_mean": 0.25, "masks_kept": 1}
    assert [type(view[k]) for k in ("chosen_pairs", "causal_pairs", "chosen_share",
                                    "kth_score_mean", "masks_kept")] == [int, int, float,
                                                                         float, int]
    assert sown.FAMILIES["dsa"].derive([step, step])["chosen_share"] == 0.4
    view = sown.FAMILIES["diffusion"].derive([{
        "tokens": np.float32(16), "masked_tokens": np.float32(4), "t_sum": np.float32(1.0)}])
    assert view == {"masked_tokens": 4, "mask_rate": 0.25, "t_mean_masked": 0.25}
    assert type(view["masked_tokens"]) is int


def test_a_family_takes_its_own_out_of_a_steps_statistics():
    step = {"expert_counts": 1, "aux_loss": 2, "ssm_state_absmax": 3, "attn_gate_mean": 4,
            "gdn_state_absmax": 5}
    assert sown.FAMILIES["moe"].of(step) == {"expert_counts": 1, "aux_loss": 2}
    assert sown.FAMILIES["ssm"].of(step) == {"state_absmax": 3}
    assert sown.FAMILIES["attn"].of(step) == {"gate_mean": 4}
    assert sown.FAMILIES["kda"].of(step) == {}


def _step(k):
    """What a step of every family returns, written out by hand (``k`` scales
    some of it; ``k == 2`` is a two-step dispatch where a shape shows it)."""
    f32, i32 = np.float32, np.int32
    return {"expert_counts": np.array([[3, 0, 5, 1]] * k, i32).squeeze(),
            "group_counts": np.array([4, 5], i32), "rows_held": i32(5 * k),
            "share_fallback": i32(1), "aux_loss": f32(0.02 * k),
            "ssm_state_absmax": f32(1.5 * k), "ssm_dt_mean": f32(.25),
            "mla_latent_rms": f32(.7), "mla_k_rope_rms": f32(.3 * k),
            "diffusion_tokens": f32(64), "diffusion_masked_tokens": f32(16 * k),
            "diffusion_t_sum": f32(9.5),
            "dsa_chosen_pairs": np.array([900, 904], i32) * k,
            "dsa_causal_pairs": np.array([2080., 2080.], f32),
            "dsa_kth_score_mean": f32(-.12), "dsa_masks_kept": i32(1),
            "kda_state_absmax": f32(2.5), "kda_decay_mean": f32(.99), "kda_beta_mean": f32(.5),
            "kda_fused_rows": f32(1), "kda_head_block": f32(4), "kda_grid_steps": f32(2048),
            "gdn_state_absmax": f32(3.5 * k), "gdn_decay_mean": f32(.82),
            "gdn_beta_mean": f32(.5), "gdn_fused_rows": f32(1), "gdn_head_block": f32(4),
            "gdn_grid_steps": f32(64),
            "selscan_state_absmax": f32(1.25), "selscan_dt_mean": f32(.05 * k),
            "diffattn_lambda_mean": np.array([.2, .4, .6], f32), "attn_gate_mean": f32(.5),
            "loop_exit_mass": np.array([.5, .25, .125, .125], f32),
            "loop_ce": np.array([5., 4., 3., 2.], f32) * k, "loop_exit_entropy": f32(1.2)}


# after a publish of steps 1 and 2 and a second of step 3: a gauge holds the
# second publish's value, a counter the sum of both (the parent's own
# `_publish_moe_stats` gave these for the same steps)
PUBLISHED = {
    "ds_moe_tokens_routed_total": 54.0, "ds_moe_expert_load_max_over_mean": 20 / 9,
    "ds_moe_rows_held_total": 30.0, "ds_moe_rows_held_share": 5 / 9,
    "ds_moe_share_fallback_total": 3.0, "ds_moe_aux_loss": 0.06,
    "ds_ssm_state_absmax": 4.5, "ds_ssm_dt_mean": 0.25,
    "ds_mla_latent_rms": 0.7, "ds_mla_k_rope_rms": 0.9,
    "ds_diffusion_masked_tokens_total": 96.0, "ds_diffusion_mask_rate": 0.75,
    "ds_dsa_chosen_pairs_total": 10824.0, "ds_dsa_chosen_share": 5412 / 4160,
    "ds_kda_state_absmax": 2.5, "ds_kda_decay_mean": 0.99, "ds_kda_fused_rows": 1.0,
    "ds_kda_head_block": 4.0, "ds_kda_grid_steps": 2048.0,
    "ds_selscan_state_absmax": 1.25, "ds_selscan_dt_mean": 0.15,
    "ds_diffattn_lambda_mean": 0.4, "ds_gdn_state_absmax": 10.5, "ds_gdn_decay_mean": 0.82,
    "ds_attn_gate_mean": 0.5,
    # a series a pass (``Gauge.label``): the second publish's step
    "ds_loop_exit_mass": [.5, .25, .125, .125], "ds_loop_ce": [15., 12., 9., 6.],
    "ds_loop_exit_entropy": 1.2}


def _series(gauge):
    """The registry's series of ``gauge``: one, or one a position of its label."""
    reg = get_registry()
    if gauge.label is None:
        return [reg.get(gauge.name)]
    return [reg.get(gauge.name, {gauge.label: str(i)})
            for i in range(len(PUBLISHED[gauge.name]))]


@pytest.fixture(scope="module")
def published(request):
    import types
    from deepspeed_tpu.runtime import engine as engine_module
    monkeypatch = pytest.MonkeyPatch()
    request.addfinalizer(monkeypatch.undo)
    monkeypatch.setattr(engine_module, "host_fetch", lambda tree: tree)
    reg = get_registry()
    before = {g.name: reg.counter(g.name, g.help).value for g in GAUGES if g.counter}
    holder = types.SimpleNamespace(_kernel_line_logged=True,
                                   _sown_families=LlamaForCausalLM.sown_families)
    for steps in ([_step(1), _step(2)], [_step(3)]):
        holder._sown_pending = steps
        engine_module.DeepSpeedTpuEngine._publish_sown_stats(holder)
        assert holder._sown_pending == []
    return {g.name: [m.value - before.get(g.name, 0.0) for m in _series(g)] for g in GAUGES}


@pytest.mark.parametrize("gauge", GAUGES, ids=lambda gauge: gauge.name)
def test_a_publish_sets_every_declared_series_from_hand_built_steps(gauge, published):
    assert set(PUBLISHED) == {g.name for g in GAUGES}
    assert published[gauge.name] == pytest.approx(
        np.ravel(PUBLISHED[gauge.name]).tolist(), rel=1e-6)
    for series in _series(gauge):
        assert type(series).__name__ == ("Counter" if gauge.counter else "Gauge")
        assert series.help == gauge.help


# ---- (e) a scanned model's statistics reach the host -----------------------

def test_a_scanned_model_of_gated_attention_layers_returns_its_gates_mean():
    """``attn_stats`` was not among the scan's ``variable_axes``: flax dropped
    what the scanned layers sowed and ``engine.attn_stats()`` was ``None``."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_output_gate="elementwise",
                           hidden_size=32, intermediate_size=64, vocab_size=64)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 16)), jnp.int32)
    _, scanned = init_llama(dataclasses.replace(cfg, scan_layers=True), seed=3)
    # the same weights, a tree a layer
    stack = scanned["model"]["layers"]["layer"]
    unscanned = {**scanned, "model": {
        **{name: tree for name, tree in scanned["model"].items() if name != "layers"},
        **{f"layers_{i}": jax.tree_util.tree_map(lambda x: x[i], stack)
           for i in range(cfg.num_hidden_layers)}}}
    model = LlamaForCausalLM(cfg)
    by_layer = jax.jit(lambda p: model.apply({"params": p}, ids, mutable=["attn_stats"])[1])(
        unscanned)["attn_stats"]["model"]
    want = np.mean([by_layer[f"layers_{i}"]["self_attn"]["gate_mean"]
                    for i in range(cfg.num_hidden_layers)])
    reg = get_registry()
    try:
        engine = _engine(LlamaForCausalLM(dataclasses.replace(cfg, scan_layers=True)),
                         scanned, rows=2)
        engine.train_batch(iter([(ids, ids)]))
        got = engine.attn_stats()
        assert got is not None and set(got) == {"gate_mean"}
        assert got == engine.sown_stats("attn")
        engine.train_batch(iter([(ids, ids)]))
        assert reg.get("ds_attn_gate_mean").value == pytest.approx(float(got["gate_mean"]))
    finally:
        reset_mesh_context()
    assert 0.3 < float(got["gate_mean"]) < 0.7
    np.testing.assert_allclose(got["gate_mean"], want, rtol=1e-6)
