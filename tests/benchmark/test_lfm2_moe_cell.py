"""The LFM2 cell's own tests: its configuration against the published values,
its parameter and FLOP count by hand, its four readers on made-up traces, its
manifest entries, and a rehearsal of the runner end to end. All on the CPU; no
number here is a measurement."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import manifest_checks  # noqa: E402  (beside this file)
from benchmark import conv_cost, lfm2_cost, moe_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-lfm2moe-1chip-seq8k", "lfm2-24b-a2b-ep8-train1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
# config.json at SOURCE, key by key as published (40 layers, 64 experts, a
# vocabulary of 65,536; this cell runs layers 1-5, holds 8 and an eighth)
PERIOD = ["conv", "conv", "full_attention", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PERIOD * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
           "vocab_size"]
NEW = {"kernel.moe_gmm_held_roofline": "kernel", "kernel.short_conv_roofline": "kernel",
       "conv.kernel_ms_per_step": "short convolution operator",
       "moe.rows_held_pct": "router"}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "kernel.flash_fwd_roofline",
          "kernel.flash_bwd_roofline", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "moe.gmm_ms_per_step", "moe.load_max_over_mean"]
# device events as a v5e's trace would name them: the share's grouped matmuls
# over the static 32,768-row array, the convolution kernels, flash at head 64
GMM_UP = ("%ragged-dot-none.7 = bf16[32768,1536]{1,0:T(8,128)(2,1)} custom-call("
          "s32[1]{0:T(128)} %get-tuple-element.16, s32[9]{0:T(128)S(1)} %copy-done.19")
GMM_DOWN = GMM_UP.replace("none.7", "none.6").replace("[32768,1536]", "[32768,2048]")
GMM_DW = ("%ragged-dot-none.1 = bf16[8,2048,1536]{2,1,0:T(8,128)(2,1)} custom-call("
          "s32[1]{0:T(128)} %get-tuple-element.8")
CONV_FWD = ("%short_conv_fwd.3 = bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)} custom-call("
            "bf16[4,8192,6144]{2,1,0:T(8,128)(2,1)} %fusion.12")
CONV_BWD = ("%short_conv_bwd.2 = (bf16[4,8192,6144]{2,1,0:T(8,128)(2,1)}, "
            "f32[128,8,2048]{2,1,0:T(8,128)}) custom-call(bf16[4,8192,6144]")
FLASH = "%flash_fwd.1 = (bf16[32,4,8192,64]{3,2,1,0}, f32[32,4,8192,1]{3,2,1,0}) custom-call("


def config() -> dict:
    return load_json("configs", CONFIG + ".json")


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_configuration_is_the_published_one_but_for_the_stated_cuts():
    cfg = config()
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(REDUCED)
    # and with the catalog's row of that source, on a machine whose catalog has one
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [r for r in map(json.loads, f) if r["source_url"] == SOURCE]
    for row in rows:
        assert row["config"] == PUBLISHED
        assert (row["layers"], row["dense_width"], row["expert_width"]) == (40, 11776, 1536)
    # the cut: one leading dense layer and one whole period of what follows
    # (published layers 1-5), 8 of 64 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6] \
        == ["conv", "full_attention", "conv", "conv", "conv"]
    after = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert len(after) == 4 and sorted(after) == sorted(PERIOD)      # the 1 : 3 ratio
    assert cfg["num_experts"] == 8 and cfg["vocab_size"] == 8192 == 65536 // 8
    assert "each layer shared over 8 chips" in cfg["deployment"]
    for said in ("experts 0-7", "rows 0-8191", "layers 1-5", "pipeline stages"):
        assert said in cfg["deployment"], said
    assert set(cfg["assumed"]) >= {"tie_word_embeddings", "head_dim", "qk_norm",
                                   "conv_split_order", "renorm_eps", "expert_bias",
                                   "tokens_per_step"}
    assert cfg["vocab_size"] % cfg["ce_chunk_size"] == 0    # no padded head
    assert cfg["ds_config"] == {"zero_optimization": {"stage": 0}}
    assert cfg["remat"] is True and cfg["remat_policy"] is None and cfg["dtype"] == "bfloat16"
    # no width is among the cuts, nor the experts a token chooses
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
                "conv_L_cache", "rope_parameters"):
        assert key not in REDUCED and cfg[key] == PUBLISHED[key]


def test_manifest_entries_of_the_cell_and_the_checks_every_manifest_passes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    manifest_checks.check_entries(load_manifest())
    cells = {w["name"]: w for w in admitted["workloads"]}
    # the admitted cells keep their order; this one comes after them
    assert list(cells)[:3] == ["train-zero3-seq4k", "train-olmoe-1chip-seq4k", CELL]
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "lfm2moe-1chip-seq8k"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 4, "seq_len": 8192,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_lfm2_moe" and cell["why"] == cells[CELL]["why"]
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(layers) == set(NEW) | set(SHARED) | {
        "setup.compile_s", "setup.programs", "setup.cache_misses"}
    # not the all-experts roofline: it reads a row call's rows from the
    # event's own shape, which for a share is the padded length
    assert "kernel.moe_gmm_roofline" not in layers
    assert "coll.exposed_ms_per_step" not in layers            # one chip
    for name, layer in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"] == [CELL]
    assert [x["name"] for x in admitted["per_layer"]][-4:] == list(NEW)   # appended
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        assert metric["workloads"][-1] == CELL                 # at the end of each list


def test_parameters_and_flops_by_hand():
    cfg = config()
    h = 2048
    conv = 3 * h * h + h * h + 3 * h                     # in_proj, out_proj, taps
    attn = 2 * h * 32 * 64 + 2 * h * 8 * 64 + 2 * 64     # q, o; k, v; two head norms
    dense = 3 * h * 11776
    moe = h * 64 + 64 + 8 * 3 * h * 1536                 # router, its bias, 8 experts
    layers = (conv + dense) + (attn + moe) + 3 * (conv + moe) + 5 * 2 * h
    assert lfm2_cost.router_width(cfg) == 64
    assert lfm2_cost.param_count(cfg) == layers + 8192 * h + h == 469_285_248
    # ISSUE 31's table, in millions
    assert 8192 * h == pytest.approx(16.8e6, rel=3e-3)
    assert conv + dense == pytest.approx(89.1e6, rel=1e-3)
    assert attn + moe == pytest.approx(86.1e6, rel=1e-3)
    assert 3 * (conv + moe) == pytest.approx(277.2e6, rel=1e-3)
    # uncut: 64 experts a layer, two dense layers, 30 conv and 10 attention
    whole = dict(cfg, **cfg["published"])
    assert lfm2_cost.router_width(whole) == 64
    assert lfm2_cost.param_count(whole) == pytest.approx(23.8e9, rel=5e-3)   # "24B"
    # forward MFLOP a token at 8,192-token sequences, the ISSUE's shares
    conv_f, attn_f = 2 * 4 * h * h, 2 * (2 * h * 2048 + 2 * h * 512)
    scores = 4 * 32 * 64 * 4096.5
    dense_f, router_f = 2 * dense, 2 * h * 64
    experts_f = 0.5 * 2 * 3 * h * 1536                   # 4 * 8 / 64 experts a token
    head_f = 2 * h * 8192
    assert lfm2_cost.experts_held_per_token(cfg) == 0.5
    for got, want in ((conv_f, 33.6e6), (attn_f, 21.0e6), (scores, 33.6e6),
                      (dense_f, 144.7e6), (router_f, 0.26e6), (experts_f, 9.4e6),
                      (head_f, 33.6e6)):
        assert got == pytest.approx(want, rel=1e-2)
    fwd = 4 * conv_f + attn_f + scores + dense_f + 4 * (router_f + experts_f) + head_f
    assert lfm2_cost.forward_flops_per_token(cfg, 8192) == fwd
    assert fwd == pytest.approx(405.8e6, rel=1e-4)
    assert lfm2_cost.train_flops_per_token(cfg, 8192) == 3 * fwd == pytest.approx(1.217e9, rel=1e-3)
    assert 197e12 / (3 * fwd) == pytest.approx(161.8e3, rel=1e-3)    # tokens/s at the peak
    # a token pays for its expected share of the experts held, not for four
    all_held = dict(cfg, num_experts=64, reduced=[])
    assert lfm2_cost.forward_flops_per_token(all_held, 8192) - fwd \
        == 4 * 3.5 * 2 * 3 * h * 1536


def test_conv_cost_by_hand():
    # 32,768 tokens x 2048 channels in bf16: B, C, u read and y written
    assert conv_cost.call_bytes(CONV_FWD) == 4 * 32768 * 2048 * 2 == 16384 * 32768
    # B, C, u and dy read, dB, dC, du written: 28 KB a token
    assert conv_cost.call_bytes(CONV_BWD) == 7 * 32768 * 2048 * 2 == 28672 * 32768
    assert conv_cost.call_bytes(CONV_FWD.replace("bf16[4,8192,2048]", "f32[2,512,128]")) \
        == 4 * 2 * 512 * 128 * 4
    for other in (CONV_FWD.replace("%short_conv_fwd", "%fusion"), FLASH,
                  CONV_FWD.replace("bf16[4,8192,2048]", "bf16[32768,2048]"),
                  CONV_BWD.replace("bf16[4,8192,6144]", "bf16[4,8192,2047]", 1),  # no 3C
                  "%short_conv_fwd.9 = token[] custom-call(",
                  CONV_FWD.replace("bf16[4", "s32[4")):
        assert conv_cost.call_bytes(other) is None, other


def run_with(kernels, **over) -> dict:
    return dict({"config": config(), "tokens_per_step": 32768, "trace_steps": 4,
                 "device": {"kind": "TPU v5 lite"}, "trace": {"kernels": kernels},
                 "moe_rows_held_samples": [65536, 60000, 70000, 66608],
                 "moe_rows_per_step": 16384.0}, **over)


def test_readers_report_nothing_when_nothing_matched():
    flash = {"%flash_fwd.1": {"count": 4, "seconds": 0.02, "hlo": FLASH}}
    for run in ({}, {"trace": {"kernels": {}}}, run_with({}), run_with(flash),
                run_with(flash, moe_rows_held_samples=[])):
        for name in ("kernel.moe_gmm_held_roofline", "kernel.short_conv_roofline",
                     "conv.kernel_ms_per_step"):
            assert read(name, run) is None, (name, run)
    # a program that sows no rows held (the parent's) gives no samples
    for run in ({}, run_with({}, moe_rows_held_samples=[])):
        assert read("moe.rows_held_pct", run) is None
        assert read("kernel.moe_gmm_held_roofline", run) is None


def test_conv_readers_on_a_made_up_trace():
    fwd_s, bwd_s = 1.0e-3, 2.0e-3      # least: 0.655 ms and 1.147 ms at 819 GB/s
    kernels = {
        # four conv layers, a recomputed forward: 8 forward and 4 backward a step
        "%short_conv_fwd.3": {"count": 32, "seconds": 32 * fwd_s, "hlo": CONV_FWD},
        "%short_conv_bwd.2": {"count": 16, "seconds": 16 * bwd_s, "hlo": CONV_BWD},
        "%flash_fwd.1": {"count": 8, "seconds": 1.0, "hlo": FLASH},
        "%fusion.9": {"count": 8, "seconds": 1.0, "hlo": CONV_FWD.replace(
            "%short_conv_fwd.3", "%fusion.9")}}
    run = run_with(kernels)
    conv = conv_cost.traced_conv(run)
    assert conv["calls"] == 48
    assert conv["bytes"] == 32 * 16384 * 32768 + 16 * 28672 * 32768
    assert read("conv.kernel_ms_per_step", run) == pytest.approx(8 * 1.0 + 4 * 2.0)
    least = (32 * 16384 + 16 * 28672) * 32768 / 819e9
    assert read("kernel.short_conv_roofline", run) == pytest.approx(
        100 * least / (32 * fwd_s + 16 * bwd_s))
    assert 60 < read("kernel.short_conv_roofline", run) < 62
    at_peak = run_with({"%short_conv_fwd.1": {
        "count": 8, "seconds": 8 * 16384 * 32768 / 819e9, "hlo": CONV_FWD}})
    assert read("kernel.short_conv_roofline", at_peak) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        read("kernel.short_conv_roofline", dict(run, device={"kind": "TPU v9"}))


def test_held_roofline_counts_the_rows_held_and_not_the_padded_array():
    one = 2 * 16384 * 2048 * 1536                 # a grouped matmul over the even share
    t = 1.0e-3
    kernels = {
        "%ragged-dot-none.7": {"count": 32, "seconds": 32 * t, "hlo": GMM_UP},
        "%ragged-dot-none.6": {"count": 32, "seconds": 32 * t, "hlo": GMM_DOWN},
        "%ragged-dot-none.1": {"count": 48, "seconds": 48 * t, "hlo": GMM_DW},
        "%ragged-dot-metadata.1": {"count": 16, "seconds": 1.0, "hlo":
                                   "%ragged-dot-metadata.1 = (s32[9]{0}) custom-call("},
        "%short_conv_fwd.3": {"count": 32, "seconds": 1.0, "hlo": CONV_FWD}}
    run = run_with(kernels)
    gmm = moe_cost.traced_gmm(run)
    assert gmm["calls"] == 112 and gmm["seconds"] == pytest.approx(112 * t)
    assert read("moe.gmm_ms_per_step", run) == pytest.approx(28.0)
    # nine calls a layer and step over the rows the program counted:
    # 262,144 rows in the four traced steps (each sample sums four layers)
    rows = 65536 + 60000 + 70000 + 66608
    least = 9 * 2 * rows * 2048 * 1536 / 197e12
    assert read("kernel.moe_gmm_held_roofline", run) == pytest.approx(
        100 * least / (112 * t))
    assert rows == 16 * 16384 and least == pytest.approx(9 * 16 * one / 197e12)
    # the all-experts reader would count the padded 32,768 rows of every row
    # call as work; this one does not move when the array is padded further
    padded = {k: dict(v, hlo=v["hlo"].replace("[32768,", "[131072,"))
              for k, v in kernels.items()}
    assert read("kernel.moe_gmm_held_roofline", run_with(padded)) \
        == read("kernel.moe_gmm_held_roofline", run)
    assert moe_cost.traced_gmm(run_with(padded))["flops"] > 3 * gmm["flops"]
    # at the peak, with no recomputed forward, it reads 100 and never more
    peak = run_with({"%moe_gmm.2": {"count": 9 * 16, "seconds": 9 * 16 * one / 197e12,
                                    "hlo": GMM_UP.replace("%ragged-dot-none.7", "%moe_gmm.2")}})
    assert read("kernel.moe_gmm_held_roofline", peak) == pytest.approx(100.0)


def test_rows_held_share_by_hand():
    run = run_with({})
    assigned = 32768 * 4 * 4                     # tokens x top-4 x four expert layers
    assert read("moe.rows_held_pct", run) == pytest.approx(
        100 * (65536 + 60000 + 70000 + 66608) / 4 / assigned)
    assert read("moe.rows_held_pct", run_with({}, moe_rows_held_samples=[65536])) == 12.5
    assert read("moe.load_max_over_mean", {"moe_load_samples": [1.5, 2.5]}) == 2.0


def _made_up_step(rng, wrong_share: float = 0.0, update_scale: float = 1.0):
    """A program's first step and the reference's on a made-up tree: logits
    with rounding noise five times the wrong router's offset and a tenth of
    the positions flipped, gradients 1% off outside the expert blocks and
    20% inside, eight assignments moved."""
    from benchmark.runners import train_steps_lfm2_moe as runner
    tree = lambda f: {"model": {                                   # noqa: E731
        "embed_tokens": {"embedding": f((32, 16))},
        "layers_0": {"ffn_norm": {"weight": f((16, ))},
                     "conv": {"conv_weight": f((3, 16))}},
        "layers_1": {"ffn_norm": {"weight": f((16, ))},
                     "block_sparse_moe": {"gate": {"kernel": f((16, 8))},
                                          "expert_bias": np.zeros(8, np.float32),
                                          "w1": f((2, 16, 4))}}}}
    normal = lambda shape: rng.normal(size=shape).astype(np.float32)   # noqa: E731
    want_g = tree(normal)
    routed = lambda name: "block_sparse_moe" in name or "layers_1\'][\'ffn" in name  # noqa: E731,E501
    import jax
    got_g = jax.tree_util.tree_map_with_path(
        lambda path, g: g + (0.2 if routed(jax.tree_util.keystr(path)) else 0.01)
        * np.linalg.norm(g) * normal(g.shape) / np.sqrt(g.size), want_g)
    logits = normal((4, 64, 512))
    offset, noise = 0.004 * normal(logits.shape), 0.02 * normal(logits.shape)
    flipped = rng.random((4, 64)) < 0.1
    noise[flipped] += 0.3 * normal(logits.shape)[flipped] + 5 * offset[flipped]
    counts = rng.integers(50, 80, size=8)
    moved = counts.copy()
    moved[0] -= 8
    moved[5] += 8
    got = {"logits": logits + noise + wrong_share * offset, "loss": 9.01,
           "grads": got_g, "stats": {"expert_counts": moved, "rows_held": moved[:2].sum()},
           "before": want_g, "after": jax.tree_util.tree_map(
               lambda p, g: p + np.float32(update_scale * -runner.LR) * g
               / (np.abs(g) + np.float32(runner.ADAM_EPS)), want_g, got_g)}
    want = {"logits": logits, "ce": 9.0, "grads": want_g, "counts": counts,
            "rows_held": int(counts[:2].sum()), "margin": rng.random((4, 64)) * 0.05}
    return runner.readings(got, want, logits + offset)


def test_the_runners_readings_by_hand_on_a_made_up_step():
    r = _made_up_step(np.random.default_rng(3))
    assert 0.02 * 0.95 < r["logit_median"] < 0.02 * 1.1 and r["logit_worst"] > 0.2
    # flips along the wrong router's direction do not reach the share: they
    # are not quiet positions
    assert abs(r["wrong_router_share"]) < 0.1 and 150 < r["positions_quiet"] < 240
    assert r["grad_worst"][1] == pytest.approx(0.01, rel=0.3)
    assert r["grad_routed_worst"][1] == pytest.approx(0.2, rel=0.3)
    # the norm the router reads counts among the routed leaves; the bias has
    # no gradient on either side and is left out
    assert set(r["grad_err"]) == {
        "['model']['embed_tokens']['embedding']", "['model']['layers_0']['ffn_norm']['weight']",
        "['model']['layers_0']['conv']['conv_weight']", "['model']['layers_1']['ffn_norm']['weight']",
        "['model']['layers_1']['block_sparse_moe']['gate']['kernel']",
        "['model']['layers_1']['block_sparse_moe']['w1']"}
    assert r["grad_err"]["['model']['layers_1']['ffn_norm']['weight']"] > 0.1
    assert r["update_err"] < 1e-6 and r["moved"] == 8
    assert r["rows_held"][1] - r["rows_held"][0] == 8
    assert r["loss_err"] == pytest.approx(0.01 / 9.0, rel=1e-3)


@pytest.mark.parametrize("wrong_share,update_scale,reading,least", [
    (1.0, 1.0, "wrong_router_share", 0.9),     # weights from the biased score
    (0.0, 3.16, "update_err", 2.0),            # Adam without its bias correction
    (0.0, -1.0, "update_err", 1.9)])           # ascent
def test_the_runners_readings_tell_a_wrong_step(wrong_share, update_scale, reading, least):
    from benchmark.runners import train_steps_lfm2_moe as runner
    r = _made_up_step(np.random.default_rng(4), wrong_share, update_scale)
    assert r[reading] > least > max(runner.WRONG_ROUTER_SHARE, runner.UPDATE_RTOL)


def test_first_moment_is_found_in_the_engines_adamw_state():
    import jax.numpy as jnp
    import optax
    from benchmark.runners.train_steps_lfm2_moe import ADAM_B1, first_moment
    params = {"w": jnp.ones((3, ))}
    tx = optax.adamw(1e-4)
    grads = {"w": jnp.asarray([1.0, -2.0, 0.5])}
    _, state = tx.update(grads, tx.init(params), params)
    np.testing.assert_allclose(np.asarray(first_moment(state)["w"]) / (1 - ADAM_B1),
                               np.asarray(grads["w"]), rtol=1e-6)


def test_the_calibration_of_the_limits_rehearses():
    """``calibrate_lfm2_moe.py`` is where the limits' readings come from: on
    the CPU at tiny sizes it has to run and to tell the wrong references."""
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "calibrate_lfm2_moe.py"),
         "--seeds", "3", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = {r["against"]: r for r in map(json.loads, (
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")))}
    assert list(rows) == ["sound", "biased_weights", "no_renormalisation",
                          "fp8_convs_and_experts", "fp8_experts"]
    sound = rows["sound"]
    assert abs(sound["wrong_router_share"]) < 0.3 < 0.7 < \
        rows["biased_weights"]["wrong_router_share"]
    for wrong in ("no_renormalisation", "fp8_convs_and_experts"):
        assert rows[wrong]["logit_median"] > 4 * sound["logit_median"]
        assert rows[wrong]["grad_worst"][1] > 3 * sound["grad_worst"][1]
    assert sound["update_err"] < 1e-3


@pytest.mark.parametrize("trace,devices", [(0, 1), (1, 1), (0, 4)])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace, devices):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(devices, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 31), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has; 2 of 16 experts held
    assert "'data': 1," in said and "2 of 16 experts held" in said
    assert "conv+dense/attention+moe/conv+moe/conv+moe/conv+moe" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check
    assert "expert counts sum 4096 of 4096 over 16 experts" in check
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # what the program counts; no kernel events and no utilization on a CPU
        assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        assert 5.0 < line["metrics"]["moe.rows_held_pct"]["value"] < 25.0
        for absent in ("kernel.moe_gmm_held_roofline", "kernel.short_conv_roofline",
                       "conv.kernel_ms_per_step", "step.mfu_pct", "moe.gmm_ms_per_step"):
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
