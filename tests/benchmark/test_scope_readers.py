"""``benchmark/scope_time.py`` and the nine ``scope.*`` readers: the phase and
part rules on hand-built paths, the readers on a hand-built table, the wire
reader on a traced CPU step (the join through the profile's own "Hlo Proto"),
and the manifest's nine entries. All on the CPU; no number here is a
measurement."""

import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import manifest_checks  # noqa: E402  (beside this file)
from benchmark import scope_time  # noqa: E402
from benchmark.run import cell_metrics, load_manifest, load_module  # noqa: E402

THREE = ["train-zero3-seq4k", "train-olmoe-1chip-seq4k", "train-lfm2moe-1chip-seq8k"]
NINE = [("scope.fwd_ms_per_step", "ms", "lower", "model step, training", THREE),
        ("scope.bwd_ms_per_step", "ms", "lower", "model step, training", THREE),
        ("scope.recompute_ms_per_step", "ms", "lower", "model step, training", THREE),
        ("scope.update_ms_per_step", "ms", "lower", "optimizer", THREE),
        ("scope.head_ms_per_step", "ms", "lower", "head", THREE),
        ("scope.ffn_ms_per_step", "ms", "lower", "dense FFN", [THREE[0], THREE[2]]),  # OLMoE has none
        ("scope.mixer_proj_ms_per_step", "ms", "lower", "operator projections", THREE),
        ("scope.moe_block_ms_per_step", "ms", "lower", "MoE block", THREE[1:]),
        ("scope.named_pct.train", "%", "higher", "device", THREE)]

STEP = "jit(train_step)/ds.step.loss/"
FWD = STEP + "jvp(LlamaForCausalLM)/model/"
BWD = (STEP + "transpose(jvp(LlamaForCausalLM))/model/ds.step.loss/"
       "jvp(LlamaForCausalLM)/model/checkpoint/")


# one case a rule, the precedence cases among them
@pytest.mark.parametrize("path,phase,part", [
    (FWD + "layers_3/mlp/up_proj/dot_general", "fwd", "ffn"),
    (BWD + "layers_3/mlp/up_proj/dot_general", "bwd", "ffn"),
    # the recomputed forward runs inside the backward: its mark wins
    (BWD + "rematted_computation/layers_3/mlp/up_proj/dot_general", "recompute", "ffn"),
    # the first module segment under the layer decides: a norm inside the
    # attention is the operator's, the layer's own norm is a norm
    (FWD + "layers_0/self_attn/q_norm/mul", "fwd", "mixer"),
    (FWD + "layers_0/self_attn/ds.rope/mul", "fwd", "mixer"),
    (FWD + "layers_0/input_layernorm/rsqrt", "fwd", "norm"),
    (FWD + "layers_2/operator_norm/mul", "fwd", "norm"),
    (FWD + "norm/mul", "fwd", "norm"),
    (FWD + "layers_1/conv/in_proj/dot_general", "fwd", "mixer"),
    (BWD + "layers_4/mamba/out_proj/dot_general", "bwd", "mixer"),
    (FWD + "layers_2/block_sparse_moe/ds.moe.dispatch/gather", "fwd", "moe"),
    (BWD + "layers_2/block_sparse_moe/shared_expert/up_proj/dot_general", "bwd", "moe"),
    (FWD + "layers_2/shared_expert/up_proj/dot_general", "fwd", "ffn"),
    # an op of the layer itself (the residual add); nn.scan's layers/layer
    (FWD + "layers_5/add", "fwd", "layer"),
    (STEP + "jvp(LlamaForCausalLM)/while/body/closed_call/layers/layer/mlp/mul",
     "fwd", "ffn"),
    (STEP + "transpose(jvp(LlamaForCausalLM))/while/body/closed_call/layers/layers/"
     "checkpoint/layer/add_any", "bwd", "layer"),
    (FWD + "embed_tokens/jit(_take)/gather", "fwd", "head"),
    (FWD + "lm_head/dot_general", "fwd", "head"),
    (STEP + "jvp(LlamaForCausalLM)/ds.head.loss/while/body/closed_call/dot_general",
     "fwd", "head"),
    # the engine's own regions; a gather or cast differentiated through is
    # forward or backward work by the marks, and still the engine's part
    ("jit(train_step)/ds.step.cast/convert_element_type", "prep", "prep"),
    ("jit(train_step)/ds.step.gather/all-gather", "prep", "prep"),
    (STEP + "jvp(ds.step.gather)/mul", "fwd", "prep"),
    (STEP + "transpose(jvp(ds.step.cast))/convert_element_type", "bwd", "prep"),
    # grad_norm ends in "_norm" and is no norm layer
    ("jit(train_step)/ds.step.grad_norm/reduce_sum", "update", "update"),
    ("jit(train_step)/ds.step.optimizer/jit(_where)/select_n", "update", "update"),
    # no module, no ds. scope: the rotary tables at the model's top, a bare op
    (FWD + "cos", "fwd", "unnamed"),
    ("jit(train_step)/mul", "other", "unnamed"),
    ("", "other", "unnamed"),
])
def test_phase_and_part_of_a_path(path, phase, part):
    assert (scope_time.phase_of(path), scope_time.part_of(path)) == (phase, part)


def test_segments_come_out_of_the_transformation_marks():
    assert scope_time.segments("jit(f)/transpose(jvp(M))/vmap()/layers_0/jit(silu)/x") \
        == ["f", "M", "", "layers_0", "silu", "x"]
    assert scope_time.innermost_ds(STEP + "jvp(M)/block_sparse_moe/ds.moe.route/top_k") \
        == "ds.moe.route"
    assert scope_time.innermost_ds(FWD + "layers_0/mlp/mul") is None   # ds.step.loss alone


FLASH = "%flash_fwd.1 = (bf16[8,4,4096,128]{3,2,1,0}) custom-call(%bitcast.25, %copy.1)"
GMM = "%ragged-dot-none.3 = bf16[131072,1024]{1,0} custom-call(%fusion.7, %convert.1)"
# a fusion that only READS a custom call's result is no custom call
FUSION = "%fusion.7 = bf16[4096,4096]{1,0} fusion(bf16[4096,4096]{1,0} %custom-call.3)"


def rows(scale=1.0):
    """One chip's events of two traced steps: ``(text, ns, path)``."""
    ms = 1e6 * scale
    return [(FUSION, 20 * ms, FWD + "layers_0/mlp/up_proj/dot_general"),
            (FUSION, 40 * ms, BWD + "layers_0/mlp/up_proj/dot_general"),
            (FUSION, 18 * ms, BWD + "rematted_computation/layers_0/mlp/up_proj/dot_general"),
            (FUSION, 6 * ms, FWD + "layers_0/self_attn/q_proj/dot_general"),
            (FLASH, 10 * ms, FWD + "layers_0/self_attn/jit(_dispatched_attention)/"
             "flash_fwd/pallas_call"),
            (FUSION, 8 * ms, FWD + "layers_0/block_sparse_moe/ds.moe.dispatch/gather"),
            # XLA's own grouped-matmul call has no name stack: the MoE block's
            # by its instruction name, its pass unknown; its tile table is not
            (GMM, 5 * ms, "ragged-dot-none"),
            (GMM.replace("-none.3", "-metadata.1"), 0.5 * ms, "ragged-dot-metadata"),
            (FUSION, 30 * ms, STEP + "jvp(LlamaForCausalLM)/ds.head.loss/while/body/dot"),
            (FUSION, 12 * ms, "jit(train_step)/ds.step.optimizer/mul"),
            (FUSION, 4 * ms, "jit(train_step)/ds.step.grad_norm/reduce_sum"),
            (FUSION, 2 * ms, "jit(train_step)/ds.step.cast/convert_element_type"),
            # XLA's layout copy of a state buffer: an argument's name, no path
            ("%copy.9 = f32[8]{0} copy(%p.1)", 1 * ms, "params['model']['norm']['weight']"),
            (FUSION, 3 * ms, "jit(train_step)/mul"),         # a path, and no name in it
            # holders of other ops are left out: their time is their children's
            ("%while.3 = (s32[]) while(%tuple.1), body=%body", 500 * ms, FWD + "while")]


def run_with(planes, steps=2):
    table = scope_time.build_table(planes, steps)
    return {"_scope_table": table if table and table["step_scopes"] else None}


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_nine_readers_on_a_hand_built_table():
    run = run_with({"/device:TPU:0": rows()})
    got = {name: read(name, run) for name, *_ in NINE}
    assert got["scope.fwd_ms_per_step"] == pytest.approx((20 + 6 + 10 + 8 + 30) / 2)
    assert got["scope.bwd_ms_per_step"] == pytest.approx(40 / 2)
    assert got["scope.recompute_ms_per_step"] == pytest.approx(18 / 2)
    assert got["scope.update_ms_per_step"] == pytest.approx((12 + 4) / 2)
    assert got["scope.head_ms_per_step"] == pytest.approx(30 / 2)
    assert got["scope.ffn_ms_per_step"] == pytest.approx((20 + 40 + 18) / 2)
    # the operator less its custom call: the projection alone
    assert got["scope.mixer_proj_ms_per_step"] == pytest.approx(6 / 2)
    assert got["scope.moe_block_ms_per_step"] == pytest.approx((8 + 5) / 2)
    busy = 20 + 40 + 18 + 6 + 10 + 8 + 5 + 0.5 + 30 + 12 + 4 + 2 + 1 + 3
    assert got["scope.named_pct.train"] == pytest.approx(100 * (busy - 3 - 1 - 0.5) / busy)
    # phases partition the busy time, and so do parts
    table = run["_scope_table"]
    assert sum(scope_time.total(table, phase=p) for p in scope_time.PHASES) \
        == pytest.approx(busy / 2) == pytest.approx(table["busy_ms"])
    assert sum(scope_time.total(table, part=p) for p in scope_time.PARTS) \
        == pytest.approx(busy / 2)
    assert table["ds_ms"][("ds.moe.dispatch", "fwd")] == pytest.approx(8 / 2)
    assert scope_time.total(table, "other", "moe") == pytest.approx(5 / 2)
    assert scope_time.total(table, "other") == pytest.approx((5 + 0.5 + 1 + 3) / 2)
    assert [n for _, n, _ in table["unnamed"]] == ["%fusion.7", "%copy.9",
                                                   "%ragged-dot-metadata.1"]
    assert table["top"][0][0] == pytest.approx((20 + 40 + 18 + 6 + 8 + 30 + 12 + 4 + 2 + 3) / 2)
    text = "\n".join(scope_time.render(table, "tf_op"))
    assert "| recompute |" in text and "ds.step scopes present" in text
    assert "scope ds.step.optimizer [update] 6.000 ms" in text


def test_the_readers_average_over_the_chips():
    one = run_with({"/device:TPU:0": rows()})
    four = run_with({f"/device:TPU:{i}": rows(s)
                     for i, s in enumerate((0.5, 1.0, 1.0, 1.5))})
    for name, *_ in NINE:
        assert read(name, four) == pytest.approx(read(name, one)), name


def test_a_cell_that_does_not_recompute_reads_zero_and_one_with_no_experts_nothing():
    mine = [r for r in rows() if "rematted" not in r[2] and "moe" not in r[2]
            and "ragged" not in r[2]]
    run = run_with({"/device:TPU:0": mine})
    assert read("scope.recompute_ms_per_step", run) == 0.0
    assert read("scope.moe_block_ms_per_step", run) is None
    assert read("scope.fwd_ms_per_step", run) == pytest.approx((20 + 6 + 10 + 30) / 2)


@pytest.mark.parametrize("name", [n for n, *_ in NINE])
def test_a_program_without_the_step_scopes_reports_nothing(name):
    """The parent commit's program, or one loaded from a compile-cache entry it
    wrote (the key ignores metadata): flax's module paths and JAX's marks are
    there, ``ds.step.*`` is not, and no reader gives a split without them."""
    parents = [(t, d, p.replace("ds.step.loss/", ""))
               for t, d, p in rows() if "ds.step." not in p or "ds.step.loss" in p]
    assert not any("ds.step." in p for _, _, p in parents)
    table = scope_time.build_table({"/device:TPU:0": parents}, 2)
    assert table["step_scopes"] is False and table["busy_ms"] > 0
    assert "ds.step scopes ABSENT" in scope_time.render(table, "tf_op")[0]
    assert read(name, run_with({"/device:TPU:0": parents})) is None
    # nor where nothing was traced, or the run left no one trace to read
    assert read(name, {"trace_steps": 0}) is None
    assert read(name, {"_scope_table": None}) is None
    assert scope_time.build_table({}, 2) is None


@pytest.fixture(scope="module")
def traced_step(tmp_path_factory):
    """A toy step with the program's scope names, compiled in this process
    (nothing loaded from a cache: a CPU program that is carries no Hlo Proto
    into the profile) and traced once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        def step(w, x):
            with jax.named_scope("ds.step.cast"):
                wc = w.astype(jnp.bfloat16)

            def loss(wc):
                with jax.named_scope("layers_0"), jax.named_scope("mlp"):
                    y = jnp.tanh(x.astype(jnp.bfloat16) @ wc)
                with jax.named_scope("ds.head.loss"):
                    return jnp.sum(y.astype(jnp.float32) ** 2)
            with jax.named_scope("ds.step.loss"):
                value, g = jax.value_and_grad(loss)(wc)
            with jax.named_scope("ds.step.optimizer"):
                return value, w - 0.1 * g.astype(w.dtype)

        f = jax.jit(step)
        w, x = jnp.ones((256, 256)), jnp.ones((64, 256))
        jax.block_until_ready(f(w, x))
        out = str(tmp_path_factory.mktemp("scope_trace"))
        with jax.profiler.trace(out):
            for _ in range(2):
                value, w = f(w, x)
            jax.block_until_ready(w)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
    return path


def test_a_traced_cpu_step_through_the_hlo_proto_join(traced_step, capsys):
    planes, source = scope_time.read_events(traced_step, 1)
    assert source == "hlo_proto" and list(planes) == ["/device:TPU:0"]
    paths = {p for _, _, p in planes["/device:TPU:0"] if p}
    assert any("ds.step.optimizer" in p for p in paths)
    assert any("transpose(jvp(layers_0))/mlp" in p for p in paths)
    table = scope_time.build_table(planes, 2)
    assert table["step_scopes"] and table["chips"] == 1
    assert scope_time.total(table, "fwd", "ffn") > 0
    assert scope_time.total(table, "bwd", "ffn") > 0
    assert scope_time.total(table, "update", "update") > 0
    assert scope_time.total(table, part="head") > 0
    # the entry point a builder runs by hand on any traced run's file
    assert scope_time.main([traced_step, "--chips", "1", "--steps", "2"]) == 0
    said = capsys.readouterr().out
    assert "paths from hlo_proto" in said and "scope ds.step.optimizer [update]" in said
    assert scope_time.main([traced_step, "--steps", "2", "--chips", "1"]) == 0


def test_load_reads_the_runs_one_trace_once(traced_step, monkeypatch, capsys):
    calls = []
    real = scope_time.read_events
    monkeypatch.setattr(scope_time.host_spans, "_xplane_path", lambda: traced_step)
    monkeypatch.setattr(scope_time, "read_events",
                        lambda *a: calls.append(a) or real(*a))
    run = {"trace_steps": 2, "device": {"count": 1}}
    values = {name: read(name, run) for name, *_ in NINE}
    assert len(calls) == 1                       # nine readers, one parse
    assert values["scope.update_ms_per_step"] > 0
    assert values["scope.fwd_ms_per_step"] > 0 and values["scope.bwd_ms_per_step"] > 0
    assert values["scope.recompute_ms_per_step"] == 0.0
    assert values["scope.moe_block_ms_per_step"] is None
    assert 0 < values["scope.named_pct.train"] <= 100
    assert "scope table (ms a step and chip; 2 steps" in capsys.readouterr().out
    # no one trace on disk: nothing to report
    monkeypatch.setattr(scope_time.host_spans, "_xplane_path", lambda: None)
    assert read("scope.fwd_ms_per_step", {"trace_steps": 2}) is None


def test_a_cell_with_no_dense_ffn_reports_no_ffn_and_does_not_list_it():
    # the OLMoE cell: every layer's feed-forward is the MoE block, so part
    # `ffn` is empty, its reader finds nothing, and a cell that listed the
    # metric would print a line that lacks it
    mine = [r for r in rows() if "/mlp/" not in r[2] and "shared_expert" not in r[2]]
    run = run_with({"/device:TPU:0": mine})
    assert read("scope.ffn_ms_per_step", run) is None
    assert read("scope.moe_block_ms_per_step", run) == pytest.approx((8 + 5) / 2)
    olmoe = {x["name"] for x in cell_metrics(load_manifest(), "train-olmoe-1chip-seq4k",
                                             "per_layer")}
    assert {n for n, *_ in NINE} - olmoe == {"scope.ffn_ms_per_step"}


def test_the_nine_entries_are_appended_and_well_formed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    names = [m["name"] for m in admitted["per_layer"]]
    # after everything the manifest had, in the documented order (membership
    # and relative order: a later PR appends after them)
    assert [n for n in names if n.startswith("scope.")] == [n for n, *_ in NINE]
    assert names.index("scope.fwd_ms_per_step") > names.index("flash.kernel_ms_per_step")
    by_name = {m["name"]: m for m in admitted["per_layer"]}
    for name, unit, better, layer, cells in NINE:
        assert by_name[name] == {"name": name, "unit": unit, "better": better,
                                 "source": "device_trace", "layer": layer,
                                 "moves": "train_tok_s", "workloads": cells}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
    m = load_manifest()
    granite = {x["name"] for x in cell_metrics(m, "train-granite4hm-1chip-longseq",
                                               "per_layer")}
    assert not any(n.startswith("scope.") for n in granite)     # ROADMAP D13
    dense = {x["name"] for x in cell_metrics(m, "train-zero3-seq4k", "per_layer")}
    assert "scope.moe_block_ms_per_step" not in dense
    assert {n for n, *_ in NINE} - {"scope.moe_block_ms_per_step"} <= dense
