"""Tests for the compiler-scheduled ZeRO-3 program
(``runtime/zero3_schedule.py``): schedule-pass unit tests (trace, epoch
derivation, governor budget), engine-level stage-3 vs stage-2 parity (fp32
and quantized wires, sync and async-window drivers), per-chip memory
reduction, observability counters, per-shard checkpointing with
stage 2<->3 reshard-on-load, and a dp=2 subprocess acceptance run."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from simple_model import simple_model_and_params  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.comm.bucketing import plan_buckets  # noqa: E402
from deepspeed_tpu.comm.mesh import reset_mesh_context  # noqa: E402
from deepspeed_tpu.runtime.zero3_schedule import (  # noqa: E402
    build_store_meta, derive_schedule, materialize_params, store_from_tree,
    trace_param_uses)


# ---------------------------------------------------------------------------
# schedule pass (host-side, no mesh)
# ---------------------------------------------------------------------------


class TestSchedulePass:

    def _traced(self):
        """Three-matmul chain: params used strictly in order."""
        def loss(pl, x):
            a, b, c = pl
            return jnp.sum(((x @ a) @ b) @ c)

        structs = [jax.ShapeDtypeStruct((16, 16), jnp.float32)
                   for _ in range(3)]
        closed = jax.make_jaxpr(loss)(structs,
                                      jax.ShapeDtypeStruct((4, 16),
                                                           jnp.float32))
        return closed, structs

    def test_trace_first_last_use_ordered(self):
        closed, structs = self._traced()
        first, last = trace_param_uses(closed, 3)
        assert None not in first and None not in last
        assert first[0] < first[1] < first[2]  # chain order
        for f, l in zip(first, last):
            assert f <= l

    def test_trace_unused_leaf_is_none(self):
        def loss(pl, x):
            a, _unused = pl
            return jnp.sum(x @ a)

        structs = [jax.ShapeDtypeStruct((8, 8), jnp.float32)] * 2
        closed = jax.make_jaxpr(loss)(structs,
                                      jax.ShapeDtypeStruct((4, 8),
                                                           jnp.float32))
        first, last = trace_param_uses(closed, 2)
        assert first[0] is not None
        assert first[1] is None and last[1] is None

    def _layout3(self):
        # one bucket per 16x16 leaf: tiny bucket cap forces the split
        structs = [jax.ShapeDtypeStruct((16, 16), jnp.float32)
                   for _ in range(3)]
        layout = plan_buckets(structs, bucket_size_mb=256 * 4 / 2**20,
                              pad_multiple=1)
        assert len(layout.buckets) == 3
        return layout, structs

    def test_one_ahead_prefetch(self):
        closed, _ = self._traced()
        first, last = trace_param_uses(closed, 3)
        layout, _ = self._layout3()
        sched = derive_schedule(layout, (0, 1, 2), first, last,
                                len(closed.jaxpr.eqns),
                                max_live_parameters=None,
                                max_reuse_distance=None,
                                persistent_elements=0, world=8,
                                fwd_tier="fp32", block=256)
        assert len(sched.epochs) == 3
        assert sched.epochs[0].issue_at == -1  # program start
        # epoch j issues at epoch j-1's first use: gather overlaps compute
        for j in range(1, 3):
            assert sched.epochs[j].issue_at == sched.epochs[j - 1].first_use
            assert sched.epochs[j].prefetched
        assert sched.prefetch_count == 3

    def test_budget_demotes_prefetch(self):
        closed, _ = self._traced()
        first, last = trace_param_uses(closed, 3)
        layout, _ = self._layout3()
        free = derive_schedule(layout, (0, 1, 2), first, last,
                               len(closed.jaxpr.eqns), None, None, 0, 8,
                               "fp32", 256)
        # budget of one bucket: prefetching a second bucket while the first
        # is live would hold 512 elements -> demote to gather-at-use
        tight = derive_schedule(layout, (0, 1, 2), first, last,
                                len(closed.jaxpr.eqns),
                                max_live_parameters=256,
                                max_reuse_distance=None,
                                persistent_elements=0, world=8,
                                fwd_tier="fp32", block=256)
        assert free.peak_live_elements > 256
        assert tight.peak_live_elements <= 256
        assert tight.prefetch_count < free.prefetch_count

    def test_reuse_distance_splits_epochs(self):
        """A bucket used at the start AND end of the program re-gathers when
        the elements touched in between exceed max_reuse_distance."""
        def loss(pl, x):
            a, b = pl
            h = x @ a          # a: first use early
            h = h @ b          # b: 256 elements between a's uses
            return jnp.sum(h @ a)  # a again at the end

        structs = [jax.ShapeDtypeStruct((16, 16), jnp.float32)] * 2
        closed = jax.make_jaxpr(loss)(structs,
                                      jax.ShapeDtypeStruct((4, 16),
                                                           jnp.float32))
        first, last = trace_param_uses(closed, 2)
        layout = plan_buckets(structs, bucket_size_mb=256 * 4 / 2**20,
                              pad_multiple=1)
        keep = derive_schedule(layout, (0, 1), first, last,
                               len(closed.jaxpr.eqns), None, None, 0, 8,
                               "fp32", 256)
        split = derive_schedule(layout, (0, 1), first, last,
                                len(closed.jaxpr.eqns), None,
                                max_reuse_distance=128,  # < 256 between uses
                                persistent_elements=0, world=8,
                                fwd_tier="fp32", block=256)
        n_a_keep = sum(1 for e in keep.epochs if e.bucket == 0)
        n_a_split = sum(1 for e in split.epochs if e.bucket == 0)
        assert n_a_keep == 1 and n_a_split == 2
        assert split.gather_wire_bytes > keep.gather_wire_bytes

    def test_gather_bucket_mb_caps(self):
        from deepspeed_tpu.runtime.zero_governor import gather_bucket_mb
        # defaults are no-ops
        assert gather_bucket_mb(25.0, None, None) == 25.0
        assert gather_bucket_mb(25.0, 1e9, 5e7) == 25.0
        # max_live: a bucket may hold at most half the live budget
        # (the in-use bucket + the prefetched one)
        assert gather_bucket_mb(25.0, 2**20, None) == pytest.approx(2.0)
        # prefetch_bucket_size caps directly
        assert gather_bucket_mb(25.0, None, 2**20) == pytest.approx(4.0)
        assert gather_bucket_mb(1.0, 2**30, 2**30) == 1.0

    def test_store_meta_roundtrip(self):
        tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                "b": jnp.arange(4, dtype=jnp.float32),
                "s": jnp.float32(3.0)}
        # scalar leaf persistent (1 element <= threshold index set)
        leaves = jax.tree_util.tree_leaves(tree)
        pidx = [i for i, l in enumerate(leaves) if l.size <= 1]
        meta = build_store_meta(tree, pidx, bucket_size_mb=25.0,
                                pad_multiple=8)
        store = store_from_tree(tree, meta)
        assert len(store["persistent"]) == 1
        back = materialize_params(store, meta)
        for a, b in zip(jax.tree_util.tree_leaves(back), leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine-level (8 virtual devices)
# ---------------------------------------------------------------------------


def _engine(extra=None, seed=0, gas=2):
    reset_mesh_context()
    model, mp = simple_model_and_params(seed=seed)
    cfg = {"train_batch_size": 8 * gas, "gradient_accumulation_steps": gas,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
    cfg.update(extra or {})
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=mp,
                                          config=cfg)
    return engine


def _z3(extra=None, **kw):
    cfg = {"zero_optimization": {"stage": 3,
                                 "stage3_param_persistence_threshold": 0},
           "gradient_comm": {"enabled": True, "overlap_comm": True}}
    for k, v in (extra or {}).items():
        if k in cfg and isinstance(v, dict):
            cfg[k].update(v)
        else:
            cfg[k] = v
    return _engine(cfg, **kw)


def _z2(extra=None, **kw):
    cfg = {"zero_optimization": {"stage": 2},
           "gradient_comm": {"enabled": True, "overlap_comm": True}}
    cfg.update(extra or {})
    return _engine(cfg, **kw)


def _data(n=8, seed=7):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
             jnp.asarray(rng.normal(size=(8, 16)), jnp.float32))
            for _ in range(n)]


def _full_tree(e):
    if getattr(e, "_zero3_store", None) is not None:
        return e.full_params()
    return e.params

def _max_param_diff(e1, e2):
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree_util.tree_leaves(_full_tree(e1)),
                               jax.tree_util.tree_leaves(_full_tree(e2))))


def _per_chip_bytes(tree):
    tot = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        tot += leaf.addressable_shards[0].data.nbytes
    return tot


# losses diverge by at most ~1 ulp from stage-2: the scheduled program's
# gather/slice transposes change XLA fusion in the backward matmuls
# (forward is bitwise; see docs/zero3.md)
ULP = dict(rtol=3e-7, atol=0)


@pytest.mark.world_size(8)
class TestZero3Scheduled:

    def test_engages_with_store_and_schedule(self):
        e = _z3()
        assert e._zero3_store is not None
        assert e._grad_comm_layout is not None
        assert e._train_steps_fused is None  # scheduled program owns the step
        # store holds buckets sharded 1/dp: every leaf below the (zeroed)
        # persistence threshold lives bucketed
        assert isinstance(e.params, dict)
        assert e.params["persistent"] == []
        w = e.dp_world_size
        for b in e.params["buckets"]:
            assert b.addressable_shards[0].data.size == b.size // w
        e.train_batch(iter(_data()))
        sched = e._zero3_schedule
        assert sched is not None and len(sched.epochs) >= 1
        assert sched.epochs[0].issue_at == -1

    def test_loss_parity_vs_stage2_five_steps(self):
        e2, e3 = _z2(), _z3()
        data = _data()
        for step in range(5):
            l2 = float(e2.train_batch(iter(data)))
            l3 = float(e3.train_batch(iter(data)))
            np.testing.assert_allclose(l3, l2, err_msg=f"step {step}", **ULP)
        assert _max_param_diff(e2, e3) < 1e-6

    def test_gas1_routes_through_scheduled_program(self):
        e = _z3(gas=1)
        assert e._zero3_store is not None
        assert e._train_step_fused is None
        loss = float(e.train_batch(iter(_data(1))))
        assert np.isfinite(loss)
        assert e._zero3_schedule is not None

    def test_async_window_parity(self):
        e2 = _z2()
        e3 = _z3({"async_pipeline": {"enabled": True, "window_steps": 2}})
        data = _data()
        l2s = [float(e2.train_batch(iter(data))) for _ in range(4)]
        l3s = [float(e3.train_batch(iter(data))) for _ in range(4)]
        np.testing.assert_allclose(l3s, l2s, **ULP)

    def test_quantized_gather_within_tolerance(self):
        e2 = _z2()
        eq = _z3({"zero_optimization": {"zero_quantized_weights": True}})
        data = _data()
        for _ in range(3):
            l2 = float(e2.train_batch(iter(data)))
            lq = float(eq.train_batch(iter(data)))
        # int8 blockwise wire on the param gather: same trajectory within
        # quantization noise
        np.testing.assert_allclose(lq, l2, rtol=0.05)
        assert _max_param_diff(e2, eq) < 0.1

    def test_governor_budget_respected(self):
        budget = 4096
        e = _z3({"zero_optimization": {"stage3_max_live_parameters": budget},
                 "gradient_comm": {"bucket_size_mb": 512 * 4 / 2**20}})
        e.train_batch(iter(_data()))
        sched = e._zero3_schedule
        assert sched.peak_live_elements <= budget

    def test_per_chip_param_and_opt_bytes_reduced(self):
        e2, e3 = _z2(), _z3()
        p2, p3 = _per_chip_bytes(e2.params), _per_chip_bytes(e3.params)
        o3 = _per_chip_bytes(e3.opt_state)
        # stage 2 replicates params; stage 3 holds exactly 1/8 of the
        # padded buckets per chip
        w = e3.dp_world_size
        padded = sum(b.padded_size for b in e3._zero3_store.layout.buckets)
        assert p3 == 4 * padded // w
        assert p3 < p2 / 2
        # Adam moments are built OVER the store: two bucket shards + step
        # scalars (NOT replicated moments — that would be 2*4*padded bytes)
        assert o3 <= 2 * p3 + 64

    def test_gather_counters_bank(self):
        from deepspeed_tpu.observability import get_registry
        e = _z3()
        reg = get_registry()
        g0 = reg.counter("ds_zero3_gather_bytes_total").value
        h0 = reg.counter("ds_zero3_prefetch_hits_total").value
        e.train_batch(iter(_data()))
        sched = e._zero3_schedule
        gas = e.gradient_accumulation_steps()
        assert reg.counter("ds_zero3_gather_bytes_total").value - g0 == \
            pytest.approx(sched.gather_wire_bytes * gas)
        assert reg.counter("ds_zero3_prefetch_hits_total").value - h0 == \
            pytest.approx(sched.prefetch_count * gas)

    def test_eval_and_fwd_under_store(self):
        e2, e3 = _z2(), _z3()
        x, y = _data(1)[0]
        l2 = float(e2.eval_batch(x, y))
        l3 = float(e3.eval_batch(x, y))
        np.testing.assert_allclose(l3, l2, **ULP)

    def test_full_params_matches_stage2_tree(self):
        e2, e3 = _z2(), _z3()
        data = _data()
        for _ in range(2):
            e2.train_batch(iter(data))
            e3.train_batch(iter(data))
        assert _max_param_diff(e2, e3) < 1e-6
        # tree structure round-trips exactly
        assert (jax.tree_util.tree_structure(e3.full_params())
                == jax.tree_util.tree_structure(e2.params))

    def test_save_16bit_model_gathers(self, tmp_path):
        e = _z3()
        e.train_batch(iter(_data()))
        assert e.save_16bit_model(str(tmp_path), "model.npz")
        archive = np.load(tmp_path / "model.npz")
        leaves = jax.tree_util.tree_leaves(e.full_params())
        names = [k for k in archive.files if k != "__dtype__"]
        assert len(names) == len(leaves)

    def test_persistence_threshold_keeps_small_leaves_replicated(self):
        # default SimpleModel leaves are all <= 1e5 elements: with the
        # threshold raised every leaf is persistent (degenerate but legal)
        e = _z3({"zero_optimization":
                 {"stage3_param_persistence_threshold": int(1e5)}})
        assert e._zero3_store is not None
        assert e.params["buckets"] == []
        assert len(e.params["persistent"]) > 0
        loss = float(e.train_batch(iter(_data())))
        assert np.isfinite(loss)

    def test_offload_falls_back(self):
        e = _z3({"zero_optimization": {
            "offload_optimizer": {"device": "cpu"}}})
        assert e._zero3_store is None  # store refuses; engine still trains
        loss = float(e.train_batch(iter(_data())))
        assert np.isfinite(loss)


@pytest.mark.world_size(8)
class TestZero3Checkpoint:

    def test_stage3_roundtrip_per_shard(self, tmp_path):
        e1 = _z3()
        data = _data()
        e1.train_batch(iter(data))
        e1.save_checkpoint(str(tmp_path), tag="z3")
        ref = float(e1.train_batch(iter(data)))
        e2 = _z3(seed=1)
        path, _ = e2.load_checkpoint(str(tmp_path), tag="z3")
        assert path is not None
        got = float(e2.train_batch(iter(data)))
        np.testing.assert_allclose(got, ref, **ULP)

    def test_reshard_stage2_to_stage3(self, tmp_path):
        """A stage-2 (tree-form) checkpoint loads into a stage-3 engine:
        the restore lands in save-time format, then converts to the store."""
        e2 = _z2()
        data = _data()
        e2.train_batch(iter(data))
        e2.save_checkpoint(str(tmp_path), tag="t2")
        ref = float(e2.train_batch(iter(data)))
        e3 = _z3(seed=1)
        path, _ = e3.load_checkpoint(str(tmp_path), tag="t2")
        assert path is not None
        assert _max_param_diff(e2, e3) > 0  # e2 already stepped past the save
        got = float(e3.train_batch(iter(data)))
        np.testing.assert_allclose(got, ref, **ULP)

    def test_reshard_stage3_to_stage2(self, tmp_path):
        e3 = _z3()
        data = _data()
        e3.train_batch(iter(data))
        e3.save_checkpoint(str(tmp_path), tag="t3")
        ref = float(e3.train_batch(iter(data)))
        e2 = _z2(seed=1)
        path, _ = e2.load_checkpoint(str(tmp_path), tag="t3")
        assert path is not None
        got = float(e2.train_batch(iter(data)))
        np.testing.assert_allclose(got, ref, **ULP)

    def test_host_state_records_store_meta(self, tmp_path):
        e = _z3()
        e.train_batch(iter(_data()))
        e.save_checkpoint(str(tmp_path), tag="meta")
        saved = e._peek_host_state(str(tmp_path / "meta")).get("zero3_store")
        assert saved is not None
        assert saved["n_leaves"] == e._zero3_store.n_leaves
        assert saved["persistent_idx"] == list(e._zero3_store.p_idx)


_DP2_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, {unit_dir!r})
    import numpy as np, jax, jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from simple_model import simple_model_and_params

    def engine(extra):
        reset_mesh_context()
        model, mp = simple_model_and_params(seed=0)
        cfg = {{"train_batch_size": 8,
                "gradient_accumulation_steps": 2,
                "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}}}}
        cfg.update(extra)
        e, *_ = deepspeed_tpu.initialize(model=model, model_parameters=mp,
                                         config=cfg)
        return e

    e2 = engine({{"zero_optimization": {{"stage": 2}},
                  "gradient_comm": {{"enabled": True, "overlap_comm": True}}}})
    e3 = engine({{"zero_optimization":
                  {{"stage": 3, "stage3_param_persistence_threshold": 0}},
                  "gradient_comm": {{"enabled": True, "overlap_comm": True}}}})
    assert e3._zero3_store is not None
    rng = np.random.default_rng(7)
    data = [(jnp.asarray(rng.normal(size=(4, 16)), jnp.float32),
             jnp.asarray(rng.normal(size=(4, 16)), jnp.float32))
            for _ in range(8)]
    for step in range(5):
        l2 = float(e2.train_batch(iter(data)))
        l3 = float(e3.train_batch(iter(data)))
        np.testing.assert_allclose(l3, l2, rtol=3e-7, atol=0,
                                   err_msg=f"step {{step}}")

    def per_chip(tree):
        return sum(l.addressable_shards[0].data.nbytes
                   for l in jax.tree_util.tree_leaves(tree))

    p2, p3 = per_chip(e2.params), per_chip(e3.params)
    o3 = per_chip(e3.opt_state)
    # dp=2: params ~2x smaller per chip (stage 2 replicates them; the gap
    # to exactly 2x is bucket padding on this toy model), and the Adam
    # moments are bucket shards too (2 x p3 + step scalars), not replicated
    w = 2
    padded = sum(b.padded_size for b in e3._zero3_store.layout.buckets)
    assert p3 == 4 * padded // w, (p3, padded)
    assert p3 < 0.75 * p2, (p2, p3)
    assert o3 <= 2 * p3 + 64, (o3, p3)
    print("DP2_OK", p2, p3, o3)
""")


class TestZero3DP2Subprocess:

    def test_dp2_parity_and_memory(self, force_host_devices):
        repo = os.path.join(os.path.dirname(__file__), "..", "..", "..")
        unit_dir = os.path.join(os.path.dirname(__file__), "..")
        env = force_host_devices(2, extra={
            "PYTHONPATH": os.path.abspath(repo)})
        script = _DP2_SCRIPT.format(unit_dir=os.path.abspath(unit_dir))
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-4000:]
        assert "DP2_OK" in out.stdout
