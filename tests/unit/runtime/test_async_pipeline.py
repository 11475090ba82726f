"""Async train-step pipeline: device prefetch ordering, windowed host sync
(zero per-step device→host transfers in steady state), on-device grad-norm
parity with the host path, fused-partition scheduling, and the persistent
compile-cache wiring."""

import sys
import os
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from simple_model import simple_model_and_params  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.comm.mesh import reset_mesh_context  # noqa: E402
from deepspeed_tpu.runtime.dataloader import (DevicePrefetchIterator,  # noqa: E402
                                              PrefetchingLoader)


def make_engine(**over):
    reset_mesh_context()
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 1000}
    cfg.update(over)
    model, params = simple_model_and_params(seed=0)
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    return engine


def batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
             jnp.zeros((8, 16)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# device prefetch iterator
# ---------------------------------------------------------------------------

def test_prefetch_preserves_order_and_prefetches_ahead():
    puts = []

    def put(b):
        puts.append(b)
        return b * 10  # marker: consumers must see the PUT value

    it = DevicePrefetchIterator(iter([1, 2, 3, 4, 5]), put, depth=2)
    # construction already dispatched `depth` transfers
    assert puts == [1, 2]
    out = [next(it)]
    # consuming one batch tops the buffer back up BEFORE returning
    assert puts == [1, 2, 3]
    out.extend(it)
    assert out == [10, 20, 30, 40, 50]
    assert puts == [1, 2, 3, 4, 5]


def test_prefetch_exhaustion_and_short_iterators():
    it = DevicePrefetchIterator(iter([7]), lambda b: b, depth=4)
    assert next(it) == 7
    with pytest.raises(StopIteration):
        next(it)
    # empty host iterator: immediate StopIteration, no put calls
    puts = []
    it = DevicePrefetchIterator(iter([]), lambda b: puts.append(b), depth=2)
    with pytest.raises(StopIteration):
        next(it)
    assert puts == []


def test_prefetching_loader_epoch_boundary():
    """The loader is re-iterable: each epoch restarts the inner loader and
    yields every batch exactly once, in order."""
    epochs_seen = []

    class Loader:
        def __iter__(self):
            epochs_seen.append(len(epochs_seen))
            return iter([1, 2, 3])

        def __len__(self):
            return 3

    pl = PrefetchingLoader(Loader(), lambda b: b + 100, depth=2)
    assert len(pl) == 3
    assert list(pl) == [101, 102, 103]
    assert list(pl) == [101, 102, 103]  # second epoch
    assert epochs_seen == [0, 1]


def test_engine_prefetch_yields_device_batches():
    e = make_engine(async_pipeline={"enabled": True, "prefetch_depth": 2})
    # engine.prefetch wraps any iterator; batches come back device-committed
    it = e.prefetch(iter([(np.zeros((8, 16), np.float32),
                           np.zeros((8, 16), np.float32))] * 3), depth=2)
    got = list(it)
    assert len(got) == 3
    assert all(isinstance(x, jax.Array) for pair in got for x in pair)
    # prefetched batches flow through the train path unchanged
    e2 = make_engine(async_pipeline={"enabled": True, "sync_interval": 2})
    data = batches(3, seed=5)
    pre = list(e2.prefetch(iter(data), depth=2))
    for x, y in pre:
        e2.fused_train_step(x, y)
    assert e2.global_steps == 3


# ---------------------------------------------------------------------------
# windowed host sync: zero per-step device→host transfers in steady state
# ---------------------------------------------------------------------------

def test_no_per_step_host_sync_in_steady_state(monkeypatch):
    """Trace-level assertion for the tentpole: with the async window on,
    the engine performs NO device→host fetch and NO effects-barrier in the
    per-step path — host syncs happen only at window drains. Every host
    fetch the engine does goes through the ``host_fetch`` seam and every
    timer barrier through ``timer._sync``, so instrumenting those seams IS
    the transfer trace."""
    import deepspeed_tpu.runtime.engine as engine_mod
    import deepspeed_tpu.utils.timer as timer_mod

    e = make_engine(async_pipeline={"enabled": True, "sync_interval": 4})
    counts = {"fetch": 0, "sync": 0}
    real_fetch = engine_mod.host_fetch

    def counting_fetch(x):
        counts["fetch"] += 1
        return real_fetch(x)

    monkeypatch.setattr(engine_mod, "host_fetch", counting_fetch)
    monkeypatch.setattr(timer_mod, "_sync",
                        lambda: counts.__setitem__("sync", counts["sync"] + 1))

    data = batches(8)
    per_step_fetches = []
    for x, y in data:
        loss = e.fused_train_step(x, y)
        per_step_fetches.append(counts["fetch"])
    # the loss the step returns is still a live device scalar
    assert isinstance(loss, jax.Array)
    # drains fired ONLY at steps 4 and 8 (one batched fetch each); every
    # other step performed zero device→host transfers
    assert per_step_fetches == [0, 0, 0, 1, 1, 1, 1, 2]
    # the throughput timer never forced a device barrier
    assert counts["sync"] == 0
    # deferred accounting reconciled at the drains
    assert e.global_steps == 8
    assert not e._async_window.entries


def test_windowed_sync_matches_synchronous_path():
    """Async windowing changes WHEN host accounting happens, never the
    math: losses, params, and scheduler position match the sync engine."""
    data = batches(6, seed=3)
    e_sync = make_engine(
        scheduler={"type": "WarmupLR",
                   "params": {"warmup_num_steps": 4, "warmup_max_lr": 1e-2}})
    ref = [float(e_sync.fused_train_step(x, y)) for x, y in data]

    e_async = make_engine(
        scheduler={"type": "WarmupLR",
                   "params": {"warmup_num_steps": 4, "warmup_max_lr": 1e-2}},
        async_pipeline={"enabled": True, "sync_interval": 4})
    dev_losses = [e_async.fused_train_step(x, y) for x, y in data]
    # get_loss drains mid-window and returns the NEWEST step's loss
    assert e_async.get_loss() == pytest.approx(ref[-1], rel=1e-6)
    np.testing.assert_allclose([float(l) for l in dev_losses], ref, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(e_sync.params),
                    jax.tree_util.tree_leaves(e_async.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert e_async.global_steps == e_sync.global_steps == 6
    assert e_async.grad_acc is None and e_sync.grad_acc is None  # fused steps
    # scheduler advanced once per non-skipped step despite deferred drains
    assert e_async.get_lr() == pytest.approx(e_sync.get_lr())


def test_fused_train_steps_vector_entries_drain():
    """A K-step fused dispatch pushes ONE vector entry; the drain expands
    it (K scheduler advances, per-step overflow accounting)."""
    e = make_engine(
        async_pipeline={"enabled": True, "sync_interval": 4},
        scheduler={"type": "WarmupLR",
                   "params": {"warmup_num_steps": 100, "warmup_max_lr": 1e-2}})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    y = jnp.zeros((4, 8, 16), jnp.float32)
    sched_pos = e.lr_scheduler.last_batch_iteration
    losses = e.fused_train_steps(x, y)
    assert losses.shape == (4, )
    assert e.global_steps == 4
    e._drain_async_window()
    assert not e._async_window.entries
    assert e.grad_acc is None       # K fused steps: no accumulation buffer
    # 4 warmup advances of lr happened at the drain
    assert e.lr_scheduler.last_batch_iteration == sched_pos + 4


def test_monitor_events_deferred_until_flush():
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    class Cfg:
        class _Sub:
            enabled = False
        tensorboard = _Sub()
        wandb = _Sub()
        csv_monitor = _Sub()
        comet = _Sub()

    m = MonitorMaster(Cfg())
    m.enabled = True  # pretend a writer is attached
    written = []
    m.write_events = written.extend
    fetches = []

    def fetch(vals):
        fetches.append(len(vals))
        return [np.asarray(v) for v in vals]

    m.write_events_async([("loss", jnp.float32(1.5), 8)])
    m.write_events_async([("loss", jnp.asarray([2.0, 3.0]), [16, 24])])
    assert written == []  # nothing fetched, nothing written yet
    m.flush_events(fetch=fetch)
    # ONE batched transfer carried the whole window (both queued events)
    assert fetches == [2]
    assert written == [("loss", 1.5, 8), ("loss", 2.0, 16), ("loss", 3.0, 24)]
    m.flush_events(fetch=fetch)  # idempotent on an empty queue
    assert fetches == [2]


# ---------------------------------------------------------------------------
# on-device grad-norm/clip parity with the host path
# ---------------------------------------------------------------------------

def test_offload_prep_matches_host_norm_bitwise_fp32():
    """The compiled prep program's unscale+global-norm+clip must reproduce
    the host reference EXACTLY in fp32. Integer-valued gradients make every
    sum exact (no rounding under any association), so device vs host must
    agree to the BIT; sqrt/div/min are IEEE correctly-rounded on both
    sides."""
    from deepspeed_tpu.runtime.host_offload import flatten_tree
    clip = 1.0
    e = make_engine(
        gradient_clipping=clip,
        zero_optimization={"stage": 2,
                           "offload_optimizer": {"device": "cpu"}})
    rng = np.random.default_rng(7)
    # integer-valued fp32 grads, exactly representable, sums exact
    acc = jax.tree_util.tree_map(
        lambda g: jnp.asarray(
            rng.integers(-8, 9, size=g.shape).astype(np.float32)),
        e._ensure_grad_acc())
    clipped_d, overflow_d, gnorm_d = e._offload_prep(acc, e.scale_state)

    # host mirror: same flat-key order, same left-fold, pure np.float32
    flat = {k: np.asarray(v, np.float32)
            for k, v in flatten_tree(acc).items()}
    sq = np.float32(0.0)
    for k in flat:
        sq = np.float32(sq + np.float32(np.sum(np.square(flat[k]))))
    gnorm_h = np.float32(np.sqrt(sq))
    factor = np.float32(min(np.float32(1.0),
                            np.float32(clip / (gnorm_h + np.float32(1e-6)))))
    assert not bool(overflow_d)
    assert np.float32(gnorm_d).tobytes() == gnorm_h.tobytes()
    for k, v in clipped_d.items():
        ref = (flat[k] * factor).astype(np.float32)
        assert np.asarray(v).tobytes() == ref.tobytes(), k


def test_offload_prep_random_data_close_and_overflow():
    from deepspeed_tpu.runtime.host_offload import flatten_tree
    e = make_engine(
        gradient_clipping=0.5,
        zero_optimization={"stage": 2,
                           "offload_optimizer": {"device": "cpu"}})
    rng = np.random.default_rng(11)
    acc = jax.tree_util.tree_map(
        lambda g: jnp.asarray(rng.normal(size=g.shape), jnp.float32),
        e._ensure_grad_acc())
    clipped, overflow, gnorm = e._offload_prep(acc, e.scale_state)
    flat = np.concatenate([np.asarray(v, np.float64).ravel()
                           for v in jax.tree_util.tree_leaves(acc)])
    ref_norm = float(np.sqrt((flat ** 2).sum()))
    assert float(gnorm) == pytest.approx(ref_norm, rel=1e-5)
    assert not bool(overflow)
    factor = min(1.0, 0.5 / (ref_norm + 1e-6))
    got_norm = float(np.sqrt(sum(
        float((np.asarray(v, np.float64) ** 2).sum())
        for v in clipped.values())))
    assert got_norm == pytest.approx(ref_norm * factor, rel=1e-5)
    # a non-finite leaf flags overflow and suppresses clipping scale-up
    bad = {k: v for k, v in flatten_tree(acc).items()}
    first = next(iter(bad))
    bad_acc = jax.tree_util.tree_map(lambda g: g, acc)
    from deepspeed_tpu.runtime.host_offload import unflatten_like
    bad[first] = jnp.asarray(np.full(np.shape(bad[first]), np.inf,
                                     np.float32))
    bad_acc = unflatten_like(bad, acc)
    _, overflow2, _ = e._offload_prep(bad_acc, e.scale_state)
    assert bool(overflow2)


def test_offload_step_no_per_leaf_gradient_fetch(monkeypatch):
    """Tentpole 2's transfer contract: the host-offload step fetches ONLY
    the clipped host-subset leaves + two scalars through the seam — the
    global-norm/clip itself pulls no gradient tree across the host
    boundary (the old path device_get the ENTIRE grad tree first)."""
    import deepspeed_tpu.runtime.engine as engine_mod
    e = make_engine(
        gradient_clipping=1.0,
        zero_optimization={"stage": 2,
                           "offload_optimizer": {"device": "cpu"}})
    fetched = []
    real_fetch = engine_mod.host_fetch
    monkeypatch.setattr(engine_mod, "host_fetch",
                        lambda x: fetched.append(x) or real_fetch(x))
    x, y = batches(1)[0]
    loss = e.forward(x, y)
    e.backward(loss)
    e.step()
    # exactly one seam call per step: the (overflow, gnorm) scalar pair
    assert len(fetched) == 1
    leaves = jax.tree_util.tree_leaves(fetched[0])
    assert all(np.ndim(l) == 0 for l in leaves)


# ---------------------------------------------------------------------------
# fused-partition scheduling (inference)
# ---------------------------------------------------------------------------

def _partition_stub(max_context, seen):
    """Minimal engine stub for the pure-scheduling fused_partition."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    stub = types.SimpleNamespace(
        _config=types.SimpleNamespace(
            state_manager=types.SimpleNamespace(max_context=max_context)),
        _state_manager=types.SimpleNamespace(
            get_sequence=lambda u: types.SimpleNamespace(
                seen_tokens=seen[u])))
    return lambda uids, budgets, cap: InferenceEngineV2.fused_partition(
        stub, uids, budgets, cap)


def test_fused_partition_isolates_near_budget_request():
    part = _partition_stub(max_context=1024, seen={1: 10, 2: 20, 3: 30})
    # request 2 has ONE token of budget left: it must ride solo while the
    # others keep the full fused window
    fusable, K, solo = part([1, 2, 3], [100, 1, 100], cap=16)
    assert fusable == [1, 3]
    assert K == 16
    assert solo == [2]
    # uniform healthy batch: everything fuses, nothing solo
    fusable, K, solo = part([1, 2, 3], [100, 100, 5], cap=16)
    assert fusable == [1, 2, 3]
    assert K == 4  # power-of-2 snap of min room 5
    assert solo == []


def test_fused_partition_context_room_and_degenerate_cases():
    # context ceiling constrains like the output budget does
    part = _partition_stub(max_context=32, seen={1: 31, 2: 8})
    fusable, K, solo = part([1, 2], [100, 100], cap=16)
    assert fusable == [2] and solo == [1]
    assert K == 16
    # everyone constrained -> no fused wave at all
    part = _partition_stub(max_context=32, seen={1: 31, 2: 31})
    fusable, K, solo = part([1, 2], [100, 100], cap=16)
    assert (fusable, K, solo) == ([], 0, [1, 2])
    # cap < 2 forbids fusing even with room
    part = _partition_stub(max_context=1024, seen={1: 0})
    fusable, K, solo = part([1], [100], cap=1)
    assert (fusable, K, solo) == ([], 0, [1])


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

@pytest.fixture
def _restore_jax_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_set_is_used_and_unchanged(tmp_path, monkeypatch,
                                                     _restore_jax_cache_dir):
    """The launcher's ``JAX_COMPILATION_CACHE_DIR`` is the cache, for the
    trainer and the server alike; nothing in code sets another."""
    from deepspeed_tpu.runtime import compiler
    chosen = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", chosen)
    jax.config.update("jax_compilation_cache_dir", chosen)  # as jax reads it
    assert compiler.configure_compile_cache() == chosen
    assert compiler.configure_compile_cache(
        types.SimpleNamespace(cache_min_compile_secs=None)) == chosen
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == chosen
    assert jax.config.jax_compilation_cache_dir == chosen
    assert not os.path.exists(compiler.compile_cache_dir() + "_other")


def test_compile_cache_env_unset_is_the_checkout(monkeypatch,
                                                 _restore_jax_cache_dir):
    """Unset: ``<checkout>/.jax_cache`` — a fixed path (it is part of the
    cache key), never a home, temporary, pid- or time-derived one — and the
    environment stays unset."""
    import deepspeed_tpu
    from deepspeed_tpu.runtime import compiler
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(deepspeed_tpu.__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compiler.compile_cache_dir() == want
    assert compiler.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
