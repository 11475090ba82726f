"""Compile watch + train instruments: per-key compile/retrace/hit
classification on real jax.jit caches, cost-analysis FLOPs without an
AOT compile, memory gauges on CPU, and the MFU publish path."""

import contextlib
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.observability import (CompileWatch, GoodputLedger,
                                         MetricsRegistry, TrainInstruments,
                                         WatchedJit, cost_analysis_flops,
                                         refresh_memory_gauges)


def test_watched_jit_classifies_compile_hit_retrace():
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(lambda x: x * 2.0 + 1.0), "toy")
    assert isinstance(fn, WatchedJit)
    x = jnp.ones((4, 4), jnp.float32)
    fn(x)                       # first shape: compile
    fn(x)                       # same shape: cache hit
    fn(x)
    c = watch.counts("toy")
    assert c["compiles"] == 1 and c["recompiles"] == 0 and c["hits"] == 2
    assert c["compile_seconds"] > 0
    fn(jnp.ones((8, 4), jnp.float32))   # new shape: RETRACE
    c = watch.counts("toy")
    assert c["compiles"] == 2 and c["recompiles"] == 1 and c["hits"] == 2
    # wrap is idempotent — re-watching a WatchedJit must not double-count
    assert watch.wrap(fn, "toy") is fn


def test_watched_jit_forwards_attributes():
    """The wrapper must be indistinguishable to callers probing jit
    internals (flops profiler does hasattr(fn, "lower"))."""
    fn = CompileWatch(registry=MetricsRegistry()).wrap(
        jax.jit(lambda x: x + 1), "fwd")
    assert hasattr(fn, "lower")
    out = fn(jnp.zeros((2,)))
    assert float(out[0]) == 1.0


def test_program_flops_without_aot_compile():
    """program_flops resolves from lower().cost_analysis() — verify it
    matches the known matmul FLOP count and never touches .compile()
    (the AOT path would pay a full fresh XLA compile)."""
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(lambda a, b: a @ b), "mm")
    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    fn(a, b)  # compiling call captures specs AND resolves flops eagerly
    f = fn.program_flops()
    assert f == pytest.approx(2 * 32 * 64 * 16, rel=0.5)
    assert fn.program_flops() is f or fn.program_flops() == f  # cached
    # the plain helper normalizes both Lowered and Compiled returns
    low = jax.jit(lambda a, b: a @ b).lower(a, b)
    assert cost_analysis_flops(low) == pytest.approx(f, rel=1e-6)
    assert cost_analysis_flops(object()) == 0.0  # no cost model → 0, no raise


def test_unjitted_callable_first_call_is_compile():
    """Wrappers without _cache_size (plain functions, e.g. the grad-comm
    step builder) degrade to first-call-is-compile."""
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(lambda x: x + 1, "plain")
    fn(1), fn(2), fn(3)
    c = watch.counts("plain")
    assert c["compiles"] == 1 and c["hits"] == 2 and c["recompiles"] == 0


def test_refresh_memory_gauges_cpu_graceful():
    """CPU backends report no memory_stats — the refresh must not raise
    and must simply set nothing rather than inventing zeros."""
    reg = MetricsRegistry()
    out = refresh_memory_gauges(reg)
    assert isinstance(out, dict)
    for name, val in out.items():
        assert val >= 0  # if a backend DOES report, values are sane


def test_train_instruments_step_and_mfu_publish():
    reg = MetricsRegistry()
    reads = []  # every reading the ledger takes of its clock

    def clock():
        reads.append(time.perf_counter())
        return reads[-1]

    led = GoodputLedger(registry=reg, clock=clock)
    ti = TrainInstruments(registry=reg, ledger=led, peak_flops=1e12)
    fn = ti.watch_program(jax.jit(lambda a, b: a @ b), "train_step")
    ti.start_clock()
    a = jnp.ones((64, 64), jnp.float32)
    for _ in range(4):
        jax.block_until_ready(fn(a, a))
        ti.step_mark()
    marked = reads[-1] - reads[0]  # construction to the last mark
    ti.publish()
    h = reg.get("ds_train_step_seconds")
    assert h.count == 4
    mfu = reg.get("ds_train_mfu").value
    assert 0.0 < mfu <= 1.0
    # goodput: the compile call's wall was carved into "compile"
    t = led.totals()
    assert t["compile"] > 0 and t["useful_step"] > 0
    # ... and the categories sum to the wall the marks spanned, on the
    # ledger's own clock (what this thread did after the last mark, under
    # whatever load, is not the ledger's to attribute yet)
    assert led.attributed_seconds() == pytest.approx(marked, rel=1e-6)
    assert marked <= led.wall_seconds()
    # fused K-step accounting: one mark books K histogram samples
    ti.step_mark(steps=8)
    assert reg.get("ds_train_step_seconds").count == 12


def test_compile_seconds_feed_goodput_ledger():
    reg = MetricsRegistry()
    led = GoodputLedger(registry=reg)
    ti = TrainInstruments(registry=reg, ledger=led, peak_flops=1e12)
    fn = ti.watch_program(jax.jit(lambda x: jnp.sin(x).sum()), "probe")
    ti.start_clock()
    jax.block_until_ready(fn(jnp.ones((256,))))
    ti.step_mark()
    t = led.totals()
    assert t["compile"] > 0  # on_compile_seconds → note_compile → carve


# ---- the cost analysis reads jit's own trace and lowering (PR 43) ----

@contextlib.contextmanager
def _compile_stages():
    """-> the list jax.monitoring's compile-stage durations are recorded into
    while the block runs: (event, fun_name, seconds, monotonic end)."""
    import jax.monitoring as monitoring
    events = []

    def on_event(name, secs, **kw):
        events.append((name.rsplit("/", 1)[-1], kw.get("fun_name", ""),
                       float(secs), time.monotonic()))

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield events
    finally:
        monitoring.unregister_event_duration_listener(on_event)


def _stages_inside(events, scope, fun):
    """-> (seconds of ``fun``'s trace events, its lowerings) that ended inside
    ``scope``. jit reports a ``jaxpr_trace_duration`` on a hit of its trace
    cache too, of microseconds; a lowering it reports only when it lowers."""
    inside = [e for e in events
              if scope["t0_monotonic"] <= e[3] <= scope["t1_monotonic"]
              and fun in e[1]]
    return (sum(e[2] for e in inside if e[0] == "jaxpr_trace_duration"),
            sum(e[0] == "jaxpr_to_mlir_module_duration" for e in inside))


def _cost_scopes(key, since=0.0):
    from deepspeed_tpu.observability.tracing import get_tracer
    return [s for s in get_tracer().scopes("ds.compile.cost_analysis", since)
            if s["args"].get("key") == key]


def _one_device_mesh():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:1]), ("data", ))
    return NamedSharding(mesh, PartitionSpec())


def _committed_step(runs, depth=12):
    """A ``value_and_grad`` step as ``runtime/engine.py`` jits its fused
    step: parameters donated, ``out_shardings`` named, no ``in_shardings``;
    the arguments committed to a ``NamedSharding`` of a one-device mesh."""
    sh = _one_device_mesh()

    def step(params, x, scale):
        runs.append(1)

        def loss(p):
            h = x
            for i in range(depth):
                h = jnp.tanh(h @ p[f"w{i}"])
            return jnp.sum(h) * scale

        value, grads = jax.value_and_grad(loss)(params)
        return value, jax.tree_util.tree_map(lambda p, g: p - 0.1 * g,
                                             params, grads)

    params = {f"w{i}": jax.device_put(jnp.full((32, 32), 0.01, jnp.float32), sh)
              for i in range(depth)}
    x = jax.device_put(jnp.ones((8, 32), jnp.float32), sh)
    fn = jax.jit(step, donate_argnums=(0, ),
                 out_shardings=(None, {k: sh for k in params}))
    return fn, params, x


def test_cost_analysis_of_committed_arguments_reads_the_dispatchs_trace():
    """(a) the body runs once over the call plus ``program_flops()``: the
    specs carry each committed argument's sharding (and a scalar's weak
    type), so jit answers from its caches; the FLOPs are those of a lowering
    of the real arguments."""
    from deepspeed_tpu.observability.xla import _arg_specs
    runs = []
    fn, params, x = _committed_step(runs)
    scale = jnp.asarray(2.0)            # weakly typed, not committed
    assert scale.weak_type and not scale.committed
    want = cost_analysis_flops(fn.lower(params, x, scale))
    assert len(runs) == 1 and want > 0
    fn.clear_cache()
    jax.clear_caches()
    del runs[:]

    reg = MetricsRegistry()
    watch = CompileWatch(registry=reg)
    w = watch.wrap(fn, "committed_step")
    with _compile_stages() as events:
        value, new = w(params, x, scale)
        assert len(runs) == 1
        (spec_p, spec_x, spec_s), _ = _arg_specs((params, x, scale), {})
        assert params["w0"].is_deleted()        # donated: read as metadata
        assert spec_p["w0"].sharding == x.sharding == spec_x.sharding
        assert spec_s.sharding is None and spec_s.weak_type
        traced = [e[2] for e in events
                  if e[0] == "jaxpr_trace_duration" and e[1] == "step"]
        assert w.program_flops() == want
    assert len(runs) == 1, "the cost analysis traced the step again"
    scope, = _cost_scopes("committed_step")
    trace_s, lowerings = _stages_inside(events, scope, "step")
    assert lowerings == 0 and trace_s < 0.05 * max(traced)
    assert float(value) == pytest.approx(
        float(w(new, x, scale)[0]), rel=0.5)    # still dispatches


def test_cost_analysis_through_a_tiny_engine_traces_the_step_once(tmp_path):
    """(b) the same through ``DeepSpeedTpuEngine`` on the CPU mesh: one fused
    step and its ``program_flops()`` trace the model once and lower once."""
    import flax.linen as nn
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from deepspeed_tpu.observability import get_registry
    runs = []

    class Counted(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            runs.append(1)
            for _ in range(2):
                x = nn.relu(nn.Dense(16)(x))
            return jnp.mean((nn.Dense(16)(x) - y) ** 2)

    reset_mesh_context()
    get_registry().reset()
    model = Counted()
    ones = jnp.ones((8, 16), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ones, ones)["params"]
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8, "steps_per_print": 1000,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "observability": {"enabled": True}})
    step = engine._train_step_fused
    assert isinstance(step, WatchedJit)
    del runs[:]
    began = time.monotonic()    # the ring is the process's: other engines' too
    with _compile_stages() as events:
        engine.fused_train_step(ones, jnp.zeros((8, 16)))
        traced = len(runs)
        assert traced >= 1
        flops = step.program_flops()
    assert flops > 0 and len(runs) == traced, \
        "the cost analysis traced the step again"
    assert sum(e[0] == "jaxpr_to_mlir_module_duration" and "train_step" in e[1]
               for e in events) == 1
    scope, = _cost_scopes("train_step_fused", began)
    assert _stages_inside(events, scope, "train_step")[1] == 0
    counts = engine._train_obs.compile_watch.counts("train_step_fused")
    assert counts["compiles"] == 1
    assert counts["cost_analysis_seconds"] == pytest.approx(scope["dur_s"])
    reset_mesh_context()


def test_program_kept_bytes_equal_a_fresh_traces():
    """(c) a program that recomputes with named values: what
    ``program_kept_bytes`` reads off jit's cached jaxpr is what
    ``named_residual_bytes`` reads off a fresh trace."""
    from jax.ad_checkpoint import checkpoint_name
    from deepspeed_tpu.observability.xla import named_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.ops.attention import RESIDUAL_NAMES
    sh = _one_device_mesh()
    w0 = jax.device_put(jnp.full((16, 16), 0.1, jnp.float32), sh)
    x = jax.device_put(jnp.ones((4, 16), jnp.float32), sh)

    def layer(w, h):
        kept = checkpoint_name(jnp.tanh(h @ w), RESIDUAL_NAMES[0])
        with remat.keeping(RESIDUAL_NAMES):     # offered, and made again
            again = remat.keep(kept @ w, remat.MIXER_IN)
        return jnp.sin(again)

    def loss(w, x):
        h = x
        for _ in range(3):
            h = jax.checkpoint(layer, policy=remat.KEPT_POLICY)(w, h)
        return jnp.sum(h)

    def fresh():
        return jax.jit(jax.grad(loss), out_shardings=sh)

    want = named_residual_bytes(fresh().trace(w0, x).jaxpr)
    assert want == (3 * 4 * 16 * 4, 2 * 3 * 4 * 16 * 4)
    w = CompileWatch(registry=MetricsRegistry()).wrap(fresh(), "recomputes")
    assert w.program_kept_bytes() == (0, 0)     # nothing compiled yet
    w(w0, x)
    assert w.program_kept_bytes() == want
    assert w.program_flops() == cost_analysis_flops(fresh().lower(w0, x))


def test_uncommitted_arguments_and_plain_callables_as_before():
    """(d) an uncommitted array's spec names no sharding (jit reads a spec
    with one as a committed argument: another key), a numpy array's neither,
    statics pass through; a callable that is not a jit has no FLOPs and
    raises nothing."""
    from deepspeed_tpu.observability.xla import _arg_specs
    a = jnp.ones((4, 8), jnp.float32)
    (sa, sn, static), kw = _arg_specs((a, np.ones((2, ), np.int32), "mean"),
                                      {"k": a})
    assert not a.committed and sa.sharding is None and kw["k"].sharding is None
    assert (sa.shape, sa.dtype, sa.weak_type) == ((4, 8), jnp.float32, False)
    assert (sn.shape, sn.dtype, sn.sharding) == ((2, ), np.int32, None)
    assert static == "mean"
    runs = []

    def body(a, b):
        runs.append(1)
        return a @ b

    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(body), "uncommitted")
    fn(a, a.T)
    assert fn.program_flops() == pytest.approx(2 * 4 * 8 * 4, rel=0.5)
    assert len(runs) == 1
    plain = watch.wrap(lambda v: v + 1, "plain")
    assert plain(1) == 2
    assert plain.program_flops() == 0.0 and plain.program_kept_bytes() == (0, 0)


def test_cost_analysis_seconds_are_published_once_a_program():
    """(e) ``ds_cost_analysis_seconds_total{key}`` beside
    ``ds_compile_seconds{key}``: what the one cost analysis of a program
    took, not counted again by later reads, and not in the compile's wall."""
    reg = MetricsRegistry()
    watch = CompileWatch(registry=reg)
    fn = watch.wrap(jax.jit(lambda a: jnp.tanh(a) @ a), "once")
    a = jnp.ones((16, 16), jnp.float32)
    fn(a)
    counter = reg.get("ds_cost_analysis_seconds_total", labels={"key": "once"})
    assert counter.value == 0.0         # a tiny program's analysis is lazy
    fn.program_flops()
    took = counter.value
    assert 0.0 < took == watch.counts("once")["cost_analysis_seconds"]
    scope = _cost_scopes("once")[-1]
    assert took == pytest.approx(scope["dur_s"], abs=0.25)
    fn(a), fn.program_flops(), fn.program_kept_bytes()
    assert counter.value == took
    assert [m.labels for m in reg.series("ds_cost_analysis_seconds_total")] == [
        {"key": "once"}]
    assert reg.get("ds_compile_seconds", labels={"key": "once"}).count == 1
