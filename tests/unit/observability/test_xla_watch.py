"""Compile watch + train instruments: per-key compile/retrace/hit
classification on real jax.jit caches, cost-analysis FLOPs without an
AOT compile, memory gauges on CPU, and the MFU publish path."""

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
