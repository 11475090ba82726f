"""Compile & runtime observability for the XLA layer.

Three pieces, all host-side and off the per-step critical path:

- :class:`CompileWatch` + :class:`WatchedJit`: transparent wrappers around
  jitted callables that classify every dispatch as compile / retrace /
  cache hit **per compile key** and record the wall time of compiling
  calls into labeled histograms (``ds_compile_seconds{key=...}``). The
  detection mechanism is ``fn._cache_size()`` growth across a call — one
  cheap C call per dispatch; when the attribute is missing (plain
  function wrappers, e.g. the grad-comm step builder) the first call
  counts as the compile and later calls as hits.
- FLOPs accounting: a compiling call captures ``ShapeDtypeStruct`` specs
  of its arguments, each with the committed sharding and weak type jit
  keyed its caches on (a spec without them misses both caches, and the
  whole program is traced and lowered again), so
  :meth:`WatchedJit.program_flops` gets the dispatch's own jaxpr and
  lowering back and runs HLO-level cost analysis on the lowered (NOT
  compiled) module. Its wall is the ``ds.compile.cost_analysis`` scope,
  summed in ``ds_cost_analysis_seconds_total{key=...}``. Resolved inside
  the compiling call for real programs, lazily at publish time for tiny
  ones, never on the step path. :class:`TrainInstruments` turns
  (dispatches × program FLOPs) over a wall interval into the
  ``ds_train_mfu`` gauge; serving uses the same ``program_flops`` for
  ``ds_serving_wave_mfu``. A lowered module has no cost analysis on a TPU
  (``cost_analysis()`` is None), so on a chip the FLOPs read 0.0 and both
  gauges stay unset; the named residual bytes come out everywhere
  (ROADMAP S9 iii).
- Device-memory gauges (:func:`refresh_memory_gauges`) from
  ``device.memory_stats()`` — live bytes, peak watermark, allocator
  limit. CPU backends return no stats; the gauges simply stay absent.

``install_backend_compile_listener`` additionally taps jax's monitoring
event ``/jax/core/compile/backend_compile_duration`` into an unlabeled
histogram — it catches XLA compiles that bypass the wrapped entry points
(model init, eager ops, persistent-cache misses during deserialization).
"""

import threading
import time
from typing import Any, Optional, Tuple

from .metrics import Histogram, MetricsRegistry, get_registry
from .tracing import get_tracer

# compile times span ~ms (tiny CPU programs) to ~1h (giant TPU programs)
_COMPILE_HIST = dict(lo=1e-3, hi=1e4, buckets_per_decade=5)
# step times: µs-scale fused CPU steps to minutes-long K-step waves
_STEP_HIST = dict(lo=1e-6, hi=1e3, buckets_per_decade=10)


def cost_analysis_flops(stage) -> float:
    """FLOPs from ``cost_analysis()`` of a ``jax.stages.Lowered`` OR
    ``Compiled``, normalizing the list-of-dicts vs dict return across jax
    versions; 0.0 when the backend doesn't report a cost model."""
    try:
        cost = stage.cost_analysis()
    except Exception:
        return 0.0
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        return float(cost.get("flops", 0.0) or 0.0)
    except Exception:
        return 0.0


def _arg_specs(args, kwargs) -> Tuple[tuple, dict]:
    """Skeleton of a call's arguments as jit keyed its caches on them:
    arrays become ``ShapeDtypeStruct`` (metadata survives donation; no
    buffers are retained) with their weak type and, where the array is
    committed to a sharding, that sharding: jit reads a spec with a sharding
    as a committed argument and one without as an uncommitted one, so
    ``trace`` and ``lower`` on these specs return what the dispatch traced
    and lowered. Statics pass through untouched."""
    import jax

    def spec(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(
                tuple(x.shape), x.dtype,
                sharding=x.sharding if getattr(x, "committed", False) else None,
                weak_type=getattr(x, "weak_type", False))
        return x

    return (jax.tree_util.tree_map(spec, args),
            jax.tree_util.tree_map(spec, kwargs))


def named_residual_bytes(closed_jaxpr, names=None):
    """-> (kept, offered) bytes of the values named ``names`` (``jax.
    ad_checkpoint.checkpoint_name``; default ``ops/remat.py::KEPT_NAMES``:
    the attention kernels' residuals and every candidate the rule may
    choose). ``kept``: what a traced program's recomputations keep from the
    forward instead of making it again: the named values computed outside a
    recomputation (the body of a differentiated ``jax.checkpoint`` equation)
    less those computed inside one, from the shapes in the jaxpr, a ``scan``
    body counted ``length`` times; a name the policy did not choose is made
    again inside and cancels. 0 for a program that recomputes nothing: its
    autodiff keeps every residual, named or not. ``offered``: all the named
    values of the forward, those too that their layer did not choose
    (``ops/remat.py::AGAIN``): what keeping every name would hold."""
    from jax._src import core
    from jax._src.ad_checkpoint import remat_p
    from ..ops.remat import AGAIN
    if names is None:
        from ..ops.remat import KEPT_NAMES as names
    outside = inside = offered = recomputations = 0

    def walk(jaxpr, times, recomputed):
        nonlocal outside, inside, offered, recomputations
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "name" and eqn.params["name"].removesuffix(AGAIN) in names:
                nbytes = times * sum(v.aval.size * v.aval.dtype.itemsize
                                     for v in eqn.outvars)
                if not recomputed:
                    offered += nbytes
                if eqn.params["name"] in names:
                    if recomputed:
                        inside += nbytes
                    else:
                        outside += nbytes
            again = eqn.primitive is remat_p and eqn.params["differentiated"]
            recomputations += bool(again)
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub, times * (eqn.params["length"] if prim == "scan" else 1),
                     recomputed or again)

    walk(closed_jaxpr.jaxpr, 1, False)
    return (max(0, outside - inside) if recomputations else 0), offered


def kept_residual_bytes(closed_jaxpr, names=None) -> int:
    """``named_residual_bytes``' kept bytes."""
    return named_residual_bytes(closed_jaxpr, names)[0]


class WatchedJit:
    """Transparent wrapper around one jitted program. Forwards everything
    (``lower``, ``clear_cache``, ...) so callers — including the flops
    profiler's ``hasattr(fn, "lower")`` probe — can't tell the difference;
    adds per-dispatch compile/hit classification and lazy FLOPs."""

    def __init__(self, fn, key: str, watch: "CompileWatch"):
        self._fn = fn
        self.key = key
        self._watch = watch
        self._calls = 0
        self.dispatches = 0       # read by TrainInstruments.publish()
        self._flops: Optional[float] = None
        self._kept_bytes = self._offered_bytes = 0
        self._flops_spec = None

    def _cache_entries(self) -> Optional[int]:
        try:
            return int(self._fn._cache_size())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_entries()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        after = self._cache_entries()
        if after is None:
            # no jit cache introspection: first call is the compile
            compiled, retrace = self._calls == 0, False
        else:
            compiled = after > (before or 0)
            retrace = compiled and bool(before)
        self._calls += 1
        self.dispatches += 1
        if compiled:
            # wall of a compiling call ≈ trace + compile: execution is
            # dispatched async, so the device work barely contributes
            dt = time.perf_counter() - t0
            self._watch.on_compile(self.key, dt, retrace)
            if self._flops_spec is None:
                try:
                    self._flops_spec = _arg_specs(args, kwargs)
                except Exception:
                    pass
                # real programs (compile cost ≫ lowering cost): resolve the
                # cost analysis NOW, inside the compile event — deferring it
                # would bill the first steady-state publish() a
                # whole-program lowering. Tiny programs (unit tests) stay
                # lazy: their lowering is milliseconds wherever it lands,
                # and doing it eagerly taxes every engine construction.
                if dt > 0.5:
                    self.program_flops()
        else:
            self._watch.on_hit(self.key)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def program_flops(self) -> float:
        """Cost-analysis FLOPs of one dispatch of this program. Lazy and
        cached: the first call asks jit for the trace and the lowering of
        the captured arg specs, which are the dispatch's own and come from
        jit's caches (``_arg_specs``), walks the jaxpr for the named
        residuals and runs HLO-level cost analysis on the LOWERED module
        (0.0 on a TPU, where a lowered module has none). It deliberately
        never calls ``.compile()``, which would pay a full fresh XLA compile
        (the AOT path shares no executable cache with dispatch). Never
        invoked on the step path."""
        if self._flops is not None:
            return self._flops
        if self._flops_spec is None:
            return 0.0
        a, k = self._flops_spec
        # timed: a spec that misses jit's caches costs a whole-program trace
        # and lowering inside the compile stall, and this is where it shows
        with get_tracer().scope("ds.compile.cost_analysis",
                                key=self.key) as scope:
            try:
                traced = self._fn.trace(*a, **k)
                self._kept_bytes, self._offered_bytes = named_residual_bytes(
                    traced.jaxpr)
                self._flops = cost_analysis_flops(traced.lower())
            except Exception:
                self._flops = 0.0
        self._watch.on_cost_analysis(self.key, scope.dur_s)
        return self._flops

    def program_kept_bytes(self) -> Tuple[int, int]:
        """``named_residual_bytes`` of this program, (kept, offered), read
        off the same trace as ``program_flops`` (0 before the program has
        compiled)."""
        self.program_flops()
        return self._kept_bytes, self._offered_bytes


class CompileWatch:
    """Per-compile-key compile telemetry sink. Lazily creates one labeled
    series per key:

    - ``ds_compile_seconds{key=...}``: wall seconds of compiling calls
    - ``ds_compiles_total{key=...}``: compile events (first + retraces)
    - ``ds_recompiles_total{key=...}``: retraces only (cache already warm
      — the "why is my steady state recompiling" counter)
    - ``ds_compile_cache_hits_total{key=...}``: dispatches served from the
      jit cache
    - ``ds_cost_analysis_seconds_total{key=...}``: wall seconds of the
      program's cost analysis (``WatchedJit.program_flops``, once a
      program); not part of ``ds_compile_seconds``

    ``on_compile_seconds`` (optional) feeds measured compile wall into the
    goodput ledger's pending-compile pool."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 on_compile_seconds=None):
        self.registry = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        self._per_key = {}
        self._on_compile_seconds = on_compile_seconds

    def _handles(self, key: str):
        h = self._per_key.get(key)
        if h is None:
            with self._lock:
                h = self._per_key.get(key)
                if h is None:
                    lab = {"key": key}
                    reg = self.registry
                    h = (reg.histogram(
                            "ds_compile_seconds",
                            "Wall seconds of jit trace+compile per compile "
                            "key (first call and retraces)",
                            labels=lab, **_COMPILE_HIST),
                         reg.counter(
                            "ds_compiles_total",
                            "Compile events per compile key", labels=lab),
                         reg.counter(
                            "ds_recompiles_total",
                            "Retraces per compile key (compile with a warm "
                            "cache — steady state should hold at 0)",
                            labels=lab),
                         reg.counter(
                            "ds_compile_cache_hits_total",
                            "Dispatches served from the jit cache per "
                            "compile key", labels=lab),
                         reg.counter(
                            "ds_cost_analysis_seconds_total",
                            "Wall seconds reading a program's FLOPs and named "
                            "residual bytes off jit's trace and lowering per "
                            "compile key (once a program; beside, not in, "
                            "ds_compile_seconds)", labels=lab))
                    self._per_key[key] = h
        return h

    def wrap(self, fn, key: str) -> Optional[WatchedJit]:
        if fn is None:
            return None
        if isinstance(fn, WatchedJit):
            return fn
        return WatchedJit(fn, key, self)

    def on_compile(self, key: str, seconds: float, retrace: bool) -> None:
        hist, compiles, recompiles = self._handles(key)[:3]
        hist.record(seconds)
        compiles.inc()
        if retrace:
            recompiles.inc()
        cb = self._on_compile_seconds
        if cb is not None:
            cb(seconds)

    def on_hit(self, key: str) -> None:
        self._handles(key)[3].inc()

    def on_cost_analysis(self, key: str, seconds: float) -> None:
        self._handles(key)[4].inc(seconds)

    def counts(self, key: str) -> dict:
        """Introspection helper for tests/consoles."""
        hist, compiles, recompiles, hits, cost = self._handles(key)
        return {"compiles": compiles.value, "recompiles": recompiles.value,
                "hits": hits.value, "compile_seconds": hist.sum,
                "cost_analysis_seconds": cost.value}


def refresh_memory_gauges(registry: Optional[MetricsRegistry] = None) -> dict:
    """Device-memory gauges from the first local device's allocator stats
    (live bytes, peak watermark, capacity). Backends without memory stats
    (CPU) produce no gauges — returns whatever was set."""
    reg = registry if registry is not None else get_registry()
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        stats = {}
    out = {}
    for src, name, help_ in (
            ("bytes_in_use", "ds_device_bytes_in_use",
             "Live device (HBM) bytes in use"),
            ("peak_bytes_in_use", "ds_device_peak_bytes_in_use",
             "Peak device bytes watermark since process start"),
            ("bytes_limit", "ds_device_bytes_limit",
             "Device memory capacity visible to the allocator")):
        if src in stats:
            v = float(stats[src])
            reg.gauge(name, help_).set(v)
            out[name] = v
    return out


_BACKEND_LISTENER_INSTALLED = False


def install_backend_compile_listener(
        registry: Optional[MetricsRegistry] = None) -> bool:
    """Tap jax's ``/jax/core/compile/backend_compile_duration`` monitoring
    event into ``ds_xla_backend_compile_seconds`` — XLA compile wall as the
    runtime itself measures it, including compiles outside any watched
    entry point. Idempotent per process (jax.monitoring offers no listener
    removal); returns False when the hook isn't available."""
    global _BACKEND_LISTENER_INSTALLED
    if _BACKEND_LISTENER_INSTALLED:
        return True
    reg = registry if registry is not None else get_registry()
    hist = reg.histogram(
        "ds_xla_backend_compile_seconds",
        "XLA backend_compile wall seconds (jax.monitoring event, all "
        "compiles process-wide)", **_COMPILE_HIST)
    try:
        import jax.monitoring as _monitoring

        def _on_event(name, secs, **kw):
            if name.endswith("backend_compile_duration"):
                hist.record(float(secs))

        _monitoring.register_event_duration_secs_listener(_on_event)
    except Exception:
        return False
    _BACKEND_LISTENER_INSTALLED = True
    return True


def peak_device_flops() -> float:
    """Per-device peak bf16 FLOP/s from the accelerator abstraction (the
    MFU denominator). A TPU whose kind has no published peak raises; a
    CPU gets the accelerator ABC's default."""
    from ..accelerator import get_accelerator
    return max(1.0, float(get_accelerator().peak_bf16_flops()))


class TrainInstruments:
    """Pre-resolved training-side metric handles (the engine's sibling of
    ``ServingInstruments``): per-step wall histogram, MFU gauge, the
    compile watch, and the goodput ledger — one object the engine threads
    through its step boundaries and window drains.

    Per-step cost (``step_mark``): one ``perf_counter``, a histogram bump
    per optimizer step, one ledger mark. Everything derived — FLOPs cost
    analysis, memory stats, MFU, goodput fraction — happens in
    ``publish()`` at the drain/monitor cadence."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 ledger=None, compile_watch: Optional[CompileWatch] = None,
                 peak_flops: Optional[float] = None):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        self.ledger = ledger
        self.step_seconds = reg.histogram(
            "ds_train_step_seconds",
            "Wall seconds per optimizer step at the host dispatch boundary "
            "(a K-step fused dispatch records K samples of wall/K)",
            **_STEP_HIST)
        self.mfu = reg.gauge(
            "ds_train_mfu",
            "Model FLOPs utilization over the last publish interval: "
            "dispatched program FLOPs (XLA cost analysis) / wall / "
            "peak_bf16_flops")
        self.compile_watch = compile_watch or CompileWatch(
            registry=reg,
            on_compile_seconds=(ledger.note_compile
                                if ledger is not None else None))
        self.peak_flops = (peak_device_flops() if peak_flops is None
                           else max(1.0, float(peak_flops)))
        self._programs = []     # [WatchedJit, dispatches_already_published]
        self._t_last = None     # step-boundary clock (set by start_clock)
        self._mfu_t0 = None

    # -- program registry --------------------------------------------------

    def watch_program(self, fn, key: str):
        """Wrap a jitted program for compile telemetry AND register it for
        FLOPs/MFU accounting. Idempotent on already-wrapped programs."""
        if fn is None:
            return None
        if isinstance(fn, WatchedJit):
            return fn
        w = self.compile_watch.wrap(fn, key)
        self._programs.append([w, 0])
        return w

    # -- step boundary (hot path) -----------------------------------------

    def start_clock(self, now: Optional[float] = None) -> None:
        """Anchor the step clock — call once when the engine is ready to
        train, so the first step's sample excludes construction time."""
        now = time.perf_counter() if now is None else now
        self._t_last = now
        self._mfu_t0 = now

    def step_mark(self, steps: int = 1) -> None:
        """Record the wall since the previous boundary as ``steps``
        optimizer steps (K samples of wall/K for a fused K-step dispatch)
        and attribute the interval to goodput "useful_step"."""
        now = time.perf_counter()
        if self._t_last is None:
            self.start_clock(now)
            if self.ledger is not None:
                self.ledger.mark("useful_step")
            return
        dt = max(0.0, now - self._t_last)
        self._t_last = now
        n = max(1, int(steps))
        per = dt / n
        for _ in range(n):
            self.step_seconds.record(per)
        if self.ledger is not None:
            self.ledger.mark("useful_step")

    # -- publish cadence ---------------------------------------------------

    def publish(self) -> None:
        """Refresh every derived view: device-memory gauges, the goodput
        fraction, and MFU over the interval since the last publish. Runs
        at the async-window drain (or per step in sync mode) — the lazy
        ``program_flops`` cost analyses land here, not on the step path."""
        refresh_memory_gauges(self.registry)
        if self.ledger is not None:
            self.ledger.publish()
        now = time.perf_counter()
        if self._mfu_t0 is None:
            self._mfu_t0 = now
            return
        wall = now - self._mfu_t0
        flops = 0.0
        any_dispatch = False
        for ent in self._programs:
            prog, seen = ent
            d = prog.dispatches - seen
            if d > 0:
                any_dispatch = True
                f = prog.program_flops()
                if f > 0:
                    flops += f * d
                if not seen:
                    kept, offered = prog.program_kept_bytes()
                    self.registry.gauge(
                        "ds_remat_kept_bytes",
                        "Bytes of the named values (the attention kernels' "
                        "output and log-sum-exp, and the candidates the "
                        "budget admitted: ops/remat.py) a dispatch of the "
                        "program keeps from its forward to a recomputed "
                        "layer's backward, from the shapes in its trace; 0 "
                        "for a program that recomputes nothing",
                        labels={"key": prog.key}).set(float(kept))
                    self.registry.gauge(
                        "ds_remat_offered_bytes",
                        "Bytes of every named value of the program's "
                        "forward: what ds_remat_kept_bytes would read with "
                        "room for the whole list",
                        labels={"key": prog.key}).set(float(offered))
                ent[1] = prog.dispatches
        if any_dispatch and wall > 0 and flops > 0:
            self.mfu.set(min(1.0, flops / (wall * self.peak_flops)))
        if any_dispatch:
            self._mfu_t0 = now

    def publish_done(self) -> None:
        """Close a publish: what followed :meth:`publish` (the monitor
        bridge, the textfile) is attributed now, to the category the next
        step's mark would give it, so a run's last scrape accounts for its
        whole wall clock."""
        if self.ledger is not None:
            self.ledger.mark("useful_step")
            self.ledger.publish()
