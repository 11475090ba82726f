"""Compile & runtime observability for the XLA layer.

Four pieces, all host-side and off the per-step critical path:

- :class:`CompileWatch` + :class:`WatchedJit`: transparent wrappers around
  jitted callables that classify every dispatch as compile / retrace /
  cache hit **per compile key** and record the wall time of compiling
  calls into labeled histograms (``ds_compile_seconds{key=...}``). The
  detection mechanism is ``fn._cache_size()`` growth across a call — one
  cheap C call per dispatch; when the attribute is missing (plain
  function wrappers, e.g. the grad-comm step builder) the first call
  counts as the compile and later calls as hits.
- The anatomy of a compiling call (:func:`install_backend_compile_listener`,
  the program's one tap on ``jax.monitoring``): jax reports, each at its
  end and on the thread that compiled, the seconds it traced a function,
  turned the jaxpr into an MLIR module and had the backend compile it or
  load it from the persistent cache, with the function's name, and whether
  that cache hit, what the load took and what it saved. The tap stamps each
  piece on the tracer ring's clock as it arrives (``t1 = time.monotonic()``,
  ``t0 = t1 - seconds``) and holds it on its thread; a trace or a lowering
  that ends inside another piece (every inner ``jit``'s trace, a helper a
  lowering rule traces) belongs to the outermost and is dropped. A
  :class:`WatchedJit` call that turned out to compile then claims what
  began at or after its own start: per-key counters
  (``ds_compile_trace_seconds_total{key}``, ``..._lower_...``,
  ``..._backend_...``, ``ds_compile_cache_load_seconds_total{key}``,
  ``ds_compile_persistent_hits_total{key}``, ``..._misses_total{key}``) and
  the spans ``ds.compile.call`` > ``ds.compile.trace`` / ``.lower`` /
  ``.backend`` / ``.cost_analysis`` in the tracer's kept ring. What no
  watched call claims (model init, eager ops, a reference) is charged to
  ``key="-"`` at the next claim or :meth:`TrainInstruments.publish`, with a
  span only where a piece took ``SPAN_FLOOR_S`` or more. A dispatch that
  hits jit's cache does none of this. ``ds_xla_backend_compile_seconds``
  stays the unlabeled histogram of every backend compile process-wide.
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
"""

import threading
import time
from typing import Optional, Tuple

from .metrics import Histogram, MetricsRegistry, get_registry
from .tracing import get_tracer

# compile times span ~ms (tiny CPU programs) to ~1h (giant TPU programs)
_COMPILE_HIST = dict(lo=1e-3, hi=1e4, buckets_per_decade=5)
# step times: µs-scale fused CPU steps to minutes-long K-step waves
_STEP_HIST = dict(lo=1e-6, hi=1e3, buckets_per_decade=10)

# the pieces of one compile as jax reports them (jax 0.9.0: jax/_src/
# dispatch.py, compiler.py, compilation_cache.py). Each duration event is
# preceded by a scalar event of the same name when its piece BEGINS.
_PIECES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "backend"}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_STATE = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
# the key of what no watched call claimed
UNCLAIMED = "-"
# an unclaimed piece gets a span in the kept ring only from this length on:
# a cell runs some hundred small programs before its window
SPAN_FLOOR_S = 0.25
# pieces one thread may hold before they are charged to UNCLAIMED unasked
_PENDING_CAP = 4096


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
        t0 = time.monotonic()       # the tracer ring's clock
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
            # the wall of a compiling call: the trace, the lowering and the
            # backend's compile or load, which the tap's pieces split below,
            # plus what jax does around them (the caches' keys, the argument
            # handling, the dispatch). Execution is dispatched async, so the
            # device work barely contributes
            dt = time.monotonic() - t0
            self._watch.on_compile(self.key, dt, retrace)
            with get_tracer().scope("ds.compile.call", annotate=False,
                                    key=self.key, retrace=retrace) as call:
                call.began(t0)
                call.args["programs"], call.args["persistent"] = (
                    self._watch.claim_events(self.key, t0, call.sid))
                if self._flops_spec is None:
                    try:
                        self._flops_spec = _arg_specs(args, kwargs)
                    except Exception:
                        pass
                    # real programs (compile cost ≫ lowering cost): resolve
                    # the cost analysis NOW, inside the compile event —
                    # deferring it would bill the first steady-state
                    # publish() a whole-program lowering. Tiny programs
                    # (unit tests) stay lazy: their lowering is milliseconds
                    # wherever it lands, and doing it eagerly taxes every
                    # engine construction.
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
        invoked on the step path. Its wall is the ``ds.compile.cost_analysis``
        scope: inside a compiling call a child of that call's
        ``ds.compile.call``, which ends after it. A spec that misses jit's
        caches traces and lowers again AFTER the call has claimed its
        pieces: those seconds are then in this scope and, as pieces no call
        claimed, under ``key="-"``."""
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
    - ``ds_compile_cache_hits_total{key=...}``: dispatches served from
      jit's in-memory cache (NOT the persistent cache: that is
      ``ds_compile_persistent_hits_total``)
    - ``ds_cost_analysis_seconds_total{key=...}``: wall seconds of the
      program's cost analysis (``WatchedJit.program_flops``, once a
      program); not part of ``ds_compile_seconds``
    - the pieces jax reports of a compiling call (:meth:`charge`):
      ``ds_compile_trace_seconds_total``, ``ds_compile_lower_seconds_total``,
      ``ds_compile_backend_seconds_total`` (a load from the persistent cache
      included), ``ds_compile_cache_load_seconds_total`` (that load alone),
      ``ds_compile_persistent_hits_total``,
      ``ds_compile_persistent_misses_total``, all ``{key=...}``

    ``on_compile_seconds`` (optional) feeds measured compile wall into the
    goodput ledger's pending-compile pool."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 on_compile_seconds=None):
        self.registry = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        self._per_key = {}
        self._per_key_pieces = {}
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
                            "Dispatches served from jit's in-memory cache "
                            "per compile key (not the persistent cache: "
                            "ds_compile_persistent_hits_total)", labels=lab),
                         reg.counter(
                            "ds_cost_analysis_seconds_total",
                            "Wall seconds reading a program's FLOPs and named "
                            "residual bytes off jit's trace and lowering per "
                            "compile key (once a program; beside, not in, "
                            "ds_compile_seconds)", labels=lab))
                    self._per_key[key] = h
        return h

    def _piece_handles(self, key: str) -> dict:
        h = self._per_key_pieces.get(key)
        if h is None:   # the registry gets or makes: a race makes the same six
            lab = {"key": key}
            counter = self.registry.counter
            h = self._per_key_pieces[key] = {
                "trace": counter(
                    "ds_compile_trace_seconds_total",
                    "Seconds jax traced the functions of compiling calls "
                    "into jaxprs per compile key, a trace inside another "
                    "piece left to that piece (jax.monitoring "
                    "jaxpr_trace_duration)", labels=lab),
                "lower": counter(
                    "ds_compile_lower_seconds_total",
                    "Seconds jax turned the jaxprs of compiling calls into "
                    "MLIR modules per compile key "
                    "(jaxpr_to_mlir_module_duration)", labels=lab),
                "backend": counter(
                    "ds_compile_backend_seconds_total",
                    "Seconds the backend compiled the programs of compiling "
                    "calls, or loaded them from the persistent cache, per "
                    "compile key (backend_compile_duration)", labels=lab),
                "load": counter(
                    "ds_compile_cache_load_seconds_total",
                    "Seconds of ds_compile_backend_seconds_total spent "
                    "reading executables back from the persistent cache per "
                    "compile key (cache_retrieval_time_sec)", labels=lab),
                "hit": counter(
                    "ds_compile_persistent_hits_total",
                    "Programs loaded from the persistent compilation cache "
                    "per compile key", labels=lab),
                "miss": counter(
                    "ds_compile_persistent_misses_total",
                    "Programs compiled and written to the persistent "
                    "compilation cache per compile key (one the cache is "
                    "off for, or does not keep, counts as neither)",
                    labels=lab)}
        return h

    def charge(self, key: str, pieces, parent: Optional[int] = None,
               span_floor_s: float = 0.0) -> Tuple[int, str]:
        """Charge ``pieces`` (the tap's records ``(kind, t0, t1, fun_name,
        cache)``) to ``key``: the per-key counters, and for each piece of
        ``span_floor_s`` or more a ``ds.compile.<kind>`` span under
        ``parent``. -> (backend programs among them, ``hit`` | ``miss`` |
        ``mixed`` | ``off``: what the persistent cache did for them)."""
        h = self._piece_handles(key)
        tracer = get_tracer()
        programs, states = 0, set()
        for kind, t0, t1, fun_name, cache in pieces:
            h[kind].inc(t1 - t0)
            args = {"key": key, "fun_name": fun_name}
            if kind == "backend":
                programs += 1
                state, load_s, saved_s = cache
                args.update(cache=state, load_s=load_s, saved_s=saved_s)
                if state != "off":
                    states.add(state)
                    h[state].inc()
                    h["load"].inc(load_s)
            if t1 - t0 >= span_floor_s:
                tracer.closed_scope("ds.compile." + kind, t0, t1, parent, args)
        return programs, (states.pop() if len(states) == 1
                          else "mixed" if states else "off")

    def claim_events(self, key: str, since: float,
                     parent: Optional[int] = None) -> Tuple[int, str]:
        """Called by a watched call that turned out to compile: charge to
        ``key`` the pieces jax reported on this thread since the call began
        (``since``, ``time.monotonic()``), as children of the span
        ``parent``. -> :meth:`charge`'s summary; (0, "off") with no tap."""
        tap = _TAP
        if tap is None:
            return 0, "off"
        return tap.claim(self, key, since, parent)

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
        out = {"compiles": compiles.value, "recompiles": recompiles.value,
               "hits": hits.value, "compile_seconds": hist.sum,
               "cost_analysis_seconds": cost.value}
        for name, c in self._per_key_pieces.get(key, {}).items():
            out[{"hit": "persistent_hits", "miss": "persistent_misses"}.get(
                name, name + "_seconds")] = c.value
        return out


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


class _ThreadPieces:
    """One thread's compile pieces that no key has been charged for yet."""

    __slots__ = ("lock", "pending", "open", "cache", "self_s")

    def __init__(self):
        self.lock = threading.Lock()    # the owner against a flush from elsewhere
        self.pending = []               # (kind, t0, t1, fun_name, cache), by arrival
        self.open = 0                   # pieces begun and not ended
        self.cache = ["off", 0.0, 0.0]  # state, load_s, saved_s of the backend compile under way
        self.self_s = 0.0               # the tap's own seconds on this thread


class _CompileTap:
    """The program's listeners on ``jax.monitoring`` (module docstring).
    One a process, made by :func:`install_backend_compile_listener`: jax
    offers listeners no scope smaller than the process."""

    def __init__(self, registry: MetricsRegistry):
        self.unclaimed = CompileWatch(registry=registry)
        self._backend_hist = registry.histogram(
            "ds_xla_backend_compile_seconds",
            "XLA backend_compile wall seconds (jax.monitoring event, all "
            "compiles process-wide)", **_COMPILE_HIST)
        self._self_seconds = registry.counter(
            "ds_compile_tap_seconds_total",
            "Seconds the program's own jax.monitoring listeners took over "
            "the pieces they kept, and the charging of those, all threads (a "
            "piece dropped inside another is not timed)")
        self._local = threading.local()
        self._lock = threading.Lock()
        self._threads = []

    def _mine(self) -> _ThreadPieces:
        st = getattr(self._local, "pieces", None)
        if st is None:
            st = self._local.pieces = _ThreadPieces()
            with self._lock:
                self._threads.append(st)
        return st

    # ---- the listeners: on the compiling thread; a begin and an end for
    # every piece jax reports, which is every inner jit of a trace ----

    def on_scalar(self, name, value, **kw):
        if name in _PIECES:         # a piece begins
            (getattr(self._local, "pieces", None) or self._mine()).open += 1

    def on_event(self, name, **kw):
        state = _CACHE_STATE.get(name)
        if state is not None:
            self._mine().cache[0] = state

    def on_duration(self, name, secs, **kw):
        kind = _PIECES.get(name)
        if kind is None:
            if name == _CACHE_LOAD:
                self._mine().cache[1] = float(secs)
            elif name == _CACHE_SAVED:
                self._mine().cache[2] = float(secs)
            return
        st = getattr(self._local, "pieces", None) or self._mine()
        inside = st.open = st.open - 1 if st.open > 0 else 0
        if inside and kind != "backend":
            # a trace or a lowering that ends inside another piece (an inner
            # jit's trace in the program's, a helper traced by a lowering
            # rule) is in that piece's seconds: some hundred thousand a large
            # program, so nothing more is done for one, not even timing this
            return
        t1 = time.monotonic()
        secs = float(secs)
        cache = None
        if kind == "backend":
            # always its own piece, so that the keys sum to every compile
            self._backend_hist.record(secs)
            cache, st.cache = tuple(st.cache), ["off", 0.0, 0.0]
        with st.lock:
            st.pending.append((kind, t1 - secs, t1,
                               str(kw.get("fun_name", "")), cache))
            full = len(st.pending) >= _PENDING_CAP
            st.self_s += time.monotonic() - t1
        if full:
            self._flush(st)

    # ---- charging: off the dispatch path --------------------------------

    def _take(self, st: _ThreadPieces) -> list:
        with st.lock:
            pieces, st.pending = st.pending, []
            self._self_seconds.inc(st.self_s)
            st.self_s = 0.0
        return pieces

    def _flush(self, st: _ThreadPieces) -> None:
        t_in = time.monotonic()
        self.unclaimed.charge(UNCLAIMED, self._take(st),
                              span_floor_s=SPAN_FLOOR_S)
        self._self_seconds.inc(time.monotonic() - t_in)

    def claim(self, watch: "CompileWatch", key: str, since: float,
              parent: Optional[int]) -> Tuple[int, str]:
        """This thread's pieces that began at or after ``since`` to
        ``watch`` under ``key``; the older ones to ``UNCLAIMED``."""
        t_in = time.monotonic()
        mine, older = [], []
        for piece in self._take(self._mine()):
            (older if piece[1] < since else mine).append(piece)
        if older:
            self.unclaimed.charge(UNCLAIMED, older, span_floor_s=SPAN_FLOOR_S)
        summary = watch.charge(key, mine, parent)
        self._self_seconds.inc(time.monotonic() - t_in)
        return summary

    def flush(self) -> None:
        """Every thread's pieces to ``UNCLAIMED``. A compile under way on
        ANOTHER thread loses the pieces it has finished to ``"-"``: the sums
        over keys stay whole."""
        with self._lock:
            threads = list(self._threads)
        for st in threads:
            if st.pending or st.self_s:
                self._flush(st)


_TAP: Optional[_CompileTap] = None


def install_backend_compile_listener(
        registry: Optional[MetricsRegistry] = None) -> bool:
    """Install the program's one tap on ``jax.monitoring``
    (:class:`_CompileTap`): every backend compile process-wide into
    ``ds_xla_backend_compile_seconds`` — XLA compile wall as the runtime
    itself measures it, including compiles outside any watched entry point —
    and the pieces of each compile held for the watched call that claims
    them (module docstring). What no call claims is counted in ``registry``
    (default: the process-wide one). Idempotent per process; returns False
    when the hook isn't available."""
    global _TAP
    if _TAP is not None:
        return True
    tap = _CompileTap(registry if registry is not None else get_registry())
    try:
        import jax.monitoring as monitoring
        monitoring.register_scalar_listener(tap.on_scalar)
        monitoring.register_event_listener(tap.on_event)
        monitoring.register_event_duration_secs_listener(tap.on_duration)
    except Exception:
        return False
    _TAP = tap
    return True


def flush_compile_events() -> None:
    """Charge what no watched call has claimed, on any thread, to
    ``key="-"`` now (:meth:`TrainInstruments.publish` does)."""
    if _TAP is not None:
        _TAP.flush()


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
        flush_compile_events()
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
