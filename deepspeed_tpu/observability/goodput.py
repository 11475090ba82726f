"""Wall-clock goodput ledger: attribute every training second to a cause.

``GoodputLedger`` partitions the wall clock of a training process into
exhaustive, non-overlapping categories and exports them as the labeled
counter family ``ds_goodput_seconds_total{category=...}`` plus a derived
``ds_goodput_fraction`` gauge (useful-step share). The invariant the
acceptance tests check: the categories SUM to the elapsed wall clock
(within the slack of whatever has elapsed since the last attribution
point), so "where did my training day go" is answerable from one scrape.

Attribution model — two complementary mechanisms:

- ``mark(category)``: attribute everything since the previous mark (the
  *cursor*) to ``category``. The engine calls ``mark("useful_step")`` at
  each optimizer-step boundary and as each publish ends, so in steady
  state the whole step wall (dispatch + device wait + dataloader, and the
  exports that follow a window's last step) lands in ``useful_step``.
- ``span(category)``: a context manager for excursions with clear
  boundaries (checkpoint save/load, anomaly rollback, the async-window
  host fetch). A span is also a ``ds.train.<category>`` scope of the
  process-wide tracer (observability/tracing.py). A span records its own
  duration directly AND banks it as *foreign* time, which the next
  ``mark`` subtracts from the cursor
  interval — the same second is never counted twice. Nested spans fold
  into the outermost category (a rollback that internally loads a
  checkpoint is all "anomaly_rollback").

Compile time has no clean boundary of its own — it surfaces as an
unusually long step call — so the compile watch (observability/xla.py)
reports measured compile seconds via ``note_compile``; the next ``mark``
carves that much out of the interval into "compile" before attributing
the remainder. "restart" closes engine construction + auto-resume time
(one ``mark("restart")`` at the end of ``__init__``).

The ledger is host-side only and lock-cheap: one ``perf_counter`` and a
few float ops per mark/span. A test can inject a fake ``clock``.
"""

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from .metrics import MetricsRegistry, get_registry
from .tracing import get_tracer

CATEGORIES = (
    "useful_step",      # optimizer-step wall (dispatch + device + data wait)
    "compile",          # jit trace + XLA compile (from the compile watch)
    "host_sync_stall",  # blocking device→host fetches (async-window drain)
    "checkpoint_save",
    "checkpoint_load",
    "anomaly_rollback",  # sentry-triggered restore-to-last-good
    "restart",          # engine construction, auto-resume, warm restart
    "param_gather_stall",  # ZeRO-3 whole-model gather (full_params/export)
)

_HELP = ("Wall-clock seconds attributed to each training-time category "
         "(categories sum to elapsed wall clock)")


class GoodputLedger:
    """See module docstring. One instance per training engine."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 clock=time.perf_counter):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        self._clock = clock
        # eager per-category series so a scrape always shows all categories
        # (a zero is information; an absent series is a parse special-case)
        self._counters = {
            c: reg.counter("ds_goodput_seconds_total", _HELP,
                           labels={"category": c})
            for c in CATEGORIES
        }
        self.fraction = reg.gauge(
            "ds_goodput_fraction",
            "useful_step share of all attributed wall-clock seconds")
        self._lock = threading.RLock()
        now = clock()
        self._t0 = now
        self._cursor = now
        self._foreign = 0.0          # span seconds already attributed since cursor
        self._pending_compile = 0.0  # compile seconds awaiting the next mark
        self._span_depth = 0

    # -- recording ---------------------------------------------------------

    def add(self, category: str, seconds: float) -> None:
        """Directly attribute ``seconds`` to ``category`` (no cursor move)."""
        if seconds > 0:
            self._counters[category].inc(seconds)

    def note_compile(self, seconds: float) -> None:
        """Compile watch callback: carve this much out of the next marked
        interval into the "compile" category."""
        if seconds > 0:
            with self._lock:
                self._pending_compile += seconds

    @contextmanager
    def span(self, category: str):
        """Attribute the enclosed wall time to ``category`` and bank it so
        the next ``mark`` doesn't attribute it again. Nested spans record
        nothing themselves — the outermost category wins."""
        with self._lock:
            self._span_depth += 1
            nested = self._span_depth > 1
        t0 = self._clock()
        try:
            # the same excursion on the span API's two sinks (the tracer's
            # ring and, in a profiler session, the device trace's clock)
            with get_tracer().scope("ds.train." + category):
                yield
        finally:
            dt = self._clock() - t0
            with self._lock:
                self._span_depth -= 1
                if not nested:
                    self.add(category, dt)
                    self._foreign += max(0.0, dt)

    def mark(self, category: str = "useful_step") -> float:
        """Attribute the interval since the previous mark to ``category``
        (minus banked span time, minus pending compile seconds which go to
        "compile"). Returns the raw interval length."""
        now = self._clock()
        with self._lock:
            elapsed = max(0.0, now - self._cursor)
            residual = max(0.0, elapsed - self._foreign)
            carved = min(self._pending_compile, residual)
            if carved > 0:
                self.add("compile", carved)
                self._pending_compile -= carved
            self.add(category, residual - carved)
            self._cursor = now
            self._foreign = 0.0
        return elapsed

    # -- derived views -----------------------------------------------------

    def totals(self) -> Dict[str, float]:
        return {c: m.value for c, m in self._counters.items()}

    def attributed_seconds(self) -> float:
        return sum(m.value for m in self._counters.values())

    def wall_seconds(self) -> float:
        return self._clock() - self._t0

    def goodput_fraction(self) -> float:
        total = self.attributed_seconds()
        return self._counters["useful_step"].value / total if total else 0.0

    def publish(self) -> float:
        """Refresh the derived gauge (called at the registry-publish
        cadence, i.e. the async-window drain)."""
        f = self.goodput_fraction()
        self.fraction.set(f)
        return f
