"""Process-wide metrics registry: counters, gauges, log-bucketed histograms.

Design constraints (the scheduler tick is the hottest caller):

- ``record()``/``inc()`` are allocation-light: a bisect into a precomputed
  edge list and one numpy bucket bump under a per-metric lock. No dict
  lookups on the hot path — callers pre-resolve metric handles once.
- Histograms are log-bucketed with FIXED-size numpy count arrays sized at
  construction (default: 10 buckets/decade), so memory is bounded no
  matter how many samples land. Quantiles (p50/p90/p99...) are derived at
  READ time from the bucket counts — recording never sorts or stores raw
  samples. A derived quantile is exact to within one bucket (relative
  error ≤ ``10**(1/buckets_per_decade)`` ≈ 1.26× at the default), which
  is the standard Prometheus-histogram contract.
- Everything renders to Prometheus text exposition format
  (``render_prometheus``) and to the ``monitor/`` fan-out's
  ``(name, value, step)`` event schema (``to_events``), so serving and
  training share one pipeline.

The module-level registry (:func:`get_registry`) is process-wide on
purpose: the serving scheduler, engine dispatch boundaries, journal, and
supervisor all record into one namespace, and ``GET /metrics`` scrapes
one coherent snapshot. Tests and benches needing isolation construct
their own :class:`MetricsRegistry` (or diff ``snapshot()`` deltas).
"""

import os
import threading
from bisect import bisect_left
from math import ceil, log10, sqrt
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


def _fmt(v) -> str:
    """Prometheus sample value: shortest round-trippable decimal."""
    if v != v:  # NaN
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    return format(float(v), ".10g")


def _label_str(labels: Optional[dict], extra: Tuple[Tuple[str, str], ...] = ()) -> str:
    items = tuple(sorted((labels or {}).items())) + extra
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in items) + "}"


class Counter:
    """Monotonic counter. ``inc`` only ever adds a non-negative amount."""

    __slots__ = ("name", "help", "labels", "_value", "_lock")

    def __init__(self, name: str, help: str = "", labels: Optional[dict] = None):
        self.name, self.help, self.labels = name, help, labels
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name}: negative increment {n}")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"type": "counter", "value": self._value}

    def render(self) -> List[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} counter")
        lines.append(f"{self.name}{_label_str(self.labels)} {_fmt(self._value)}")
        return lines


class Gauge:
    """Point-in-time value (queue depth, occupancy, adaptive K)."""

    __slots__ = ("name", "help", "labels", "_value", "_lock")

    def __init__(self, name: str, help: str = "", labels: Optional[dict] = None):
        self.name, self.help, self.labels = name, help, labels
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"type": "gauge", "value": self._value}

    def render(self) -> List[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} gauge")
        lines.append(f"{self.name}{_label_str(self.labels)} {_fmt(self._value)}")
        return lines


def _log_edges(lo: float, hi: float, buckets_per_decade: int) -> List[float]:
    """Upper bucket edges ``lo * 10**(i/bpd)`` covering [lo, hi]."""
    if not (0 < lo < hi):
        raise ValueError(f"need 0 < lo < hi, got lo={lo} hi={hi}")
    bpd = int(buckets_per_decade)
    if bpd < 1:
        raise ValueError("buckets_per_decade must be >= 1")
    n = int(ceil((log10(hi) - log10(lo)) * bpd + 1e-9)) + 1
    return [lo * 10.0 ** (i / bpd) for i in range(n)]


def quantiles_from_counts(edges: Sequence[float], counts,
                          qs: Iterable[float]) -> List[Optional[float]]:
    """Derive quantiles from log-bucket counts (``counts`` has one extra
    trailing overflow bucket beyond ``edges``). Interior buckets resolve
    to their geometric midpoint — halving the worst-case log error; the
    underflow bucket resolves to its upper edge, the overflow bucket to
    the last edge. Returns None per-q when the histogram is empty."""
    counts = np.asarray(counts)
    total = int(counts.sum())
    if total == 0:
        return [None for _ in qs]
    cum = np.cumsum(counts)
    out = []
    for q in qs:
        target = q * total
        i = int(np.searchsorted(cum, target, side="left"))
        i = min(i, len(counts) - 1)
        if i == 0:
            out.append(float(edges[0]))
        elif i >= len(edges):  # overflow bucket: clamp to the last edge
            out.append(float(edges[-1]))
        else:
            out.append(float(sqrt(edges[i - 1] * edges[i])))
    return out


class Histogram:
    """Log-bucketed histogram over (0, inf) with fixed numpy bucket counts.

    ``counts`` has ``len(edges) + 1`` slots: ``counts[i]`` holds samples in
    ``(edges[i-1], edges[i]]`` (``(0, edges[0]]`` for i=0) and the final
    slot is the +Inf overflow bucket. Recording is a bisect + one bump
    under the metric lock — cheap enough for the scheduler tick."""

    __slots__ = ("name", "help", "labels", "edges", "counts",
                 "_sum", "_count", "_lock")

    def __init__(self, name: str, help: str = "", lo: float = 1e-6,
                 hi: float = 1e3, buckets_per_decade: int = 10,
                 labels: Optional[dict] = None):
        self.name, self.help, self.labels = name, help, labels
        self.edges = _log_edges(lo, hi, buckets_per_decade)
        self.counts = np.zeros(len(self.edges) + 1, dtype=np.int64)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def record(self, v: float) -> None:
        v = float(v)
        if v < 0:
            v = 0.0  # clock skew guard: a negative duration is a 0 sample
        idx = bisect_left(self.edges, v) if v > 0 else 0
        with self._lock:
            self.counts[idx] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> Optional[float]:
        return self._sum / self._count if self._count else None

    def quantile(self, q: float) -> Optional[float]:
        return self.percentiles((q, ))[0]

    def percentiles(self, qs: Iterable[float]) -> List[Optional[float]]:
        with self._lock:
            counts = self.counts.copy()
        return quantiles_from_counts(self.edges, counts, qs)

    def snapshot(self) -> dict:
        with self._lock:
            return {"type": "histogram", "count": self._count,
                    "sum": self._sum, "counts": self.counts.copy(),
                    "edges": self.edges}

    def render(self) -> List[str]:
        with self._lock:
            counts = self.counts.copy()
            s, c = self._sum, self._count
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} histogram")
        cum = 0
        for edge, n in zip(self.edges, counts[:-1]):
            cum += int(n)
            le = _label_str(self.labels, (("le", _fmt(edge)), ))
            lines.append(f"{self.name}_bucket{le} {cum}")
        cum += int(counts[-1])
        le = _label_str(self.labels, (("le", "+Inf"), ))
        lines.append(f"{self.name}_bucket{le} {cum}")
        lab = _label_str(self.labels)
        lines.append(f"{self.name}_sum{lab} {_fmt(s)}")
        lines.append(f"{self.name}_count{lab} {c}")
        return lines


class MetricsRegistry:
    """Named metric store. ``counter``/``gauge``/``histogram`` return the
    existing instance on re-request (handles are meant to be resolved once
    and kept), raising if the name is already bound to another type.

    A metric with ``labels`` is one SERIES of a metric family: the store
    key is ``name{labels}``, so ``counter("x", labels={"k": "a"})`` and
    ``counter("x", labels={"k": "b"})`` coexist and render under one
    ``# TYPE x`` header (compile keys, goodput categories)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}

    def _get_or_make(self, cls, name, kwargs):
        key = name + _label_str(kwargs.get("labels"))
        with self._lock:
            m = self._metrics.get(key)
            if m is not None:
                if not isinstance(m, cls):
                    raise TypeError(f"metric {key!r} already registered as "
                                    f"{type(m).__name__}, not {cls.__name__}")
                return m
            m = cls(name, **kwargs)
            self._metrics[key] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Optional[dict] = None) -> Counter:
        return self._get_or_make(Counter, name,
                                 dict(help=help, labels=labels))

    def gauge(self, name: str, help: str = "",
              labels: Optional[dict] = None) -> Gauge:
        return self._get_or_make(Gauge, name, dict(help=help, labels=labels))

    def histogram(self, name: str, help: str = "", lo: float = 1e-6,
                  hi: float = 1e3, buckets_per_decade: int = 10,
                  labels: Optional[dict] = None) -> Histogram:
        return self._get_or_make(
            Histogram, name,
            dict(help=help, lo=lo, hi=hi,
                 buckets_per_decade=buckets_per_decade, labels=labels))

    def get(self, name: str, labels: Optional[dict] = None):
        return self._metrics.get(name + _label_str(labels))

    def series(self, name: str) -> List[object]:
        """Every registered series of a metric family, labeled or not."""
        with self._lock:
            items = sorted(self._metrics.items())
        return [m for _, m in items if m.name == name]

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def snapshot(self) -> Dict[str, dict]:
        """Point-in-time copy of every metric — diffable, so benches can
        compute interval percentiles from before/after deltas."""
        with self._lock:
            items = list(self._metrics.items())
        return {name: m.snapshot() for name, m in items}

    def reset(self) -> None:
        """Zero every metric in place (handles stay valid) — tests and
        bench reruns in one process."""
        with self._lock:
            items = list(self._metrics.values())
        for m in items:
            with m._lock:
                if isinstance(m, Histogram):
                    m.counts[:] = 0
                    m._sum, m._count = 0.0, 0
                else:
                    m._value = 0.0

    def render_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4 (one scrape body).
        Labeled series of the same family (sort-adjacent, since the store
        key is ``name{labels}``) share one ``# HELP``/``# TYPE`` header."""
        with self._lock:
            items = sorted(self._metrics.items())
        lines: List[str] = []
        seen_families = set()
        for _, m in items:
            rendered = m.render()
            if m.name in seen_families:
                rendered = [ln for ln in rendered if not ln.startswith("#")]
            else:
                seen_families.add(m.name)
            lines.extend(rendered)
        return "\n".join(lines) + "\n" if lines else ""

    def write_textfile(self, path: str) -> str:
        """Prometheus *textfile* export for processes with no HTTP server
        (training runs): render the full registry and atomically replace
        ``path`` (write to ``path + ".tmp"`` then ``os.replace``), so a
        node-exporter-style collector or ``ds_top --file`` never observes
        a torn body. Recreates the parent directory if it was deleted."""
        body = self.render_prometheus()
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # unique tmp per writer: a shared ".tmp" would let one writer's
        # replace publish another's half-written body
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        with open(tmp, "w") as f:
            f.write(body)
            f.flush()
        os.replace(tmp, path)
        return path

    def to_events(self, step: int, prefix: str = "",
                  percentiles: Sequence[float] = (0.5, 0.9, 0.99)):
        """Bridge into the ``monitor/`` fan-out: the same
        ``(name, value, step)`` triples training writers consume.
        Histograms emit ``_count``/``_mean`` plus one derived ``_pNN`` per
        requested percentile (skipped while empty)."""
        with self._lock:
            items = sorted(self._metrics.items())
        events = []
        for name, m in items:
            if isinstance(m, Histogram):
                if not m.count:
                    continue
                events.append((f"{prefix}{name}_count", float(m.count), step))
                events.append((f"{prefix}{name}_mean", float(m.mean), step))
                for q, v in zip(percentiles, m.percentiles(percentiles)):
                    if v is not None:
                        events.append(
                            (f"{prefix}{name}_p{int(round(q * 100))}",
                             float(v), step))
            else:
                events.append((f"{prefix}{name}", float(m.value), step))
        return events


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every subsystem records into."""
    return _REGISTRY
