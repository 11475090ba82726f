"""Span timelines in bounded ring buffers, and the one span API.

A *span* is one closed interval on the host clock: a request's queue
wait, a prefill chunk, one fused K-wave, a journal append, the finish —
or one phase of the program's own host work (a scheduler tick's
admission, a training step's dispatch, a section of engine construction).
Spans carry a small ``args`` dict (wave K, wave size, spec accept counts,
chunk tokens...).

:meth:`RequestTracer.scope` is how the program times its own phases. It
writes to two sinks:

- the tracer's bounded ring, stamped with ``time.monotonic()``, always on:
  ``(name, t0, t1, parent, uid, args, sid)`` where ``parent`` is the
  ``sid`` of the enclosing open scope on that thread, so a layer's self
  time is its duration less its children's (:meth:`RequestTracer.scopes`);
- ``jax.profiler.TraceAnnotation(name)``, so that whenever a profiler
  session is on (``POST /debug/profile``, the benchmark's traced run) the
  span is in the xplane on the **device trace's clock**, next to the
  device lines; with no session on, entering one is a flag check.

Rule for the profiler sink: leaf phases only. A tool that names an idle
gap by the host event covering most of it cannot tell a wrapper from the
child that fills it, so a scope that encloses other scopes passes
``annotate=False`` and goes to the ring alone. Names start with ``ds.``
and are literal strings; a shape, uid or step number goes in ``args``.

Storage is bounded so a long-lived daemon cannot grow:

- at most ``max_requests`` live timelines (oldest evicted first),
- at most ``max_spans_per_request`` spans per timeline (a deque ring —
  a pathological million-token request keeps its most recent spans),
- a global ``max_waves`` ring of wave/global/scope spans for the bulk
  ``GET /debug/trace`` Chrome export,
- a small ring of its own for set-up spans (``ds.init*``, ``ds.compile.*``,
  ``ds.import``, ``ds.checkpoint.engine_build``: once per engine, program
  or process), which the window's traffic cannot evict.

A phase that nobody knows to be worth a span until it is over (a call that
turned out to compile, the pieces jax reports of it afterwards) is recorded
after the fact: :meth:`RequestTracer.closed_scope` takes the closed
interval, and a scope that is opened late is told when it
:meth:`~_Scope.began`.

Export formats:

- :meth:`RequestTracer.timeline` — the per-uid JSON served by
  ``GET /requests/<uid>/trace``: ordered spans with ``t0``/``t1``
  relative to submit, plus the raw monotonic anchors.
- :meth:`RequestTracer.chrome_trace` — Chrome ``trace_event`` JSON
  (``ph: "X"`` complete events, microsecond timestamps) loadable in
  Perfetto / chrome://tracing, one ``tid`` lane per request.
"""

import itertools
import threading
import time
from collections import OrderedDict, deque
from contextlib import nullcontext
from typing import List, Optional

from jax.profiler import TraceAnnotation

# scopes recorded once per engine or per compiled program: kept in a ring
# of their own so that steady traffic cannot evict them
KEPT_PREFIXES = ("ds.init", "ds.compile.", "ds.import", "ds.checkpoint.")


class _Timeline:
    __slots__ = ("uid", "t_submit", "spans", "events", "done")

    def __init__(self, uid: str, t_submit: float, max_spans: int):
        self.uid = uid
        self.t_submit = t_submit
        self.spans = deque(maxlen=max_spans)
        self.events = deque(maxlen=max_spans)
        self.done = False


class _Scope:
    """One open :meth:`RequestTracer.scope`. ``args`` may be filled in
    while the scope is open (a count known only after the work); ``dur_s``
    is the recorded duration once it has closed; ``sid`` is what a span
    recorded after the fact names as its ``parent``."""

    __slots__ = ("_tracer", "name", "uid", "args", "_annotation", "_t0",
                 "_sid", "_parent", "_stack", "dur_s")

    def __init__(self, tracer, name, uid, annotate, args):
        self._tracer = tracer
        self.name = name
        self.uid = uid
        self.args = args
        self._annotation = TraceAnnotation(name) if annotate else None

    def __enter__(self):
        tracer = self._tracer
        stack = self._stack = tracer._open_scopes()
        self._parent = stack[-1] if stack else None
        self._sid = next(tracer._sids)
        stack.append(self._sid)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    @property
    def sid(self) -> int:
        return self._sid

    def began(self, t0: float) -> None:
        """The phase began at ``t0`` (``time.monotonic()``), before anyone
        knew it was worth a span: the open scope is recorded from there."""
        self._t0 = t0

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._stack.pop()
        self.dur_s = t1 - self._t0
        self._tracer._record_scope(
            (self.name, self._t0, t1, self._parent,
             None if self.uid is None else str(self.uid),
             self.args or None, self._sid))
        return False


class RequestTracer:
    """Bounded recorder of request lifecycles, daemon spans and the
    program's own phases (:meth:`scope`)."""

    def __init__(self, max_requests: int = 512,
                 max_spans_per_request: int = 512,
                 max_waves: int = 2048, max_kept: int = 512):
        self._lock = threading.Lock()
        self._max_requests = int(max_requests)
        self._max_spans = int(max_spans_per_request)
        self._timelines: "OrderedDict[str, _Timeline]" = OrderedDict()
        # ring records: (name, t0, t1, parent sid, uid, args, sid)
        self._waves = deque(maxlen=int(max_waves))
        self._kept = deque(maxlen=int(max_kept))
        self._sids = itertools.count(1)
        self._local = threading.local()

    # ---- the span API (hot path: two clock reads, one lock, one append) ----

    def scope(self, name: str, uid=None, *, annotate: bool = True, **args):
        """Context manager timing one phase of host work into both sinks
        (module docstring). ``uid`` ties the span to one request: it is
        then also on that request's timeline, so pass it only for phases
        that happen a few times per request. ``annotate=False`` keeps a
        scope that encloses other scopes off the profiler's sink."""
        return _Scope(self, name, uid, annotate, args)

    def _open_scopes(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record_scope(self, rec: tuple) -> None:
        name, t0, t1, _, uid, args, _ = rec
        with self._lock:
            (self._kept if name.startswith(KEPT_PREFIXES)
             else self._waves).append(rec)
            if uid is not None:
                tl = self._timelines.get(uid)
                if tl is not None:
                    tl.spans.append((name, t0, t1, args))

    def closed_scope(self, name: str, t0: float, t1: float,
                     parent: Optional[int] = None,
                     args: Optional[dict] = None) -> int:
        """Record the closed interval ``[t0, t1]`` (``time.monotonic()``) as
        a scope after the fact, under the scope whose ``sid`` is ``parent``;
        ring only, since the profiler takes no event that is already over.
        Returns its ``sid``."""
        sid = next(self._sids)
        self._record_scope((name, t0, t1, parent, None, args or None, sid))
        return sid

    def scopes(self, prefix: str = "ds.", since: float = 0.0) -> List[dict]:
        """The recorded scopes whose name starts with ``prefix`` and that
        ended at or after ``since`` (monotonic), oldest first, each with
        its ``self_s``: its duration less that of the recorded scopes it
        directly encloses."""
        with self._lock:
            recs = [r for ring in (self._kept, self._waves) for r in ring
                    if r[6] is not None]
        child_s = {}
        for _, t0, t1, parent, _, _, _ in recs:
            if parent is not None:
                child_s[parent] = child_s.get(parent, 0.0) + (t1 - t0)
        out = []
        for name, t0, t1, parent, uid, args, sid in recs:
            if not name.startswith(prefix) or t1 < since:
                continue
            out.append({"name": name, "t0_monotonic": t0, "t1_monotonic": t1,
                        "dur_s": t1 - t0,
                        "self_s": (t1 - t0) - child_s.get(sid, 0.0),
                        "sid": sid, "parent": parent, "uid": uid,
                        "args": dict(args) if args else {}})
        out.sort(key=lambda d: d["t0_monotonic"])
        return out

    # ---- recording (hot path: one lock, one deque append) ----

    def begin(self, uid: str, t_submit: Optional[float] = None) -> None:
        """Open a timeline at submit time. Idempotent per uid (a replayed
        request re-begins and keeps its original timeline)."""
        t = time.monotonic() if t_submit is None else t_submit
        with self._lock:
            tl = self._timelines.get(uid)
            if tl is not None:
                self._timelines.move_to_end(uid)
                return
            tl = _Timeline(uid, t, self._max_spans)
            self._timelines[uid] = tl
            while len(self._timelines) > self._max_requests:
                self._timelines.popitem(last=False)

    def span(self, uid: str, name: str, t0: float, t1: float,
             args: Optional[dict] = None) -> None:
        """Record a closed [t0, t1] interval for a request."""
        with self._lock:
            tl = self._timelines.get(uid)
            if tl is None:
                return
            tl.spans.append((name, t0, t1, args))

    def event(self, uid: str, name: str, t: Optional[float] = None,
              args: Optional[dict] = None) -> None:
        """Record an instant (shed, expiry, quarantine, resume...)."""
        t = time.monotonic() if t is None else t
        with self._lock:
            tl = self._timelines.get(uid)
            if tl is None:
                return
            tl.events.append((name, t, args))

    def finish(self, uid: str, name: str = "finish",
               t: Optional[float] = None,
               args: Optional[dict] = None) -> None:
        t = time.monotonic() if t is None else t
        with self._lock:
            tl = self._timelines.get(uid)
            if tl is None:
                return
            tl.events.append((name, t, args))
            tl.done = True

    def global_span(self, name: str, t0: float, t1: float,
                    args: Optional[dict] = None,
                    uids: Optional[List[str]] = None) -> None:
        """Record a daemon-level interval (a fused wave, a restart) into
        the global ring, optionally mirrored onto member timelines."""
        with self._lock:
            self._waves.append((name, t0, t1, None, None, args, None))
            if uids:
                for uid in uids:
                    tl = self._timelines.get(uid)
                    if tl is not None:
                        tl.spans.append((name, t0, t1, args))

    # ---- export (cold path) ----

    def has(self, uid: str) -> bool:
        with self._lock:
            return uid in self._timelines

    def timeline(self, uid: str) -> Optional[dict]:
        """Per-request JSON timeline: spans sorted by start, times both
        absolute (monotonic) and relative to submit."""
        with self._lock:
            tl = self._timelines.get(uid)
            if tl is None:
                return None
            spans = list(tl.spans)
            events = list(tl.events)
            t_submit, done = tl.t_submit, tl.done
        spans.sort(key=lambda s: s[1])
        out_spans = []
        for name, t0, t1, args in spans:
            d = {"name": name, "t0": t0 - t_submit, "t1": t1 - t_submit,
                 "dur_s": t1 - t0, "t0_monotonic": t0, "t1_monotonic": t1}
            if args:
                d["args"] = dict(args)
            out_spans.append(d)
        out_events = []
        for name, t, args in sorted(events, key=lambda e: e[1]):
            d = {"name": name, "t": t - t_submit, "t_monotonic": t}
            if args:
                d["args"] = dict(args)
            out_events.append(d)
        return {"uid": uid, "t_submit_monotonic": t_submit, "done": done,
                "spans": out_spans, "events": out_events}

    def chrome_trace(self, last: Optional[int] = None) -> dict:
        """Chrome ``trace_event`` JSON of recent global spans plus every
        live timeline, one ``tid`` lane per request (pid 1 = daemon)."""
        with self._lock:
            waves = list(self._waves)
            kept = list(self._kept)
            tls = [(tl.uid, tl.t_submit, list(tl.spans), list(tl.events))
                   for tl in self._timelines.values()]
        if last is not None and last >= 0:
            waves = waves[-last:]
        events = []
        for name, t0, t1, _, _, args, _ in kept + waves:
            ev = {"name": name, "ph": "X", "pid": 1, "tid": 0,
                  "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6}
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        for tid, (uid, t_submit, spans, instants) in enumerate(tls, start=1):
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid, "args": {"name": f"req {uid}"}})
            for name, t0, t1, args in spans:
                ev = {"name": name, "ph": "X", "pid": 1, "tid": tid,
                      "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6}
                if args:
                    ev["args"] = dict(args)
                events.append(ev)
            for name, t, args in instants:
                ev = {"name": name, "ph": "i", "pid": 1, "tid": tid,
                      "ts": t * 1e6, "s": "t"}
                if args:
                    ev["args"] = dict(args)
                events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def reset(self) -> None:
        with self._lock:
            self._timelines.clear()
            self._waves.clear()
            self._kept.clear()


class _NoTracer:
    """What an engine or scheduler holds in a tracer's place with its
    observability block off: ``scope`` times nothing."""

    _nothing = nullcontext()

    def scope(self, name: str, uid=None, *, annotate: bool = True, **args):
        return self._nothing


NO_TRACER = _NoTracer()
_TRACER = RequestTracer()


def get_tracer() -> RequestTracer:
    """The process-wide tracer (serving injects its own sized instance)."""
    return _TRACER
