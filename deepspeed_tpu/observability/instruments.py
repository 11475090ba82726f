"""Pre-resolved metric/tracer handles for the serving hot path.

The scheduler tick must not pay a registry dict lookup per event, so
every metric it records is resolved ONCE here at construction; the call
sites then touch plain attributes. The scheduler holds one
:class:`ServingInstruments` (or None with the ``observability`` config
block disabled) and every recording site is guarded by a single
``if self._obs is not None``.

A custom ``registry``/``tracer`` is injectable for test isolation; the
defaults are the process-wide singletons so the HTTP ``GET /metrics``
scrape, the engine/journal/supervisor instrumentation, and the
``monitor/`` bridge all see one namespace.
"""

import time
from typing import Iterable, Optional

from .metrics import MetricsRegistry, get_registry
from .tracing import RequestTracer
from .profiler import ProfilerCapture

# Latency histograms share one shape: 1µs..1000s at 10 buckets/decade
# (91 buckets) — wide enough for a journal fsync and a 10-minute decode.
_HIST = dict(lo=1e-6, hi=1e3, buckets_per_decade=10)


class ServingInstruments:
    """Handle bundle + recording helpers for ``ServingScheduler``."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[RequestTracer] = None,
                 trace_requests: int = 512,
                 trace_spans_per_request: int = 512,
                 trace_waves: int = 2048,
                 profile_dir: Optional[str] = None,
                 profile_max_seconds: float = 60.0):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        self.tracer = tracer if tracer is not None else RequestTracer(
            max_requests=trace_requests,
            max_spans_per_request=trace_spans_per_request,
            max_waves=trace_waves)
        self.profiler = ProfilerCapture(profile_dir,
                                        max_seconds=profile_max_seconds)
        h, c, g = reg.histogram, reg.counter, reg.gauge
        self.ttft = h("ds_ttft_seconds",
                      "Submit to first emitted token (replays excluded)",
                      **_HIST)
        self.inter_token = h("ds_inter_token_seconds",
                             "Gap between consecutive emitted tokens of one "
                             "request", **_HIST)
        self.e2e = h("ds_request_e2e_seconds",
                     "Submit to finish for successful requests", **_HIST)
        self.queue_wait = h("ds_queue_wait_seconds",
                            "Submit to admission into the live set", **_HIST)
        self.tick = h("ds_serving_tick_seconds",
                      "One scheduler tick (admit + advance)", **_HIST)
        self.wave = h("ds_fused_wave_seconds",
                      "Fused K-step wave, dispatch to harvest", **_HIST)
        self.prefill = h("ds_prefill_chunk_seconds",
                         "One SplitFuse prefill chunk put", **_HIST)
        self.submitted = c("ds_requests_submitted_total",
                           "Requests accepted by submit()")
        self.finished = c("ds_requests_finished_total",
                          "Requests finished successfully")
        self.errored = c("ds_requests_errored_total",
                         "Requests finished with an error")
        self.cancelled = c("ds_requests_cancelled_total",
                           "Requests cancelled by the client")
        self.shed = c("ds_requests_shed_total",
                      "Requests refused at submit() by the shed policy")
        self.expired = c("ds_requests_expired_total",
                         "Requests expired on a deadline/TTL")
        self.quarantined = c("ds_requests_quarantined_total",
                             "Requests isolated by the tick-fault bisect")
        self.replayed = c("ds_requests_replayed_total",
                          "Requests re-admitted from the journal")
        self.tokens = c("ds_tokens_emitted_total",
                        "Tokens surfaced to consumers")
        self.fused_tokens = c("ds_fused_tokens_total",
                              "Decode tokens produced by fused dispatches")
        self.decode_tokens = c("ds_decode_tokens_total",
                               "All decode tokens produced")
        self.prefill_overlap = c(
            "ds_prefill_overlap_tokens_total",
            "Prefill tokens fed while a fused wave ran on device")
        self.fused_dispatches = c("ds_fused_dispatches_total",
                                  "Fused K-step dispatches issued")
        self.spec_drafted = c("ds_spec_drafted_total",
                              "Speculative tokens offered for verification")
        self.spec_accepted = c("ds_spec_accepted_total",
                               "Speculative tokens accepted")
        self.watchdog_trips = c("ds_watchdog_trips_total",
                                "Watchdog transitions into degraded")
        self.queue_depth = g("ds_queue_depth",
                             "Unadmitted requests (inbox + waiting)")
        self.live_requests = g("ds_live_requests",
                               "Requests in the live decode set")
        self.kv_free_blocks = g("ds_kv_free_blocks",
                                "Free KV cache blocks")
        self.adaptive_k = g("ds_adaptive_k",
                            "Fused window K chosen by the last adaptive "
                            "computation")
        self.fused_occupancy = g(
            "ds_fused_occupancy",
            "Fraction of decode tokens produced by fused dispatches")
        self.wave_mfu = g(
            "ds_serving_wave_mfu",
            "Model FLOPs utilization of the last fused decode wave "
            "(cost-analysis FLOPs / wall / peak_bf16_flops)")
        from .xla import peak_device_flops
        self.peak_flops = peak_device_flops()
        # per-tenant handle bundles, created lazily on first sight of a
        # tenant name — the hot path still touches plain attributes after
        # one dict hit, and an untenanted deployment allocates nothing
        self._tenants: dict = {}
        # per-adapter handle bundles (multi-LoRA serving), same lazy scheme
        self._adapters: dict = {}

    def _adapter(self, name: str):
        """Labeled series for one adapter id (``name@version``)."""
        a = self._adapters.get(name)
        if a is None:
            lbl = {"adapter": name}
            reg = self.registry
            from types import SimpleNamespace
            a = SimpleNamespace(
                tokens=reg.counter(
                    "ds_adapter_tokens_total",
                    "Tokens emitted by requests decoding with one adapter",
                    labels=lbl),
                finished=reg.counter(
                    "ds_adapter_requests_finished_total",
                    "Requests finished successfully per adapter",
                    labels=lbl))
            self._adapters[name] = a
        return a

    def adapter_token(self, adapter: str) -> None:
        self._adapter(adapter).tokens.inc()

    def _tenant(self, name: str):
        """Labeled series for one tenant, sharing the family names of the
        unlabeled aggregates (``ds_tokens_emitted_total{tenant="a"}`` sits
        next to plain ``ds_tokens_emitted_total``)."""
        t = self._tenants.get(name)
        if t is None:
            lbl = {"tenant": name}
            reg = self.registry
            from types import SimpleNamespace
            t = SimpleNamespace(
                tokens=reg.counter(
                    "ds_tokens_emitted_total",
                    "Tokens surfaced to consumers", labels=lbl),
                finished=reg.counter(
                    "ds_requests_finished_total",
                    "Requests finished successfully", labels=lbl),
                ttft=reg.histogram(
                    "ds_ttft_seconds",
                    "Submit to first emitted token (replays excluded)",
                    labels=lbl, **_HIST),
                e2e=reg.histogram(
                    "ds_request_e2e_seconds",
                    "Submit to finish for successful requests",
                    labels=lbl, **_HIST),
                queue_depth=reg.gauge(
                    "ds_tenant_queue_depth",
                    "Unadmitted requests of one tenant", labels=lbl))
            self._tenants[name] = t
        return t

    # ---- recording helpers (each: a few attribute ops + one deque/lock) ----

    def request_submitted(self, uid, t_submit: float) -> None:
        self.submitted.inc()
        self.tracer.begin(str(uid), t_submit)

    def request_replayed(self, uid, t_submit: float, n_outputs: int) -> None:
        self.replayed.inc()
        self.tracer.begin(str(uid), t_submit)
        self.tracer.event(str(uid), "replay", t_submit,
                          {"journaled_tokens": n_outputs})

    def request_admitted(self, uid, t_submit: float,
                         t_now: Optional[float] = None) -> None:
        t = time.monotonic() if t_now is None else t_now
        self.queue_wait.record(t - t_submit)
        self.tracer.span(str(uid), "queue", t_submit, t)

    def first_token(self, req_t_submit: float, t: float,
                    replayed: bool, tenant: Optional[str] = None) -> None:
        # a replayed request's TTFT spans the crash+restart — real for the
        # client but not a scheduler-latency signal, so it stays out
        if not replayed:
            self.ttft.record(t - req_t_submit)
            if tenant is not None:
                self._tenant(tenant).ttft.record(t - req_t_submit)

    def token_gap(self, dt: float) -> None:
        self.inter_token.record(dt)

    def tenant_token(self, tenant: str) -> None:
        self._tenant(tenant).tokens.inc()

    def tenant_queue_depth(self, tenant: str, depth: int) -> None:
        self._tenant(tenant).queue_depth.set(depth)

    def wave_span(self, uids: Iterable, t0: float, t1: float, K: int,
                  size: int, kind: str, drafted: int = 0,
                  accepted: int = 0, flops: float = 0.0,
                  ctx_tokens: int = 0) -> None:
        """``size`` (also ``rows``) is the wave's live rows and
        ``ctx_tokens`` the sum of their context lengths at dispatch: what
        the paged attention calls of the wave read."""
        self.wave.record(t1 - t0)
        if flops > 0 and t1 > t0:
            self.wave_mfu.set(min(1.0, flops / ((t1 - t0) * self.peak_flops)))
        args = {"K": K, "size": size, "kind": kind, "rows": size,
                "ctx_tokens": ctx_tokens}
        if drafted:
            args["drafted"], args["accepted"] = drafted, accepted
        self.tracer.global_span(f"fused_wave[{kind}]", t0, t1, args,
                                uids=[str(u) for u in uids])

    def prefill_span(self, uids: Iterable, t0: float, t1: float,
                     tokens: int, overlap: bool = False,
                     ctx_tokens: int = 0) -> None:
        """One span per tick in the global ring, copied onto each request
        it fed. ``rows`` is the prefilling rows and ``ctx_tokens`` the sum
        of their context lengths (tokens already cached) at dispatch."""
        self.prefill.record(t1 - t0)
        name = "prefill_overlap" if overlap else "prefill"
        uids = [str(u) for u in uids]
        args = {"tokens": tokens, "rows": len(uids),
                "ctx_tokens": ctx_tokens}
        self.tracer.global_span(name, t0, t1, args, uids=uids)

    def request_finished(self, uid, t_submit: float, t_done: float,
                         outcome: str, n_tokens: int,
                         replayed: bool, tenant: Optional[str] = None,
                         adapter: Optional[str] = None) -> None:
        if outcome == "ok":
            self.finished.inc()
            if tenant is not None:
                self._tenant(tenant).finished.inc()
            if adapter is not None:
                self._adapter(adapter).finished.inc()
            if not replayed:
                self.e2e.record(t_done - t_submit)
                if tenant is not None:
                    self._tenant(tenant).e2e.record(t_done - t_submit)
        elif outcome == "cancelled":
            self.cancelled.inc()
        elif outcome == "expired":
            self.expired.inc()
        else:
            self.errored.inc()
        self.tracer.finish(str(uid), "finish", t_done,
                           {"outcome": outcome, "tokens": n_tokens})

    def refresh(self, queue_depth: int, live: int, free_blocks: int,
                fused_tokens: int, decode_tokens: int) -> None:
        self.queue_depth.set(queue_depth)
        self.live_requests.set(live)
        self.kv_free_blocks.set(free_blocks)
        if decode_tokens:
            self.fused_occupancy.set(fused_tokens / decode_tokens)
