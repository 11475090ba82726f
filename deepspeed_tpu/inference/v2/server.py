"""Serving front end over ``InferenceEngineV2`` — the MII surface.

The reference ships FastGen behind DeepSpeed-MII (``mii.serve`` spawns a
persistent server whose scheduler drives ``engine_v2.put()`` continuously;
reference ``inference/v2/engine_v2.py:107`` is the documented integration
point for exactly this loop). This module is that missing deployment layer,
TPU-native and stdlib-only:

- :class:`ServingScheduler` — a background thread running TRUE continuous
  batching with Dynamic SplitFuse scheduling (the FastGen algorithm):
  requests arrive and retire asynchronously; every tick is one ragged
  forward of at most ``token_budget`` tokens where decoding sequences are
  guaranteed their token first and prefills chunk into the remainder
  (a drafted tick adds a separate windowed put — speculative decoding
  rides the same loop, and in steady state eligible speculative rows run
  their draft/verify/accept entirely on device inside the fused K-window
  scan). Per-request sampling controls, logprobs, token streaming. Admission reserves full decode headroom (prompt +
  max_new_tokens blocks) exactly like ``InferenceEngineV2.generate`` so
  a tick cannot run the allocator dry; if it still does (best-effort
  admission), the newest sequence is evicted and replayed.
- :class:`RequestHandle` — caller's side of one request: ``stream()``
  yields token ids as they land, ``result()`` /
  ``result_with_logprobs()`` block for the full output, ``cancel()``
  retires the sequence at the next scheduler tick.
- :func:`create_http_server` / ``bin/ds_serve`` — a ThreadingHTTPServer
  exposing ``POST /generate`` (optionally chunk-streamed), the OpenAI
  ``/v1/completions`` and ``/v1/chat/completions`` shapes, and
  ``GET /health`` (queue depths + TTFT/decode-rate aggregates).
  Token-id native; pass a HF tokenizer name to accept ``{"text": ...}``
  bodies, string stops, and chat messages.

Single-threaded device access: ONLY the scheduler thread touches the
engine. ``submit``/``cancel`` just enqueue under a lock and set an event,
so arbitrarily many HTTP threads are safe.

Resilience (``serving_resilience`` config block, see config_v2.py):
per-request deadlines/TTL expire with a typed :class:`DeadlineExceeded`
(HTTP 504) and release their KV; bounded queues shed at ``submit()``
with :class:`SchedulerOverloaded` (HTTP 429 + Retry-After); a per-tick
fault boundary retries transient engine errors and bisects a
reproducible fault down to the one poisoning request (error-finishing
only it — the loop survives); a watchdog flips ``/health`` to
``degraded`` when ticks stall. All deterministic-testable through the
``serve.*`` sites of ``utils/fault_injection.py``.

Durability (``durable_serving`` config block + ``inference/v2/journal.py``):
with the write-ahead request journal enabled, every admitted request is
persisted (prompt, sampling params, seed, deadline) and its emitted-token
high-water mark + PRNG key-burn count follow per tick. A daemon crash (or
SIGTERM ``handoff()``) therefore loses nothing: the next ``start()`` scans
the journal, re-admits unfinished requests with their original uids and
remaining deadlines, force-feeds the already-emitted tokens as prefix, and
fast-forwards each key chain by its burn count — resumed greedy AND sampled
streams continue byte-identically to an uninterrupted run. Clients
re-attach by request id: ``GET /requests/<uid>`` blocks for the result,
``GET /requests/<uid>/stream?from_token=N`` resumes a token stream at the
client's own high-water mark (offset-addressed, so nothing double-emits).
"""

import itertools
import json
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from ...observability import ProfilerBusy, ServingInstruments
from ...observability.tracing import NO_TRACER
from ...utils.fault_injection import InjectedFault, get_fault_injector
from ...utils.logging import logger
from ...utils.retry import RetriesExhausted, retry_with_backoff
from .config_v2 import (ContinuousFusionConfig, DurableServingConfig,
                        ObservabilityConfig, ServingResilienceConfig,
                        TenantConfig)
from .adapters import AdapterSlotsExhausted
from .disagg import DisaggServing
from .journal import RequestJournal, ServingCrash
from .engine_v2 import InferenceEngineV2, SampleSpec
from .ragged.sequence_descriptor import PlaceholderSequenceDescriptor
from .scheduling_utils import (DeadlineExceeded, SchedulerOverloaded,
                               SchedulingError, SchedulingResult,
                               UnsupportedFeature, error_reason)

_END = object()  # stream sentinel


@dataclass
class _Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: int = 0
    stop: list = field(default_factory=list)  # normalized token-id seqs
    min_new_tokens: int = 0
    repetition_penalty: float = 1.0
    logits_processor: Optional[object] = None
    speculative: Optional[str] = None
    num_draft_tokens: int = 4
    draft_ngram: int = 2
    return_logprobs: bool = False
    # multi-tenant scheduling: which tenant contract (config ``tenants``
    # block) this request admits/sheds/budgets under
    tenant: str = "default"
    # multi-LoRA: the client-facing adapter name (None = base weights) and
    # the RESOLVED versioned id (``name@version``) the stream decodes with —
    # the journal records the resolved id so replay/migration re-pin the
    # exact factors, never "whatever version is latest over there"
    adapter: Optional[str] = None
    adapter_id: Optional[str] = None
    logprobs: list = field(default_factory=list)
    # speculative accept-rate accounting (drafted tokens offered / accepted)
    drafted: int = 0
    accepted: int = 0
    # host prompt-lookup fallback: cached last-match position so the
    # bounded backward scan usually starts where it last succeeded
    match_cache: dict = field(default_factory=dict)
    # scheduler state
    outputs: List[int] = field(default_factory=list)
    fed: int = 0                   # tokens of prompt+outputs already in KV
    stream_q: "queue.Queue" = field(default_factory=queue.Queue)
    done: "threading.Event" = field(default_factory=threading.Event)
    cancelled: bool = False
    error: Optional[BaseException] = None
    rng: Optional[np.random.Generator] = None
    # durability state: counted device-PRNG key burns (one per sampled
    # per-token dispatch / fused scan step / verified speculative window),
    # the journal high-water marks, and the replay/skip flags
    key_burns: int = 0
    journaled_n: int = 0       # outputs already on journal record
    journaled_burns: int = 0   # key_burns already on journal record
    journal_skip: bool = False  # host logits_processor: not serializable
    replayed: bool = False
    stream: bool = False       # submitted as a stream() consumer
    # resilience state
    t_deadline: Optional[float] = None        # monotonic; queue + decode
    t_queue_deadline: Optional[float] = None  # monotonic; unadmitted only
    wake: Optional[threading.Event] = None    # cancel() nudges the loop
    queued: bool = False  # counted in the shed-policy accounting
    # metrics timeline (time.monotonic)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0   # last emitted token (inter-token gap anchor)
    t_done: float = 0.0

    @property
    def feed(self) -> List[int]:
        """Everything that must be in the KV cache: prompt, plus generated
        tokens (relevant after an eviction replay resets ``fed``)."""
        return self.prompt + self.outputs

    @property
    def pending(self) -> int:
        """Tokens of ``feed`` not yet in the KV cache. 1 ⇔ a pure decode
        step (the last sampled token); >1 ⇔ (re)prefilling."""
        return len(self.prompt) + len(self.outputs) - self.fed

    def feed_slice(self, take: int) -> List[int]:
        """Next ``take`` unfed tokens, without concatenating the history."""
        start, lp = self.fed, len(self.prompt)
        if start >= lp:
            return self.outputs[start - lp:start - lp + take]
        head = self.prompt[start:start + take]
        if len(head) < take:
            head = head + self.outputs[:take - len(head)]
        return head


class RequestHandle:
    """Caller's view of one in-flight generation."""

    def __init__(self, req: _Request):
        self._req = req

    @property
    def uid(self) -> int:
        return self._req.uid

    def stream(self, timeout: Optional[float] = None):
        """Yield token ids as the scheduler produces them."""
        while True:
            tok = self._req.stream_q.get(timeout=timeout)
            if tok is _END:
                if self._req.error is not None:
                    raise self._req.error
                return
            yield tok

    def stream_from(self, from_token: int = 0,
                    timeout: Optional[float] = None, poll: float = 0.02):
        """Offset-addressed stream for (re)connecting consumers: yields
        ``outputs[from_token:]`` — already-generated tokens immediately,
        then live ones as they land. Unlike ``stream()`` it never touches
        the delivery queue, so any number of consumers can attach at their
        own high-water marks (e.g. after an HTTP reconnect or a daemon
        warm restart) without double-emitting or stealing tokens."""
        deadline = (time.monotonic() + timeout) if timeout is not None \
            else None
        i = max(0, int(from_token))
        while True:
            n = len(self._req.outputs)  # append-only: snapshot is safe
            while i < n:
                yield int(self._req.outputs[i])
                i += 1
            if self._req.done.is_set():
                if len(self._req.outputs) > i:
                    continue  # tokens landed between the scan and done
                if self._req.error is not None:
                    raise self._req.error
                return
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"request {self._req.uid} still running")
            self._req.done.wait(poll)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until generation finishes; returns all generated tokens."""
        if not self._req.done.wait(timeout):
            raise TimeoutError(f"request {self._req.uid} still running")
        if self._req.error is not None:
            raise self._req.error
        return list(self._req.outputs)

    def result_with_logprobs(self, timeout: Optional[float] = None):
        """(tokens, per-token logprobs) — requires submit(...,
        return_logprobs=True)."""
        toks = self.result(timeout)
        return toks, list(self._req.logprobs[:len(toks)])

    @property
    def stats(self) -> dict:
        """Per-request accounting. For speculative requests this carries
        the accept-rate counters (``drafted`` tokens offered for
        verification, ``accepted`` of them kept), available live and after
        ``result()``."""
        r = self._req
        out = {"tokens": len(r.outputs)}
        if r.speculative is not None:
            out["drafted"] = r.drafted
            out["accepted"] = r.accepted
            out["accept_rate"] = (round(r.accepted / r.drafted, 4)
                                  if r.drafted else None)
        return out

    def cancel(self) -> None:
        self._req.cancelled = True
        if self._req.wake is not None:
            # wake an idle loop NOW: the sweep frees this request's KV
            # before the next admission pass instead of after idle_wait
            self._req.wake.set()

    @property
    def finished(self) -> bool:
        return self._req.done.is_set()


class ServingScheduler:
    """Continuous-batching serving loop over one ``InferenceEngineV2``.

    Scheduling is Dynamic SplitFuse (the reference's FastGen algorithm,
    ``blogs/deepspeed-fastgen``): every tick runs ONE ragged forward of at
    most ``token_budget`` tokens — each decoding sequence is guaranteed its
    1 token first (the decode-latency SLA), then prefilling sequences fill
    the remainder in chunks. Long prompts therefore spread across ticks
    instead of stalling live decodes behind one huge forward, and short
    prompts pack into the same forward as the decodes.
    """

    def __init__(self, engine: InferenceEngineV2, idle_wait: float = 0.05,
                 token_budget: Optional[int] = None,
                 fused_decode_window: Optional[int] = None,
                 journal: Optional[RequestJournal] = None,
                 instruments: "Union[ServingInstruments, bool, None]" = None,
                 disagg: Optional[DisaggServing] = None,
                 uid_base: Optional[int] = None):
        self._engine = engine
        self._idle_wait = idle_wait
        # disaggregated prefill/decode (disagg.py): ``engine`` is the
        # DECODE group's; pending>1 requests route to the prefill group
        # and their KV pages migrate back through the handoff queue.
        # None (the default / single-group fallback) leaves every code
        # path byte-identical to the time-overlap scheduler.
        self._disagg = disagg
        self._on_prefill: set = set()  # uids resident on the prefill group
        self._disagg_fed_tick = False  # one prefill-group put per tick
        if fused_decode_window is None:
            from ...ops.registry import on_tpu
            fused_decode_window = 16 if on_tpu() else 1
        # steady-state fast path: when nothing waits to prefill, the
        # plain-greedy subset of live decodes runs K fused steps per
        # dispatch (engine.fused_decode_steps — the CUDA-graph-replay
        # analog) while sampled/controlled requests keep their per-token
        # SplitFuse tick in the same scheduler pass
        self._fused_window = int(fused_decode_window)
        scfg = getattr(engine._config, "sampling", None)
        # on-device sampling: eligible requests (no host logits_processor)
        # sample in one batched device dispatch per tick, and — with
        # fused_sampled_decode — ride the fused K-step program next to the
        # greedy ones, so the fused partition is by FEASIBILITY
        # (prefilled, pending==1, >= 2 tokens of room), not by greediness
        self._device_sampling = bool(scfg and scfg.device_sampling)
        self._fused_sampled = bool(self._device_sampling
                                   and scfg.fused_sampled_decode)
        # fused speculative: eligible speculative rows (no host callbacks,
        # device-matchable ngram) run draft+verify+accept inside the K-step
        # scan — one dispatch + one fetch per window instead of one host
        # round-trip per token. Gate-off keeps the per-token host path (the
        # parity oracle) for everything.
        self._fused_spec = bool(scfg and scfg.fused_speculative_decode)
        self._spec_max_ngram = int(scfg.spec_max_ngram) if scfg else 8
        # continuous fusion: dispatch the fused wave (async), feed prefill
        # chunks + admit arrivals WHILE it runs on device, harvest after —
        # the K-step amortization survives sustained traffic instead of
        # being an idle-system-only mode. Gate-off restores the exclusive
        # modes exactly.
        ccfg = getattr(engine._config, "continuous_fusion", None)
        self._cf: ContinuousFusionConfig = (
            ccfg if ccfg is not None else ContinuousFusionConfig())
        # uids of wave members whose fused program is in flight: the
        # eviction and retirement paths must not flush them (the device is
        # still writing their KV); empty outside the overlap window
        self._in_flight: frozenset = frozenset()
        # EWMA of measured seconds per fused decode step — the adaptive-K
        # deadline bound's cost model (0 until the first wave completes)
        self._step_ewma = 0.0
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._inbox: List[_Request] = []
        self._waiting: List[_Request] = []
        self._live: List[_Request] = []
        # fleet uid namespacing: the router exports DS_SERVE_UID_BASE so
        # every replica generation mints uids from a disjoint stride —
        # migrated requests keep their original uids on any peer without
        # ever colliding with the peer's own mints
        self._uid_base = uid_base if uid_base is not None else int(
            os.environ.get("DS_SERVE_UID_BASE", "0") or 0)
        self._uid_iter = itertools.count(self._uid_base + 1)
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._draining = False
        # submit()..._finish() span, maintained under _lock: queue-membership
        # checks can race the loop's unlocked transfers, this count cannot
        self._active = 0
        rcfg = getattr(engine._config, "serving_resilience", None)
        self._res: ServingResilienceConfig = (
            rcfg if rcfg is not None else ServingResilienceConfig())
        # shed-policy accounting: unadmitted requests / their prompt tokens,
        # maintained under _lock so submit() can refuse without touching the
        # scheduler thread's queues
        self._queued_n = 0
        self._queued_tokens = 0
        # multi-tenant weighted-fair scheduling: per-tenant contracts from
        # the config ``tenants`` block (unknown tenants fall back to the
        # "default" entry, else weight-1/no-caps), plus the per-tenant
        # accounting admission and shedding run on. _tenant_queued mutates
        # under _lock with the global queue counters; _tenant_delivered is
        # scheduler-thread-only (stats snapshots it under _lock).
        self._tenants = dict(getattr(engine._config, "tenants", None) or {})
        self._tenant_fallback = self._tenants.get("default") or TenantConfig()
        self._tenant_queued: dict = {}
        self._tenant_delivered: dict = {}
        self._degraded = False
        # live-migration state: export_journal() flips _migrating so
        # /health answers "migrating" (distinct from a plain drain — the
        # router and ds_top can tell a handoff-in-progress from a
        # shutdown) and records how many entries left in the export
        self._migrating = False
        self._journal_export_depth = 0
        self._imported = 0
        self._last_progress = time.monotonic()
        self._watchdog: Optional[threading.Thread] = None
        # resilience event counters (mutations: scheduler thread, except
        # "shed" which submit() bumps under _lock; stats/trace snapshot
        # under the same lock)
        self._trace = {"shed": 0, "expired_queue": 0, "expired_live": 0,
                       "tick_errors": 0, "quarantined": [],
                       "watchdog_trips": 0, "slow_consumer_cancels": 0,
                       "spec_drafted": 0, "spec_accepted": 0,
                       # continuous-fusion observability: decode tokens
                       # from fused dispatches vs all decode tokens (the
                       # occupancy ratio), dispatch/window-size tallies,
                       # and prefill tokens fed inside overlap windows
                       "fused_tokens": 0, "decode_tokens": 0,
                       "fused_dispatches": 0, "fused_k_sum": 0,
                       "prefill_overlap_tokens": 0}
        # durability: the write-ahead request journal (explicit instance
        # wins; else built from the durable_serving config block), plus the
        # uid registry the reconnect surface resolves against
        dcfg = getattr(engine._config, "durable_serving", None)
        self._durable: DurableServingConfig = (
            dcfg if dcfg is not None else DurableServingConfig())
        if journal is not None:
            self._journal: Optional[RequestJournal] = journal
        elif self._durable.enabled:
            self._journal = RequestJournal(
                self._durable.journal_dir,
                fsync_policy=self._durable.fsync_policy,
                compact_every=self._durable.compact_every)
        else:
            self._journal = None
        # crash/handoff sets this so the drain's error-finishes do NOT
        # retire journal entries — the next boot must replay them
        self._preserve_journal = False
        self._requests = {}  # uid -> _Request, live + recently finished
        from collections import deque
        self._done_order: "deque" = deque()
        self._replayed = 0
        self._restart_count = int(
            os.environ.get("DS_SERVE_RESTART_COUNT", "0") or 0)
        # supervisor-exported budget headroom (how many more crashes the
        # relaunch loop will absorb) — surfaced through stats//health so
        # the router can prefer peers with budget left
        _budget = os.environ.get("DS_SERVE_RESTART_BUDGET_REMAINING", "")
        self._restart_budget_remaining = int(_budget) if _budget else None
        self._boot_wall = time.time()
        # last-256 completed requests for the metrics aggregates:
        # (t_submit, t_first, t_done, n_tokens, replayed)
        self._completed: "deque" = deque(maxlen=256)
        # observability: pre-resolved metric handles + per-request span
        # tracer + profiler guard, or None with the block disabled (every
        # recording site is one `if self._obs is not None` away from the
        # pre-observability scheduler). An explicit ``instruments``
        # (private registry) wins — test isolation; ``instruments=False``
        # force-disables regardless of config (the bench's A/B arm).
        obscfg = getattr(engine._config, "observability", None)
        self._ocfg: ObservabilityConfig = (
            obscfg if obscfg is not None else ObservabilityConfig())
        if instruments is False:
            self._obs: Optional[ServingInstruments] = None
        elif instruments is not None:
            self._obs = instruments
        elif self._ocfg.enabled:
            self._obs = ServingInstruments(
                trace_requests=self._ocfg.trace_requests,
                trace_spans_per_request=self._ocfg.trace_spans_per_request,
                trace_waves=self._ocfg.trace_waves,
                profile_dir=self._ocfg.profile_dir,
                profile_max_seconds=self._ocfg.profile_max_seconds)
        else:
            self._obs = None
        # the span API (observability/tracing.py): the scheduler's ticks and
        # the engine's halves of them record into the same tracer, the one
        # GET /debug/trace renders; off with the observability block
        self._tracer = (self._obs.tracer if self._obs is not None
                        else NO_TRACER)
        engine.tracer = self._tracer
        if disagg is not None:
            disagg.prefill_engine.tracer = self._tracer
        sm = engine._config.state_manager
        self._max_batch_tokens = sm.max_ragged_batch_size
        self._token_budget = min(token_budget or self._max_batch_tokens,
                                 self._max_batch_tokens)
        self._max_seqs = min(sm.max_ragged_sequence_count,
                             self._token_budget)
        self._max_context = sm.max_context

    # ---- client surface (any thread) ----

    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_token_id: Optional[int] = None,
               seed: int = 0,
               stop=None,
               min_new_tokens: int = 0,
               repetition_penalty: float = 1.0,
               logits_processor=None,
               speculative: Optional[str] = None,
               num_draft_tokens: int = 4,
               draft_ngram: int = 2,
               return_logprobs: bool = False,
               deadline_s: Optional[float] = None,
               queue_ttl_s: Optional[float] = None,
               stream: bool = False,
               tenant: Optional[str] = None,
               adapter: Optional[str] = None) -> RequestHandle:
        """``deadline_s``: end-to-end budget (queue + decode) after which
        the request finishes with :class:`DeadlineExceeded`; ``queue_ttl_s``
        bounds only the unadmitted wait. Both default from the
        ``serving_resilience`` config. ``stream=True`` marks the caller as
        a ``stream()`` consumer: its token queue is bounded by
        ``max_stream_backlog`` and stops the request if never drained.
        ``tenant`` selects the scheduling contract from the config
        ``tenants`` block (weighted-fair admission + budgets, per-tenant
        shed); unnamed requests run as "default". ``adapter`` names a LoRA
        adapter (or exact ``name@version``) from the engine's adapter
        registry; defaults to the tenant's ``default_adapter``; unknown
        ids are a structured error, never a silent base fallback."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self._max_context:
            raise SchedulingError(SchedulingResult.SequenceTokenLimitExceeded)
        if speculative is not None:
            if speculative != "prompt_lookup":
                raise UnsupportedFeature(
                    f"unknown speculative mode {speculative!r}",
                    reason="unknown_speculative_mode")
            if (min_new_tokens or repetition_penalty != 1.0
                    or logits_processor is not None or return_logprobs):
                # UnsupportedFeature (a ValueError) → the HTTP handler's
                # structured 400 (not a dead request). temperature/top_k/
                # top_p are FINE now: the window verify rejection-samples
                # against the draft point masses on the per-sequence key
                # chains. The leftovers here mutate the distribution per
                # emitted token (penalty/min_new) or need host callbacks/
                # per-token logprobs a multi-token accept cannot honor.
                raise UnsupportedFeature(
                    "speculative decoding does not compose with "
                    "min_new_tokens/repetition_penalty/logits_processor/"
                    "logprobs", reason="speculative_compose_unsupported")
            if temperature != 0.0 and not self._device_sampling:
                raise UnsupportedFeature(
                    "speculative sampling requires "
                    "sampling.device_sampling",
                    reason="speculative_requires_device_sampling")
        tenant_name = str(tenant) if tenant else "default"
        if adapter is None:
            # per-tenant default: the tenants config block can route a
            # tenant's unadorned requests onto its own adapter
            adapter = self._tenant_cfg(tenant_name).default_adapter
        adapter_id = None
        if adapter is not None:
            reg = getattr(self._engine, "adapters", None)
            if reg is None:
                raise UnsupportedFeature(
                    f"adapter {adapter!r} requested but the engine has no "
                    "adapter registry (adapters.enabled is off)",
                    reason="adapters_disabled")
            try:
                adapter_id = reg.resolve(str(adapter))
            except KeyError:
                raise UnsupportedFeature(
                    f"unknown adapter {adapter!r}",
                    reason="unknown_adapter") from None
        req = _Request(uid=next(self._uid_iter), prompt=prompt,
                       max_new_tokens=int(max_new_tokens),
                       temperature=float(temperature), top_k=int(top_k),
                       top_p=float(top_p), eos_token_id=eos_token_id,
                       seed=int(seed),
                       stop=InferenceEngineV2.normalize_stop(stop),
                       min_new_tokens=int(min_new_tokens),
                       repetition_penalty=float(repetition_penalty),
                       logits_processor=logits_processor,
                       speculative=speculative,
                       num_draft_tokens=int(num_draft_tokens),
                       draft_ngram=int(draft_ngram),
                       return_logprobs=bool(return_logprobs),
                       tenant=tenant_name,
                       adapter=str(adapter) if adapter else None,
                       adapter_id=adapter_id)
        req.rng = np.random.default_rng(req.seed)
        req.t_submit = time.monotonic()
        req.wake = self._wake
        req.stream = bool(stream)
        # a host logits_processor is an arbitrary callable — it cannot be
        # journaled, so such requests are (documented) non-durable
        req.journal_skip = logits_processor is not None
        res = self._res
        if res.enabled:
            if deadline_s is None:
                deadline_s = res.default_deadline_s
            if queue_ttl_s is None:
                queue_ttl_s = res.default_queue_ttl_s
            if stream and res.max_stream_backlog > 0:
                req.stream_q = queue.Queue(maxsize=int(res.max_stream_backlog))
        if deadline_s is not None:
            req.t_deadline = req.t_submit + float(deadline_s)
        if queue_ttl_s is not None:
            req.t_queue_deadline = req.t_submit + float(queue_ttl_s)
        with self._lock:
            # the lock orders this against stop()'s drain: a submit that
            # loses the race lands AFTER _stopping is visible and is
            # rejected here rather than queued for a loop that never runs
            if self._stopping or self._draining:
                raise RuntimeError("scheduler is stopped")
            if res.enabled and (
                    (res.max_queued
                     and self._queued_n >= res.max_queued)
                    or (res.max_queued_tokens and self._queued_n
                        and (self._queued_tokens + len(prompt)
                             > res.max_queued_tokens))):
                self._trace["shed"] += 1
                if self._obs is not None:
                    self._obs.shed.inc()
                raise SchedulerOverloaded(
                    f"queue full ({self._queued_n} requests, "
                    f"{self._queued_tokens} prompt tokens queued)",
                    retry_after_s=res.retry_after_s)
            tcfg = self._tenant_cfg(req.tenant)
            if (tcfg.max_queued and self._tenant_queued.get(
                    req.tenant, 0) >= tcfg.max_queued):
                # per-tenant shed: one tenant's backlog must not consume
                # the global queue budget the other tenants share
                self._trace["shed"] += 1
                if self._obs is not None:
                    self._obs.shed.inc()
                raise SchedulerOverloaded(
                    f"tenant {req.tenant!r} queue full "
                    f"({self._tenant_queued.get(req.tenant, 0)} queued)",
                    retry_after_s=res.retry_after_s if res.enabled else 1.0)
            if req.adapter_id is not None:
                # pin INSIDE the lock, after every shed check: a request
                # that is rejected above never takes a slot, and one that
                # is admitted holds its adapter until _finish unpins
                try:
                    self._engine.set_request_adapter(req.uid, req.adapter_id)
                except KeyError:
                    raise UnsupportedFeature(
                        f"adapter {req.adapter_id!r} was unloaded",
                        reason="unknown_adapter") from None
                except AdapterSlotsExhausted as e:
                    self._trace["shed"] += 1
                    if self._obs is not None:
                        self._obs.shed.inc()
                    raise SchedulerOverloaded(
                        str(e), retry_after_s=(res.retry_after_s
                                               if res.enabled else 1.0)
                    ) from None
            # journal BEFORE the request becomes visible to the loop: the
            # loop could otherwise finish it and write a finish record the
            # recovery scan would see before (and thus ignore) the admit
            self._journal_admit(req)
            self._requests[req.uid] = req
            self._inbox.append(req)
            self._active += 1
            req.queued = True
            self._tq_inc(req)
            self._queued_n += 1
            self._queued_tokens += len(prompt)
        if self._obs is not None:
            self._obs.request_submitted(req.uid, req.t_submit)
        self._wake.set()
        return RequestHandle(req)

    def _journal_admit(self, req: _Request) -> None:
        if self._journal is None or req.journal_skip:
            return
        now_w, now_m = time.time(), time.monotonic()
        params = {
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature, "top_k": req.top_k,
            "top_p": req.top_p, "eos_token_id": req.eos_token_id,
            "seed": req.seed, "stop": req.stop,
            "min_new_tokens": req.min_new_tokens,
            "repetition_penalty": req.repetition_penalty,
            "speculative": req.speculative,
            "num_draft_tokens": req.num_draft_tokens,
            "draft_ngram": req.draft_ngram,
            "return_logprobs": req.return_logprobs,
            "stream": req.stream, "tenant": req.tenant,
            "adapter": req.adapter_id}
        try:
            self._journal.record_admit(
                req.uid, req.prompt, params,
                deadline_wall=(now_w + (req.t_deadline - now_m)
                               if req.t_deadline is not None else None),
                queue_deadline_wall=(
                    now_w + (req.t_queue_deadline - now_m)
                    if req.t_queue_deadline is not None else None))
        except OSError as e:  # journaling is best-effort; serving goes on
            logger.warning(f"[journal] admit record failed for request "
                           f"{req.uid}: {e}")

    # ---- multi-tenant bookkeeping -------------------------------------

    def _tenant_cfg(self, name: str) -> TenantConfig:
        """Scheduling contract for a tenant: its ``tenants`` config entry,
        else the "default" entry, else a neutral weight-1 contract — unknown
        tenants are never rejected, they just share the default lane."""
        return self._tenants.get(name) or self._tenant_fallback

    def _tq_inc(self, req: _Request) -> None:
        """Caller holds ``_lock``. Mirrors every ``req.queued = True``."""
        self._tenant_queued[req.tenant] = \
            self._tenant_queued.get(req.tenant, 0) + 1
        if self._obs is not None:
            self._obs.tenant_queue_depth(
                req.tenant, self._tenant_queued[req.tenant])

    def _tq_dec(self, req: _Request) -> None:
        """Caller holds ``_lock``. Mirrors every ``req.queued = False``."""
        n = self._tenant_queued.get(req.tenant, 0) - 1
        self._tenant_queued[req.tenant] = max(0, n)
        if self._obs is not None:
            self._obs.tenant_queue_depth(
                req.tenant, self._tenant_queued[req.tenant])

    def lookup(self, uid: int) -> Optional[RequestHandle]:
        """Re-attach to an in-flight or recently finished request by id —
        the reconnect surface. Works across a warm restart because journal
        replay keeps original uids."""
        with self._lock:
            req = self._requests.get(int(uid))
        return RequestHandle(req) if req is not None else None

    @property
    def stats(self) -> dict:
        with self._lock:
            inbox = len(self._inbox)
            done = list(self._completed)  # (t_sub, t_first, t_done, n, rp)
            queued_tokens = self._queued_tokens
            tr = self._trace
            shed, quarantined = tr["shed"], len(tr["quarantined"])
            expired = tr["expired_queue"] + tr["expired_live"]
            watchdog_trips = tr["watchdog_trips"]
            spec_drafted = tr["spec_drafted"]
            spec_accepted = tr["spec_accepted"]
            fused_tokens = tr["fused_tokens"]
            decode_tokens = tr["decode_tokens"]
            fused_dispatches = tr["fused_dispatches"]
            fused_k_sum = tr["fused_k_sum"]
            prefill_overlap = tr["prefill_overlap_tokens"]
            tq = dict(self._tenant_queued)
            td = dict(self._tenant_delivered)
        out = {"waiting": len(self._waiting) + inbox,
               "live": len(self._live),
               "free_blocks": self._engine.free_blocks,
               "stopped": self._stopping,
               "draining": self._draining,
               "degraded": self._degraded,
               "last_progress_age_s": round(
                   time.monotonic() - self._last_progress, 3),
               "queued_tokens": queued_tokens,
               "shed": shed,
               "expired": expired,
               "quarantined": quarantined,
               "watchdog_trips": watchdog_trips,
               "spec_drafted": spec_drafted,
               "spec_accepted": spec_accepted,
               "spec_accept_rate": (round(spec_accepted / spec_drafted, 4)
                                    if spec_drafted else None),
               # continuous fusion: how much of the decode stream the
               # K-step wave owns (≈0 means every token pays a per-token
               # host round-trip), the realized mean window, and prefill
               # tokens fed while a wave was in flight
               "fused_occupancy": (round(fused_tokens / decode_tokens, 4)
                                   if decode_tokens else None),
               "mean_fused_K": (round(fused_k_sum / fused_dispatches, 2)
                                if fused_dispatches else None),
               "prefill_overlap_tokens": prefill_overlap,
               # disaggregated prefill/decode: group topology, handoff
               # queue depth, degrade/stall tallies (None = single group)
               "disagg": (self._disagg.stats()
                          if self._disagg is not None else None),
               "journal_depth": (self._journal.depth
                                 if self._journal is not None else 0),
               "replayed_requests": self._replayed,
               # live-migration readiness: a handoff in progress (journal
               # export running / exported) is NOT a plain drain
               "migrating": self._migrating,
               "journal_export_depth": self._journal_export_depth,
               "imported_requests": self._imported,
               "restart_count": self._restart_count,
               "restart_budget_remaining": self._restart_budget_remaining,
               "last_restart_age_s": (round(time.time() - self._boot_wall, 3)
                                      if self._restart_count else None),
               "completed": len(done)}
        # per-tenant scheduling view: queue depth, live load, delivered
        # tokens — the router's tenant-aware balancer and ds_top read this
        live_by = {}
        live_tok = {}
        for r in list(self._live):
            live_by[r.tenant] = live_by.get(r.tenant, 0) + 1
            live_tok[r.tenant] = (live_tok.get(r.tenant, 0)
                                  + len(r.prompt) + r.max_new_tokens)
        tenants = {}
        for name in set(tq) | set(td) | set(live_by) | set(self._tenants):
            cfg = self._tenant_cfg(name)
            tenants[name] = {
                "queued": tq.get(name, 0),
                "live": live_by.get(name, 0),
                "live_tokens": live_tok.get(name, 0),
                "delivered_tokens": td.get(name, 0),
                "weight": cfg.weight, "priority": cfg.priority}
        out["tenants"] = tenants
        out["prefix_cache"] = self._engine.prefix_cache_report()
        # multi-LoRA view: registered/live/pinned adapters — the router's
        # adapter-affinity scoring and ds_top read this
        reg = getattr(self._engine, "adapters", None)
        out["adapters"] = reg.stats() if reg is not None else None
        done = [d for d in done if d[3] > 0]
        # replayed requests' TTFT spans the crash + restart (measured from
        # the ORIGINAL admit) — real for that client, but a restart would
        # skew the scheduler-latency aggregate, so the mean excludes them
        fresh = [d for d in done if not d[4]]
        if fresh:
            # MII-style serving metrics over the recent completions:
            # time-to-first-token and per-request decode rate
            out["ttft_mean_s"] = round(
                sum(t1 - t0 for t0, t1, _, _, _ in fresh) / len(fresh), 4)
        if done:
            rates = [(n - 1) / max(t2 - t1, 1e-9)
                     for _, t1, t2, n, _ in done if n > 1]
            if rates:
                out["decode_tok_s_mean"] = round(sum(rates) / len(rates), 2)
        if self._obs is not None:
            # histogram-derived percentiles (whole-process, not last-256)
            ps = self._obs.ttft.percentiles((0.5, 0.95, 0.99))
            for q, v in zip(("p50", "p95", "p99"), ps):
                if v is not None:
                    out[f"ttft_{q}_s"] = round(v, 4)
            it99 = self._obs.inter_token.quantile(0.99)
            if it99 is not None:
                out["inter_token_p99_s"] = round(it99, 4)
        return out

    @property
    def trace(self) -> dict:
        """Resilience event counters (tests assert on these): ``shed``,
        ``expired_queue``/``expired_live``, ``tick_errors``, the ordered
        ``quarantined`` uid list, ``watchdog_trips``,
        ``slow_consumer_cancels``."""
        with self._lock:
            return {k: (list(v) if isinstance(v, list) else v)
                    for k, v in self._trace.items()}

    @property
    def observability(self) -> Optional[ServingInstruments]:
        """The instruments bundle (registry/tracer/profiler) the HTTP
        observability endpoints render, or None with the block disabled."""
        return self._obs

    @property
    def engine(self) -> InferenceEngineV2:
        """The served engine (the adapter admin endpoints reach its
        registry through this)."""
        return self._engine

    def trace_timeline(self, uid: int) -> Optional[dict]:
        """Per-request span timeline (``GET /requests/<uid>/trace``)."""
        if self._obs is None:
            return None
        return self._obs.tracer.timeline(str(int(uid)))

    def wait_timeout(self, handle: RequestHandle) -> Optional[float]:
        """Bound for a blocking wait on one request (the HTTP threads'
        ``result()`` / per-token stream gap): the remaining deadline when
        the request has one (plus slack for the expiry sweep to run), else
        the ``http_timeout_s`` cap. None only with resilience disabled —
        the legacy unbounded wait."""
        res = self._res
        cap = res.http_timeout_s if res.enabled else None
        t_deadline = handle._req.t_deadline
        if t_deadline is not None:
            remaining = max(0.05, t_deadline - time.monotonic()
                            + 4 * self._idle_wait + 1.0)
            return min(remaining, cap) if cap is not None else remaining
        return cap

    # ---- lifecycle ----

    def start(self) -> "ServingScheduler":
        assert self._thread is None, "scheduler already started"
        self._stopping = False
        self._draining = False
        self._degraded = False
        self._preserve_journal = False
        if self._journal is not None and self._durable.replay_on_start:
            self._replay_journal()
        self._last_progress = time.monotonic()
        self._thread = threading.Thread(target=self._run, name="ds-serve",
                                        daemon=True)
        self._thread.start()
        if self._res.enabled and self._res.watchdog_s > 0:
            self._watchdog = threading.Thread(
                target=self._watch, name="ds-serve-watchdog", daemon=True)
            self._watchdog.start()
        return self

    def stop(self, timeout: float = 30.0, drain: bool = False) -> None:
        """Stop the loop. ``drain=True`` first refuses new submissions and
        lets in-flight requests run to completion; the WHOLE shutdown
        (drain poll + thread join) is bounded by ``timeout``. Without
        drain, pending requests are error-finished immediately."""
        deadline = time.monotonic() + timeout
        if drain and self._thread is not None:
            with self._lock:
                self._draining = True  # submit() rejects, loop keeps going
            while time.monotonic() < deadline:
                with self._lock:
                    idle = self._active == 0  # submit().._finish() span —
                    # immune to the loop's unlocked queue transfers
                if idle:
                    break
                time.sleep(self._idle_wait)
        self._stopping = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(max(0.0, deadline - time.monotonic()) or 0.01)
            self._thread = None
        if self._watchdog is not None:
            # joined so a later start() can't race a stale watchdog seeing
            # the reset _stopping flag and living on as a duplicate
            self._watchdog.join(1.5)
            self._watchdog = None

    def handoff(self, timeout: float = 30.0) -> None:
        """SIGTERM path: stop the loop WITHOUT retiring journal entries,
        then fsync the journal — the next daemon generation (pointed at
        the same journal dir) replays every in-flight request and its
        resumed stream continues bit-identically. Pending local handles
        error-finish exactly like ``stop()``; remote clients re-attach by
        uid against the new boot."""
        self._preserve_journal = True
        with self._lock:
            self._draining = True  # submit() refuses from here on
        self._stopping = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if self._watchdog is not None:
            self._watchdog.join(1.5)
            self._watchdog = None
        if self._journal is not None:
            # every emitted token was journaled at its tick's end, so the
            # checkpoint only needs to make the tail durable
            try:
                self._journal.checkpoint()
            except OSError as e:
                logger.warning(f"[journal] handoff checkpoint failed: {e}")

    def _replay_journal(self) -> None:
        """Warm restart: re-admit every unfinished journaled request with
        its original uid and remaining wall-clock deadline. Emitted tokens
        become prefix feed (the eviction-replay machinery re-prefills them
        chunkwise and samples the next token when the feed completes), the
        host RNG re-burns its consumed entropy, and ``_restore_sampler``
        fast-forwards the device key chain at admission — so resumed
        greedy AND sampled streams are byte-identical to an uninterrupted
        run. Requests whose journaled output already satisfies a finish
        condition (crash after the last token, before the finish record)
        complete immediately instead of re-entering the queue."""
        try:
            entries = self._journal.recover()
        except OSError as e:
            logger.warning(f"[journal] recovery failed: {e}")
            return
        if not entries:
            return
        admitted, finished, _ = self._admit_replayed_entries(entries,
                                                             live=False)
        logger.warning(f"[journal] replayed {len(admitted) + len(finished)} "
                       f"unfinished request(s) ({len(finished)} already "
                       f"complete)")

    def _repin_adapter(self, req: _Request) -> bool:
        """Re-pin a replayed request's journaled adapter version; on any
        failure set a typed error and report False (the caller
        error-finishes the request instead of continuing it wrong)."""
        if req.adapter_id is None:
            return True
        try:
            self._engine.set_request_adapter(req.uid, req.adapter_id)
            return True
        except (KeyError, RuntimeError) as e:
            req.error = UnsupportedFeature(
                f"replay: adapter {req.adapter_id!r} unavailable: {e}",
                reason="adapter_unavailable")
            return False

    def _req_from_entry(self, e, now_w: float, now_m: float) -> _Request:
        """Rebuild a scheduler request from a journal entry: original uid,
        emitted tokens as prefix feed, key burns for the sampler
        fast-forward, wall deadlines converted back to monotonic."""
        p = e.params
        req = _Request(
            uid=e.uid, prompt=[int(t) for t in e.prompt],
            max_new_tokens=int(p.get("max_new_tokens", 32)),
            temperature=float(p.get("temperature", 0.0)),
            top_k=int(p.get("top_k", 0)),
            top_p=float(p.get("top_p", 1.0)),
            eos_token_id=p.get("eos_token_id"),
            seed=int(p.get("seed", 0)),
            stop=[[int(t) for t in s] for s in p.get("stop") or []],
            min_new_tokens=int(p.get("min_new_tokens", 0)),
            repetition_penalty=float(p.get("repetition_penalty", 1.0)),
            speculative=p.get("speculative"),
            num_draft_tokens=int(p.get("num_draft_tokens", 4)),
            draft_ngram=int(p.get("draft_ngram", 2)),
            return_logprobs=bool(p.get("return_logprobs")),
            tenant=str(p.get("tenant") or "default"),
            adapter=p.get("adapter"), adapter_id=p.get("adapter"))
        req.outputs = [int(t) for t in e.tokens]
        req.logprobs = list(e.logprobs)
        req.key_burns = int(e.key_burns)
        req.journaled_n = len(req.outputs)
        req.journaled_burns = req.key_burns
        req.replayed = True
        req.stream = bool(p.get("stream"))
        req.wake = self._wake
        req.t_submit = now_m
        if req.outputs:
            req.t_first = now_m
        req.rng = np.random.default_rng(req.seed)
        self._burn_host_rng(req)
        if (req.stream and self._res.enabled
                and self._res.max_stream_backlog > 0):
            req.stream_q = queue.Queue(
                maxsize=int(self._res.max_stream_backlog))
        if e.deadline_wall is not None:
            req.t_deadline = now_m + (e.deadline_wall - now_w)
        if e.queue_deadline_wall is not None:
            req.t_queue_deadline = now_m + (e.queue_deadline_wall - now_w)
        return req

    def _admit_replayed_entries(self, entries, live: bool):
        """Re-admit journal entries into the scheduler. ``live=False`` is
        the boot-time replay (the loop has not started; entries land in
        ``_waiting`` and the uid iterator bumps past them). ``live=True``
        is a cross-replica import on a RUNNING scheduler: entries land in
        the inbox (the loop's own transfer point), are re-journaled into
        THIS replica's WAL so a later crash here still preserves them, and
        uids already owned by this scheduler are refused (split brain —
        two replicas must never serve one stream). Returns
        ``(admitted_uids, finished_uids, refused_uids)``."""
        now_w, now_m = time.time(), time.monotonic()
        max_uid = 0
        finish_now: List[_Request] = []
        admitted: List[int] = []
        refused: List[int] = []
        split_brain = (get_fault_injector().fire("router.split_brain_uid")
                       if live else None)
        with self._lock:
            for e in entries:
                if live:
                    if self._stopping or self._draining:
                        refused.append(e.uid)
                        continue
                    collide = (split_brain is not None
                               and int(split_brain.get("uid", e.uid))
                               == e.uid)
                    if e.uid in self._requests or collide:
                        logger.warning(
                            f"[journal] import refused uid {e.uid}: already "
                            f"owned by this replica (split brain)")
                        refused.append(e.uid)
                        continue
                max_uid = max(max_uid, e.uid)
                req = self._req_from_entry(e, now_w, now_m)
                self._requests[req.uid] = req
                self._active += 1
                if self._finished_already(req):
                    finish_now.append(req)
                elif not self._repin_adapter(req):
                    # the journaled VERSIONED id must re-resolve exactly —
                    # a replayed stream continuing on different factors (or
                    # silently on base weights) would diverge byte-wise, so
                    # unavailability is a loud error finish
                    finish_now.append(req)
                else:
                    req.queued = True
                    self._tq_inc(req)
                    self._queued_n += 1
                    self._queued_tokens += len(req.prompt)
                    if live:
                        self._inbox.append(req)
                    else:
                        self._waiting.append(req)
                    admitted.append(req.uid)
                self._replayed += 1
                if live:
                    self._imported += 1
                if self._obs is not None:
                    self._obs.request_replayed(req.uid, req.t_submit,
                                               len(req.outputs))
            if live and self._journal is not None:
                # the importer's own WAL must cover adopted requests from
                # this instant: admit + folded progress, inside the lock so
                # no finish can precede its admit (same ordering as submit)
                for e in entries:
                    if e.uid in refused:
                        continue
                    try:
                        self._journal.record_admit(
                            e.uid, e.prompt, e.params,
                            deadline_wall=e.deadline_wall,
                            queue_deadline_wall=e.queue_deadline_wall)
                        if e.tokens or e.key_burns:
                            self._journal.record_progress(
                                e.uid, e.tokens, len(e.tokens), e.key_burns,
                                logprobs=e.logprobs or None)
                    except OSError as err:
                        logger.warning(f"[journal] import record failed "
                                       f"for request {e.uid}: {err}")
        if not live:
            # original uids survive the restart; fresh mints go above them.
            # Imports do NOT bump: a migrated uid lives in its source
            # replica's stride (DS_SERVE_UID_BASE namespacing) and must
            # not drag this replica's iterator into a foreign namespace.
            nxt = next(self._uid_iter)
            self._uid_iter = itertools.count(max(nxt, max_uid + 1))
        for req in finish_now:  # _finish takes the lock itself
            self._finish(req, flush=False)
        if live and (admitted or finish_now):
            self._wake.set()
        return admitted, [r.uid for r in finish_now], refused

    # ---- cross-replica live migration (router surface) ----

    def export_journal(self, drain: bool = True) -> bytes:
        """Drain this replica's unfinished journal entries as a portable
        CRC-frame stream (``GET /journal/export``): flips readiness to
        ``migrating``, stops the scheduler WITHOUT retiring journal
        entries (the ``handoff()`` path), and snapshots the unfinished
        state. A peer POSTs the bytes to ``/journal/import`` and replays
        every stream mid-flight, byte-identically."""
        if self._journal is None:
            raise RuntimeError("journal export needs durable serving "
                               "(durable_serving.enabled)")
        self._migrating = True
        with self._lock:
            self._journal_export_depth = self._journal.depth
        if drain and self._thread is not None:
            self.handoff()
        frames, depth = self._journal.export_frames()
        with self._lock:
            self._journal_export_depth = depth
        return frames

    def import_journal_frames(self, buf: bytes) -> dict:
        """Adopt a peer's exported journal frames mid-run
        (``POST /journal/import``): scan with the recovery scanner
        (damaged records quarantine individually), re-admit the unfinished
        requests with their ORIGINAL uids, and continue each stream
        byte-identically — emitted tokens replay as prefix feed and the
        PRNG chains fast-forward by their recorded burn counts."""
        from .journal import entries_from_frames
        entries, bad = entries_from_frames(buf)
        admitted, finished, refused = self._admit_replayed_entries(
            entries, live=True)
        if admitted or finished:
            logger.warning(
                f"[journal] imported {len(admitted) + len(finished)} "
                f"migrated request(s) ({len(finished)} already complete, "
                f"{len(refused)} refused, {bad} quarantined)")
        return {"imported": len(admitted), "finished": len(finished),
                "refused_uids": refused, "quarantined_records": bad}

    def _finished_already(self, req: _Request) -> bool:
        if not req.outputs:
            return False
        if len(req.outputs) >= req.max_new_tokens:
            return True
        # emission never continues past eos, so membership == cut
        if req.eos_token_id is not None and req.eos_token_id in req.outputs:
            return True
        return bool(req.stop
                    and self._engine.hit_stop(req.outputs, req.stop))

    def _burn_host_rng(self, req: _Request) -> None:
        """Re-consume the host numpy sampler's entropy for a replayed
        request: exactly one vocab-sized gumbel per emitted token iff the
        request sampled on host (positive temperature and top_p, not
        device-owned) — the replayed generator then continues the same
        draw sequence an uninterrupted run would have used."""
        if req.temperature <= 0 or req.top_p <= 0 or not req.outputs:
            return
        if req.speculative is not None or self._device_eligible(req):
            return  # chain lives on device; _restore_sampler handles it
        vocab = int(self._engine._model.config.vocab_size)
        for _ in req.outputs:
            req.rng.gumbel(size=vocab)

    def _restore_sampler(self, req: _Request) -> None:
        """Re-seed the device key chain at its recorded position for a
        request entering the live set WITH history (journal replay or
        eviction replay): ``flush()`` dropped the key, and reseeding from
        scratch would fork the sampled stream mid-request."""
        if req.key_burns > 0 and req.outputs:
            self._engine.fast_forward_sampler(req.uid, req.seed,
                                              req.key_burns)

    def _run(self) -> None:
        crash: Optional[BaseException] = None
        try:
            while not self._stopping:
                t_tick = time.monotonic()
                progressed = self._safe_step()
                self._last_progress = time.monotonic()
                if self._obs is not None and progressed:
                    # idle polls stay out: the histogram measures work
                    # ticks, not the idle_wait cadence
                    self._obs.tick.record(self._last_progress - t_tick)
                if self._obs is not None:
                    tr = self._trace
                    self._obs.refresh(
                        self._queued_n, len(self._live),
                        self._engine.free_blocks,
                        tr["fused_tokens"], tr["decode_tokens"])
                if not progressed:
                    with self._tracer.scope("ds.tick.idle_wait"):
                        self._wake.wait(self._idle_wait)
                    self._wake.clear()
        except BaseException as e:  # noqa: BLE001 — loop death must not
            crash = e               # silently hang every blocked caller
            # a crash is exactly what the journal exists for: keep every
            # entry so the next boot replays them (clean stop() retires)
            self._preserve_journal = True
        finally:
            self._stopping = True
            # drain UNDER the lock: submit() rejects once _stopping is
            # visible, so nothing can land in the inbox after this snapshot
            with self._lock:
                pending = self._live + self._waiting + self._inbox
                self._live, self._waiting, self._inbox = [], [], []
            for req in pending:
                if not req.done.is_set():
                    try:
                        self._engine.flush(req.uid)
                    except Exception:  # noqa: BLE001 — uid may be unknown
                        pass
                    req.error = crash or RuntimeError("server stopped")
                    self._finish(req, flush=False)
        if crash is not None:
            raise crash

    # ---- scheduler iteration (scheduler thread only) ----

    def step(self) -> bool:
        """One continuous-batching iteration: admit + prefill newly feasible
        prompts, advance every live sequence one decode token. Returns
        whether any work happened (False = fully idle)."""
        inj = get_fault_injector()
        if inj.enabled:
            args = inj.fire("serve.tick_hang")
            if args is not None:
                time.sleep(float(args.get("seconds", 0.5)))
            if inj.fire("serve.tick_error") is not None:
                raise InjectedFault("injected serving tick error")
            args = inj.fire("serve.crash")
            if args is not None:
                if str(args.get("mode", "drop")) == "exit":
                    # a real daemon death: the supervisor's relaunch path
                    os._exit(int(args.get("exit_code", 23)))
                # kill just the scheduler loop (BaseException sails past
                # the tick retry AND the quarantine bisect) — in-process
                # tests then replay the journal over the same engine
                raise ServingCrash("injected daemon crash")
        with self._tracer.scope("ds.tick.admit"):
            with self._lock:
                if self._inbox:
                    self._waiting.extend(self._inbox)
                    self._inbox = []

            # cancelled LIVE rows free their engine state HERE, before this
            # tick's admission — a cancel storm's blocks are available to
            # _admit in the same step instead of one tick later
            self._sweep_cancelled()
            self._expire_deadlines()

            admitted = self._admit()
        advanced = self._advance_tick()
        if self._journal is not None:
            with self._tracer.scope("ds.tick.emit"):
                self._journal_progress()
        return bool(admitted or advanced)

    def _journal_progress(self) -> None:
        """Append each live request's new tokens + key-burn count since the
        last record — the high-water marks a warm restart resumes from."""
        for req in self._live:
            if req.journal_skip:
                continue
            n = len(req.outputs)
            if n == req.journaled_n and req.key_burns == req.journaled_burns:
                continue
            lps = (req.logprobs[req.journaled_n:n]
                   if req.return_logprobs else None)
            t0 = time.monotonic()
            try:
                self._journal.record_progress(
                    req.uid, req.outputs[req.journaled_n:n], n,
                    req.key_burns, logprobs=lps)
            except OSError as e:
                logger.warning(f"[journal] progress record failed for "
                               f"request {req.uid}: {e}")
                continue
            if self._obs is not None:
                self._obs.tracer.span(
                    str(req.uid), "journal_append", t0, time.monotonic(),
                    {"tokens": n - req.journaled_n})
            req.journaled_n = n
            req.journaled_burns = req.key_burns

    def _sweep_cancelled(self) -> None:
        for req in [r for r in self._live if r.cancelled]:
            self._live.remove(req)
            self._finish(req)
        for req in [r for r in self._waiting if r.cancelled]:
            self._waiting.remove(req)
            self._finish(req, flush=False)

    def _expire_deadlines(self) -> None:
        """Finish requests past their deadline/TTL with a typed
        ``DeadlineExceeded``. Queued requests expire on either bound
        without ever touching the engine; live ones expire on the
        end-to-end deadline and flush, releasing their KV reservation."""
        if not self._res.enabled:
            return
        now = time.monotonic()

        def _past(t: Optional[float]) -> bool:
            return t is not None and now > t

        for req in [r for r in self._waiting
                    if _past(r.t_queue_deadline) or _past(r.t_deadline)]:
            self._waiting.remove(req)
            req.error = DeadlineExceeded(
                f"request {req.uid} expired unadmitted after "
                f"{now - req.t_submit:.3f}s")
            self._trace["expired_queue"] += 1
            self._finish(req, flush=False)
        for req in [r for r in self._live if _past(r.t_deadline)]:
            self._live.remove(req)
            req.error = DeadlineExceeded(
                f"request {req.uid} exceeded its deadline after "
                f"{now - req.t_submit:.3f}s ({len(req.outputs)} tokens)")
            self._trace["expired_live"] += 1
            self._finish(req)  # flush=True: KV reservation released

    def _safe_step(self) -> bool:
        """One tick behind the fault boundary. Transient engine errors are
        retried with backoff; a fault that survives the retry budget is
        reproducible and gets bisected to the one poisoning request, which
        alone is error-finished — the loop survives. Only a fault that
        reproduces with NO live requests (engine-global breakage with
        nothing to quarantine) still propagates to _run, whose drain
        error-finishes every blocked caller."""
        res = self._res
        if not res.enabled:
            return self.step()

        def _tick():
            try:
                return self.step()
            except Exception:
                self._trace["tick_errors"] += 1
                raise

        try:
            return retry_with_backoff(
                _tick, retries=1 + max(0, res.tick_retries),
                base_delay=res.tick_retry_backoff_s,
                exceptions=(Exception, ), desc="serving tick")
        except RetriesExhausted as e:
            self._quarantine(e.__cause__ if e.__cause__ is not None else e)
            return True

    def _quarantine(self, exc: BaseException) -> None:
        """Isolate the request that poisons the tick. The fault outlived
        its retry budget, so it is reproducible: bisect the live wave —
        tick one half with the other parked, keep whichever half still
        reproduces the fault — until one request remains, and error-finish
        only it. A probe IS a regular tick over a subset, so healthy
        requests advance their (deterministic) decode during the search;
        at most O(log n) extra probe ticks run."""
        suspects = list(self._live)
        if not suspects:
            raise exc
        while len(suspects) > 1:
            test = suspects[:len(suspects) // 2]
            rest = suspects[len(suspects) // 2:]
            test_ids = {id(r) for r in test}
            parked = [r for r in self._live if id(r) not in test_ids]
            self._live = [r for r in self._live if id(r) in test_ids]
            try:
                self._advance_tick()
                nxt = rest  # test half ticked clean: culprit is elsewhere
            except Exception:  # noqa: BLE001 — any repro narrows the hunt
                nxt = test
            finally:
                self._live.extend(parked)
            # a probe tick may have retired suspects (eos/eviction): keep
            # only the ones still live — an empty set means the fault
            # dissolved and the next regular tick proceeds normally
            live_ids = {id(r) for r in self._live}
            suspects = [r for r in nxt if id(r) in live_ids]
            if not suspects:
                return
        culprit = suspects[0]
        if culprit in self._live:
            self._live.remove(culprit)
        culprit.error = exc
        self._trace["quarantined"].append(culprit.uid)
        if self._obs is not None:
            self._obs.quarantined.inc()
            self._obs.tracer.event(str(culprit.uid), "quarantine",
                                   args={"error": repr(exc)})
        logger.warning(f"[serving] quarantined request {culprit.uid} after "
                       f"reproducible tick fault: {exc!r}")
        self._finish(culprit)  # flush=True: its KV reservation is released

    def _watch(self) -> None:
        """Watchdog thread: with work in flight and no tick progress for
        ``watchdog_s``, flip /health to degraded (carrying the
        last-progress age); clear it when the loop moves again."""
        period = max(0.02, min(self._res.watchdog_s / 4, 0.5))
        while not self._stopping:
            time.sleep(period)
            with self._lock:
                busy = self._active > 0
            age = time.monotonic() - self._last_progress
            if busy and age > self._res.watchdog_s:
                if not self._degraded:
                    self._degraded = True
                    with self._lock:
                        self._trace["watchdog_trips"] += 1
                    if self._obs is not None:
                        self._obs.watchdog_trips.inc()
                    logger.warning(f"[serving-watchdog] no scheduler "
                                   f"progress for {age:.2f}s with work in "
                                   "flight; /health degraded")
            elif self._degraded:
                self._degraded = False
                logger.warning("[serving-watchdog] scheduler progressing "
                               "again; /health restored")

    # Admission reservation MIRRORS InferenceEngineV2.generate: blocks for
    # the full feed + decode budget of every admitted AND live sequence,
    # so a tick's put cannot exhaust the allocator mid-flight (the shared
    # arithmetic is the model's own get_kv_requirements). Differences from
    # generate(), both deliberate: max_context is enforced at submit()
    # (sequences retire at seen+1 > max_context, so replay feeds stay
    # bounded), and prefill happens chunkwise inside _advance_tick's
    # SplitFuse budget instead of one whole-feed put per admission.
    def _future_blocks(self, seq_desc, extra: int) -> int:
        _, req = self._engine._model.get_kv_requirements(seq_desc, extra,
                                                         1 << 30)
        return req

    def _live_reserve(self) -> int:
        total = 0
        for r in self._live:
            seq = self._engine._state_manager.get_sequence(r.uid)
            if seq is None:  # admitted this tick, nothing fed yet
                seq = PlaceholderSequenceDescriptor()
            total += self._future_blocks(
                seq, r.pending + max(0, r.max_new_tokens - len(r.outputs)))
        return total

    def _admit(self) -> List[_Request]:
        """Move waiting requests into the live set (no forward happens
        here — _advance_tick feeds them chunkwise). A request admits when
        blocks for its ENTIRE feed + decode budget fit after the projected
        growth of everything already live.

        Admission order is weighted-fair across tenants: each pick takes
        the FIFO head of the tenant with the smallest weighted live-token
        deficit (higher ``priority`` strictly first; tenants at their
        ``max_live_tokens`` cap are skipped, so their share redistributes
        — work-conserving). The loop still breaks the moment the chosen
        head cannot fit, never queue-jumping within or across tenants, so
        a single-tenant system degenerates exactly to plain FIFO."""
        free = self._engine.free_blocks - self._live_reserve()
        admitted: List[_Request] = []
        live_tok: Dict[str, int] = {}
        for r in self._live:
            live_tok[r.tenant] = (live_tok.get(r.tenant, 0)
                                  + len(r.prompt) + r.max_new_tokens)
        queues: Dict[str, List[_Request]] = {}
        for r in self._waiting:
            queues.setdefault(r.tenant, []).append(r)
        while True:
            if len(self._live) >= self._max_seqs:
                break
            best = None
            for name, q in queues.items():
                if not q:
                    continue
                cfg = self._tenant_cfg(name)
                if (cfg.max_live_tokens
                        and live_tok.get(name, 0) >= cfg.max_live_tokens):
                    continue
                key = (-cfg.priority,
                       live_tok.get(name, 0) / cfg.weight, name)
                if best is None or key < best[0]:
                    best = (key, name, q)
            if best is None:
                break
            _, name, q = best
            req = q[0]
            need = self._future_blocks(
                PlaceholderSequenceDescriptor(),
                len(req.feed) + max(0, req.max_new_tokens - len(req.outputs)))
            if need > free:
                # the chosen head is the most-deficient admissible tenant's
                # oldest request — admitting anything else over it would be
                # queue-jumping, so stop the whole pass here
                break
            q.pop(0)
            free -= need
            self._waiting.remove(req)
            req.fed = 0
            self._restore_sampler(req)
            self._live.append(req)
            self._queue_drop(req)
            admitted.append(req)
            live_tok[name] = (live_tok.get(name, 0)
                              + len(req.prompt) + req.max_new_tokens)
        if not admitted and not self._live and self._waiting:
            # nothing can reserve full headroom: admit ONE on feed
            # feasibility alone rather than deadlocking (eviction truncates
            # it if the cache truly runs out)
            req = self._waiting[0]
            feed_need = self._future_blocks(PlaceholderSequenceDescriptor(),
                                            len(req.feed))
            if feed_need <= self._engine._state_manager.free_blocks:
                self._waiting.pop(0)
                req.fed = 0
                self._restore_sampler(req)
                self._live.append(req)
                self._queue_drop(req)
                admitted.append(req)
            else:
                # nothing is live, so nothing will ever free up: this
                # request can never run (generate() raises here too)
                req.error = SchedulingError(
                    SchedulingResult.KVCacheLimitExceeded)
                self._waiting.remove(req)
                self._finish(req, flush=False)
        if self._obs is not None and admitted:
            now = time.monotonic()
            for r in admitted:
                self._obs.request_admitted(r.uid, r.t_submit, now)
        return admitted

    @staticmethod
    def _water_fill(demands: Dict[str, Tuple[float, int]],
                    budget: int) -> Dict[str, int]:
        """Weighted max-min (water-filling) split of ``budget`` tokens over
        ``{tenant: (weight, demand)}``: each round hands every unsatisfied
        tenant its weighted share of the remaining budget (at least 1, so
        the loop always terminates), tenants that fill their demand drop
        out and their leftover redistributes — work-conserving."""
        grant = {name: 0 for name in demands}
        pending = {name: d for name, (_, d) in demands.items() if d > 0}
        while budget > 0 and pending:
            wsum = sum(demands[n][0] for n in pending)
            round_budget = budget
            for name in list(pending):
                w = demands[name][0]
                share = max(1, int(round_budget * w / wsum))
                take = min(share, pending[name], budget)
                grant[name] += take
                pending[name] -= take
                budget -= take
                if pending[name] <= 0:
                    del pending[name]
                if budget <= 0:
                    break
        return grant

    def _fair_takes(self, reqs, budget: int):
        """Split a prefill token budget across ``reqs`` (each wanting
        ``req.pending``) by tenant weight, FIFO within a tenant. With one
        tenant this is exactly the old greedy head-of-line loop. Returns
        ``[(req, take), ...]`` preserving the input (arrival) order."""
        tenants = {r.tenant for r in reqs}
        takes = []
        if len(tenants) <= 1:
            spent = 0
            for req in reqs:
                if spent >= budget:
                    break
                take = min(req.pending, budget - spent)
                takes.append((req, take))
                spent += take
            return takes
        demands = {}
        for r in reqs:
            w, d = demands.get(r.tenant, (self._tenant_cfg(r.tenant).weight,
                                          0))
            demands[r.tenant] = (w, d + r.pending)
        grant = self._water_fill(demands, budget)
        for req in reqs:
            left = grant.get(req.tenant, 0)
            if left <= 0:
                continue
            take = min(req.pending, left)
            grant[req.tenant] = left - take
            takes.append((req, take))
        return takes

    def _fair_decode_order(self, decodes):
        """WFQ order for an oversubscribed decode set: virtual finish time
        ``(i+1)/weight`` over each tenant's FIFO index ``i``, priority
        classes strictly first, uid as the deterministic tiebreak. Called
        only when decodes exceed the tick budget — the common case skips
        the sort entirely."""
        idx: Dict[str, int] = {}

        def key(r):
            cfg = self._tenant_cfg(r.tenant)
            i = idx.get(r.tenant, 0)
            idx[r.tenant] = i + 1
            return (-cfg.priority, (i + 1) / cfg.weight, r.uid)

        return sorted(decodes, key=key)

    def _queue_drop(self, req: _Request) -> None:
        """Request left the unadmitted set (admitted; finishes drop inside
        _finish's own lock section)."""
        with self._lock:
            if req.queued:
                req.queued = False
                self._tq_dec(req)
                self._queued_n -= 1
                self._queued_tokens -= len(req.prompt)

    def _queue_readd(self, req: _Request) -> None:
        """Eviction sent a live request back to the waiting queue."""
        with self._lock:
            if not req.queued:
                req.queued = True
                self._tq_inc(req)
                self._queued_n += 1
                self._queued_tokens += len(req.prompt)

    def _advance_tick(self) -> bool:
        """ONE scheduling pass of ≤ token_budget fed tokens (Dynamic
        SplitFuse): decoding sequences (pending == 1) are guaranteed their
        token first, prefilling sequences chunk into the remaining budget.
        A sequence samples only on the tick its feed completes.

        With continuous fusion (the default), the fusable decodes run
        their K-step wave EVERY tick — dispatched async, with prefill
        chunks and admission overlapped while the program runs on device
        (_continuous_tick). With the gate off, the wave only runs in the
        legacy exclusive mode: a quiet system with no prefill, no inbox,
        and no ADMISSIBLE waiting request (a request that cannot admit
        until KV frees gets no say — it cannot run either way, so it must
        not pin every decode to per-token dispatches)."""
        if self._disagg is not None:
            self._disagg_fed_tick = False
            self._disagg_pump()
        if not self._live:
            return False
        budget = self._token_budget
        decodes, prefills = [], []
        for r in self._live:
            if r.uid in self._on_prefill:
                # resident on the prefill group: pending>1 feeds there
                # (_disagg_fill); pending==1 means the final prompt chunk
                # sampled but its KV is still mid-handoff — the decode
                # wave cannot own it yet
                if r.pending <= 1:
                    self._disagg.note_decode_stall(r.uid)
                continue
            (decodes if r.pending == 1 else prefills).append(r)
        if self._fused_window > 1 and decodes:
            if self._cf.enabled:
                done = self._continuous_tick(decodes, prefills, budget)
                if done is not None:
                    return done
                # no wave could form (nothing fusable / adaptive K < 2 /
                # KV refused): the per-token tick below owns this pass
            elif (not prefills and not self._inbox
                    and not self._has_admissible_waiting()):
                # legacy exclusive mode: fuse EVERY feasible decode (K
                # steps, one dispatch) — plain-greedy requests and (when
                # on-device sampling is enabled) sampled/controlled ones
                # together; the partition is by feasibility, not
                # greediness. Requests the device cannot own — speculative
                # drafting and host logits_processor callbacks — keep
                # their per-token tick below (each request's sampling
                # depends only on its own context, so outputs are
                # unchanged by who shares the dispatch). A just-admitted
                # 1-token-prompt request has pending==1 but NO engine
                # sequence yet — it must take the per-token path, which
                # owns prefill (fused_decode_steps requires prefilled
                # history).
                eligible = [r for r in decodes if self._fusable(r)]
                fused = self._fused_tick(eligible) if eligible else []
                # speculative rows run their OWN fused wave (the
                # draft/verify scan feeds 1+d tokens per window — a
                # different program from the 1-token fused decode),
                # grouped so one dispatch still serves everything with
                # the same feed geometry
                live_ids = {id(r) for r in self._live}
                spec_rows = [r for r in decodes
                             if id(r) in live_ids and self._prefilled(r)
                             and self._spec_fusable(r)]
                fused += self._fused_spec_tick(spec_rows) if spec_rows \
                    else []
                if fused:
                    # exclude exactly the requests the fused dispatch
                    # advanced; near-budget greedy stragglers the
                    # partition left out stay in ``decodes`` and take
                    # this same tick's per-token path — one constrained
                    # request no longer demotes the whole wave
                    fused_ids = {id(r) for r in fused}
                    live_ids = {id(r) for r in self._live}
                    decodes = [r for r in decodes
                               if id(r) not in fused_ids
                               and id(r) in live_ids]
                    if not decodes:
                        return True
                    # fall through: per-token tick for the remainder
        advanced = self._per_token_tick(decodes, prefills, budget)
        # in-flight handoffs ARE progress: keep ticking (pumping) at full
        # cadence instead of sleeping idle_wait on top of the transfer
        return advanced or bool(self._on_prefill)

    def _ctx_tokens(self, reqs) -> int:
        """Sum of the rows' context lengths (tokens the engine already holds
        in the KV cache) now, before a put: the ``ctx_tokens`` of the span
        that put records. 0 with observability off."""
        if self._obs is None:
            return 0
        seqs = (self._engine._state_manager.get_sequence(r.uid) for r in reqs)
        return sum(seq.seen_tokens for seq in seqs if seq is not None)

    def _prefilled(self, r: _Request) -> bool:
        seq = self._engine._state_manager.get_sequence(r.uid)
        return seq is not None and seq.seen_tokens > 0

    def _fusable(self, r: _Request) -> bool:
        if r.speculative is not None or not self._prefilled(r):
            return False
        if self._plain_greedy(r):
            return True
        return self._fused_sampled and self._device_eligible(r)

    def _has_admissible_waiting(self) -> bool:
        """True only if some waiting request could actually join the live
        set right now (seq-count + full-reservation feasible). _admit ran
        earlier this tick, so leftovers are normally infeasible — this
        re-check exists because admission stops at the first infeasible
        head-of-line request, which may shadow a smaller feasible one.
        An infeasible-until-KV-frees request returns False: it cannot run
        whether or not the wave fuses, so it must not demote the fused
        path to per-token mode (the `_waiting`-pins-the-wave bug)."""
        if not self._waiting:
            return False
        if len(self._live) >= self._max_seqs:
            return False
        free = self._engine.free_blocks - self._live_reserve()
        for req in self._waiting:
            need = self._future_blocks(
                PlaceholderSequenceDescriptor(),
                len(req.feed) + max(0, req.max_new_tokens - len(req.outputs)))
            if need <= free:
                return True
        return False

    def _adaptive_window(self) -> int:
        """Continuous-fusion window: the configured K, shrunk toward 1 as
        queue depth grows (halved per ``queue_depth_per_halving`` queued
        requests) and capped so the wave's estimated duration fits inside
        ``deadline_slack_frac`` of the slack to the nearest deadline —
        overlap never costs more than a bounded TTFT/deadline delay."""
        cap = self._fused_window
        cf = self._cf
        if cf.queue_depth_per_halving > 0:
            with self._lock:
                depth = len(self._inbox)
            depth += len(self._waiting)
            cap >>= min(depth // cf.queue_depth_per_halving, cap.bit_length())
        if self._step_ewma > 0.0 and self._res.enabled:
            now = time.monotonic()
            slack = None
            for r in self._live + self._waiting:
                if r.t_deadline is not None:
                    s = r.t_deadline - now
                    slack = s if slack is None else min(slack, s)
            if slack is not None:
                if slack <= 0:
                    return 1  # past due: expiry owns it next tick
                cap = min(cap, int(slack * cf.deadline_slack_frac
                                   / self._step_ewma))
        return max(cap, 1)

    def _continuous_tick(self, decodes, prefills, budget) -> Optional[bool]:
        """The overlapped tick: dispatch the fused K-step wave(s) async,
        spend the overlap window on host-side work (inbox drain, admission
        of newly feasible requests, prefill chunks up to the prefill
        budget) while the program runs on device, THEN harvest the fused
        fetch, and finish with a per-token pass for whatever the wave
        could not own. Returns None when no wave formed — the caller's
        per-token tick owns the pass (including eviction)."""
        eligible = [r for r in decodes if self._fusable(r)]
        spec_rows = [r for r in decodes if self._prefilled(r)
                     and self._spec_fusable(r)]
        if not eligible and not spec_rows:
            return None
        cap = self._adaptive_window()
        if self._obs is not None:
            self._obs.adaptive_k.set(cap)
        if cap < 2:
            return None
        t0 = time.monotonic()
        wave = self._fused_begin(eligible, cap) if eligible else None
        swaves = self._fused_spec_begin(spec_rows, cap) if spec_rows else []
        if wave is None and not swaves:
            return None
        protected = set()
        if wave is not None:
            protected.update(r.uid for r in wave[0])
        for sw in swaves:
            protected.update(r.uid for r in sw[0])
        self._in_flight = frozenset(protected)
        n_steps = 0
        try:
            # a wrapper of other scopes: ring only (tracing.py's leaf rule)
            with self._tracer.scope("ds.tick.overlap_fill", annotate=False):
                fed = self._overlap_fill(budget)
            if fed:
                self._trace["prefill_overlap_tokens"] += fed
                if self._obs is not None:
                    self._obs.prefill_overlap.inc(fed)
        finally:
            # harvest EVEN IF the overlap work raised (a put fault rides
            # the tick retry boundary): an unharvested wave would leave
            # seq bookkeeping advanced with its tokens lost
            advanced = []
            if wave is not None:
                advanced += self._fused_harvest(wave)
                n_steps = max(n_steps, wave[2])
            for sw in swaves:
                advanced += self._fused_spec_harvest(sw)
                n_steps = max(n_steps, sw[1])
            self._in_flight = frozenset()
        if n_steps:
            per_step = (time.monotonic() - t0) / n_steps
            self._step_ewma = (per_step if self._step_ewma == 0.0
                               else 0.7 * self._step_ewma + 0.3 * per_step)
        self._retire_finished()
        # remainder pass: per-token tick for live decodes the wave didn't
        # advance (spec-ineligible rows, unprefilled admits, near-budget
        # stragglers) and any prefill still pending after the overlap —
        # rebuilt from the live set so overlap-window admissions ride this
        # same tick
        adv_ids = {id(r) for r in advanced}
        rem_decodes = [r for r in self._live
                       if r.pending == 1 and id(r) not in adv_ids
                       and r.uid not in self._on_prefill]
        rem_prefills = [r for r in self._live if r.pending > 1
                        and r.uid not in self._on_prefill]
        if rem_decodes or rem_prefills:
            self._per_token_tick(rem_decodes, rem_prefills, budget)
        return True

    def _overlap_fill(self, budget) -> int:
        """Host-side work done WHILE the fused wave runs on device: drain
        the inbox, admit newly feasible arrivals, and feed prefill chunks
        up to ``prefill_budget_frac`` of the token budget. The wave's KV
        is untouchable by construction — all its blocks were allocated at
        dispatch — and _tick_put's eviction fence keeps wave members out
        of the victim choice. Returns the prefill tokens fed."""
        with self._lock:
            if self._inbox:
                self._waiting.extend(self._inbox)
                self._inbox = []
        if self._waiting:
            self._admit()
        overlap_fed = 0
        if self._disagg is not None:
            # the prefill GROUP's put runs here so the host-side wait on
            # its logits overlaps the decode group's in-flight wave — the
            # space analog of the time overlap below
            overlap_fed += self._disagg_fill(budget)
        p_budget = int(budget * self._cf.prefill_budget_frac)
        if p_budget <= 0:
            return overlap_fed
        cands = [req for req in self._live
                 if not (req.uid in self._in_flight or req.pending <= 1
                         or req.uid in self._on_prefill)]
        p_reqs, p_chunks, spent = [], [], 0
        for req, take in self._fair_takes(cands, p_budget):
            p_reqs.append(req)
            p_chunks.append(req.feed_slice(take))
            spent += take
        if not p_reqs:
            return overlap_fed
        ctx = self._ctx_tokens(p_reqs)
        t0 = time.monotonic()
        if self._tick_put(p_reqs, p_chunks, {}) is None:
            # eviction fence refused / eviction ended the fill
            return overlap_fed
        if self._obs is not None:
            self._obs.prefill_span([r.uid for r in p_reqs], t0,
                                   time.monotonic(), spent, overlap=True,
                                   ctx_tokens=ctx)
        return overlap_fed + spent

    # ---- disaggregated prefill/decode (disagg.py) ----

    def _disagg_fill(self, budget) -> int:
        """Route-and-feed pass for the PREFILL group: newly admitted
        pending>1 requests with no decode-side history route here (unless
        the router is degraded or the prefill pool cannot hold them), then
        every resident gets a prompt chunk within the token budget — one
        ragged put on the prefill engine per tick. Returns tokens fed."""
        if self._disagg_fed_tick:
            return 0
        self._disagg_fed_tick = True
        ds = self._disagg
        for r in self._live:
            if (r.uid not in self._on_prefill and r.pending > 1
                    and self._engine._state_manager.get_sequence(r.uid)
                    is None
                    and ds.route_to_prefill(r.pending)):
                self._on_prefill.add(r.uid)
                if r.key_burns > 0 and r.outputs:
                    # replayed history: the final chunk SAMPLES on the
                    # prefill engine, so its key chain must stand at the
                    # recorded position too (the decode-side twin of
                    # _restore_sampler)
                    ds.prefill_engine.fast_forward_sampler(
                        r.uid, r.seed, r.key_burns)
        if not self._on_prefill:
            return 0
        reqs, chunks, spent = [], [], 0
        for r in self._live:
            if r.uid not in self._on_prefill or r.pending <= 1:
                continue
            if spent >= budget:
                break
            take = min(r.pending, budget - spent)
            reqs.append(r)
            chunks.append(r.feed_slice(take))
            spent += take
        if not reqs:
            return 0
        t0 = time.monotonic()
        if not self._disagg_put(reqs, chunks):
            return 0
        if self._obs is not None:
            self._obs.prefill_span([r.uid for r in reqs], t0,
                                   time.monotonic(), spent, overlap=True)
        return spent

    def _disagg_put(self, reqs, chunks) -> bool:
        """One ragged put on the prefill engine + handoff submission. The
        sampling mirror of _tick_put's draft-free branch pointed at the
        prefill group: a final prompt chunk's logits row comes from the
        same compiled program over the same weights as an in-group
        prefill's, and the device key chain stands at the same position —
        so the first token is bit-identical to the single-group path."""
        ds = self._disagg
        pe = ds.prefill_engine
        try:
            logits = np.asarray(pe.put([r.uid for r in reqs], chunks))
        except SchedulingError:
            # prefill pool exhausted mid-batch: nothing advanced (fed is
            # untouched) — this batch re-prefills in-group
            for r in list(reqs):
                self._degrade_to_decode(r)
            return False
        device_wave, finals = [], {}
        for req, chunk, row in zip(reqs, chunks, logits):
            req.fed += len(chunk)
            if req.pending == 0:  # feed complete: row is the next token
                # capture the handed-off history BEFORE emission grows it
                finals[id(req)] = np.asarray(req.feed, np.int32)
                if req.speculative is not None and req.temperature != 0.0:
                    new_toks, _ = pe.accept_drafts_sampled(
                        req.uid, [], row, self._spec_for(req),
                        req.num_draft_tokens)
                    req.key_burns += 1  # draft-free window still burns
                    self._trace["decode_tokens"] += self._emit_many(
                        req, new_toks)
                elif self._device_eligible(req):
                    device_wave.append((req, row))
                else:
                    self._emit(req, row)
        if device_wave:
            self._emit_device(device_wave, engine=pe)
        for req in reqs:
            hist = finals.get(id(req))
            if not ds.advance(req.uid, final=hist is not None,
                              tokens=hist):
                # decode pool refused the destination blocks
                self._degrade_to_decode(req)
        return True

    def _disagg_pump(self) -> None:
        """Land every handoff transfer that is ready on the wire, complete
        takeovers (the request joins the decode group: descriptor adopted
        over the landed blocks, prefix blocks registered, device key chain
        fast-forwarded), and degrade wedged handoffs to in-group prefill
        so admission never stalls behind a dead interconnect."""
        ds = self._disagg
        ready, degraded = ds.pump()
        for uid in ready:
            req = self._requests.get(uid)
            if (req is None or uid not in self._on_prefill
                    or req.done.is_set()):
                ds.abort(uid)
                self._on_prefill.discard(uid)
                continue
            if self._finished_already(req):
                # eos/stop/max on the very first token: no decode steps
                # will run — retire without adopting (the _finish hook
                # aborts the handoff and frees both pools)
                if req in self._live:
                    self._live.remove(req)
                self._finish(req, flush=False)
                continue
            ds.takeover(uid)
            self._on_prefill.discard(uid)
            self._restore_sampler(req)  # decode-side chain continues
        for uid in degraded:
            req = self._requests.get(uid)
            if req is not None and uid in self._on_prefill:
                self._degrade_to_decode(req, aborted=True)
            else:
                self._on_prefill.discard(uid)
        ds.refresh_occupancy(
            len(self._on_prefill),
            sum(1 for r in self._live if r.uid not in self._on_prefill))

    def _degrade_to_decode(self, req: _Request, aborted: bool = False
                           ) -> None:
        """Move a prefill-group resident back in-group, eviction-style:
        drop its prefill seq + handoff state and re-feed its WHOLE history
        on the decode group (the replay machinery — already-emitted tokens
        never re-emit, and _restore_sampler lands the key chain at its
        recorded position, so the stream continues bit-identically)."""
        self._on_prefill.discard(req.uid)
        if not aborted:
            self._disagg.abort(req.uid)
        if self._finished_already(req):
            # sampled its last token on the prefill group already; nothing
            # left to re-prefill for
            if req in self._live:
                self._live.remove(req)
            self._finish(req, flush=False)
            return
        req.fed = 0
        self._restore_sampler(req)

    def _per_token_tick(self, decodes, prefills, budget) -> bool:
        """The per-token SplitFuse pass: one ragged forward covering every
        decode's reserved token, host-path drafts, and prefill chunks in
        the spare budget."""
        if self._disagg is not None:
            # no overlap window fed the prefill group this tick (wave-less
            # pass, or the quarantine bisect re-entered): feed it here —
            # routing newly admitted pending>1 requests in the process —
            # then keep its residents out of the in-group lists
            self._disagg_fill(budget)
            if self._on_prefill:
                decodes = [r for r in decodes
                           if r.uid not in self._on_prefill]
                prefills = [r for r in prefills
                            if r.uid not in self._on_prefill]
        with self._tracer.scope("ds.tick.assemble",
                                rows=len(decodes) + len(prefills)):
            # decode SLA: every decoding sequence's 1 token is RESERVED before
            # drafts or prefill chunks may spend anything (generate() reserves
            # identically: draft_budget = max_batch - len(live))
            if len(decodes) > budget:
                # only an oversubscribed tick rations decode slots — and then
                # by weighted-fair queueing order, not arrival order
                decodes = self._fair_decode_order(decodes)
            reserve = min(len(decodes), budget)
            spare = budget - reserve
            d_reqs, d_chunks, drafted = [], [], {}
            for req in decodes[:reserve]:
                chunk = req.feed_slice(1)
                if req.speculative and spare > 0 and req.outputs:
                    seq = self._engine._state_manager.get_sequence(req.uid)
                    room = min(req.num_draft_tokens, spare,
                               self._max_context - seq.seen_tokens - 2,
                               req.max_new_tokens - len(req.outputs) - 1)
                    d = InferenceEngineV2.prompt_lookup_draft(
                        req.prompt + req.outputs,
                        draft_ngram=req.draft_ngram, max_tokens=room,
                        match_window=self._engine.spec_ring_window(
                            req.num_draft_tokens),
                        match_cache=req.match_cache)
                    if d:
                        drafted[req.uid] = d
                        chunk = chunk + d
                        spare -= len(d)
                d_reqs.append(req)
                d_chunks.append(chunk)
            p_reqs, p_chunks = [], []
            for req, take in self._fair_takes(prefills, max(0, spare)):
                p_reqs.append(req)
                p_chunks.append(req.feed_slice(take))
                spare -= take
        if not d_reqs and not p_reqs:
            return False
        ctx = self._ctx_tokens(p_reqs)
        t_put = time.monotonic()
        if drafted and p_reqs:
            # a prefill chunk inside a window-logits put would materialize
            # [S, chunk, vocab] logits — issue the windowed decode put and
            # the plain prefill put separately (generate() likewise keeps
            # its admit put apart from its windowed decode put)
            if self._tick_put(d_reqs, d_chunks, drafted) is None:
                return True  # eviction ended the tick; next tick rebuilds
            self._tick_put(p_reqs, p_chunks, {})
        elif drafted:
            self._tick_put(d_reqs, d_chunks, drafted)
        else:
            self._tick_put(d_reqs + p_reqs, d_chunks + p_chunks, {})
        if self._obs is not None and p_reqs:
            self._obs.prefill_span(
                [r.uid for r in p_reqs], t_put, time.monotonic(),
                sum(len(c) for c in p_chunks), ctx_tokens=ctx)
        self._retire_finished()
        return True

    @staticmethod
    def _plain_greedy(r: _Request) -> bool:
        """No sampling, no controls, no logprobs — the original argmax-only
        fused program (and the zero-dispatch host argmax per-token path)."""
        return (r.temperature == 0.0 and not r.return_logprobs
                and r.min_new_tokens == 0 and r.repetition_penalty == 1.0
                and r.logits_processor is None)

    def _device_eligible(self, r: _Request) -> bool:
        """Requests whose sampling/controls run on device (ops/sampling):
        anything except a host ``logits_processor`` callback (host-only by
        construction) or plain greedy (host argmax is already free)."""
        return (self._device_sampling and r.logits_processor is None
                and not self._plain_greedy(r))

    @staticmethod
    def _spec_for(r: _Request) -> "SampleSpec":
        return SampleSpec(
            temperature=r.temperature, top_k=r.top_k, top_p=r.top_p,
            repetition_penalty=r.repetition_penalty,
            eos_token_id=r.eos_token_id,
            block_eos=len(r.outputs) < r.min_new_tokens,
            history=(r.prompt + r.outputs)
            if r.repetition_penalty != 1.0 else None,
            seed=r.seed, want_logprobs=r.return_logprobs,
            n_out=len(r.outputs), min_new=r.min_new_tokens)

    def _fused_tick(self, decodes) -> list:
        """K decode steps for the fusable subset of the given (prefilled,
        device-ownable) decodes in ONE dispatch. An all-greedy wave runs
        the original argmax program; a wave with any sampled/controlled
        request runs the sampled scan program (greedy members are
        temperature-0 rows of the same dispatch — argmax over identical
        logits, so their streams don't change). Returns the list of
        requests the fused dispatch actually advanced — empty when no
        subset can reach a 2-step window or KV pressure refuses the wave
        (the caller's per-token tick owns eviction). The partition means a
        request within one token of its budget rides the per-token path
        alone instead of demoting the whole batch. Token accounting: the
        dispatch feeds each fused request's pending token plus its K-1
        first generations, so ``fed += K`` restores the pending==1 decode
        invariant; requests whose emit was cut short (eos/stop/max) retire
        this tick, exactly the conditions _emit_many cut on."""
        wave = self._fused_begin(decodes, self._fused_window)
        if wave is None:
            return []
        fused = self._fused_harvest(wave)
        self._retire_finished()
        return fused

    def _fused_begin(self, decodes, cap: int):
        """Partition + async dispatch of the plain/sampled fused wave.
        Returns ``(fused_reqs, engine_handle, K, all_greedy, t_dispatch)``,
        or None when no subset reaches a 2-step window or KV pressure
        refuses the wave (the caller's per-token tick owns eviction)."""
        with self._tracer.scope("ds.tick.assemble", rows=len(decodes)):
            fusable_uids, K, _solo = self._engine.fused_partition(
                [r.uid for r in decodes],
                [r.max_new_tokens - len(r.outputs) for r in decodes], cap)
        if K < 2:
            return None
        fusable_set = set(fusable_uids)
        fused = [r for r in decodes if r.uid in fusable_set]
        all_greedy = all(self._plain_greedy(r) for r in fused)
        try:
            if all_greedy:
                h = self._engine.fused_decode_begin(
                    [r.uid for r in fused],
                    [r.feed_slice(1)[0] for r in fused], K)
            else:
                h = self._engine.fused_decode_begin(
                    [r.uid for r in fused],
                    [r.feed_slice(1)[0] for r in fused], K,
                    specs=[self._spec_for(r) for r in fused])
        except SchedulingError:
            return None
        return (fused, h, K, all_greedy, time.monotonic())

    def _fused_harvest(self, wave) -> list:
        """Fetch + emit a dispatched fused wave (retirement is the
        caller's pass — wave members must not flush mid-overlap)."""
        fused, h, K, all_greedy, t0 = wave
        lps = None
        if all_greedy:
            toks = self._engine.fused_decode_harvest(h)
        else:
            toks, lps = self._engine.fused_decode_harvest(h)
            for r in fused:  # the sampled scan splits once per step
                r.key_burns += K
        self._trace["fused_dispatches"] += 1
        self._trace["fused_k_sum"] += K
        wave_tokens = 0
        with self._tracer.scope("ds.tick.emit", rows=len(fused)):
            for i, (req, row) in enumerate(zip(fused, toks)):
                req.fed += K
                emitted = self._emit_many(req, [int(t) for t in row],
                                          lps=[float(l) for l in lps[i]]
                                          if lps is not None else None)
                self._trace["fused_tokens"] += emitted
                self._trace["decode_tokens"] += emitted
                wave_tokens += emitted
                if not self._engine.decode_finished(
                        req.uid, req.outputs, req.max_new_tokens,
                        req.eos_token_id, req.stop):
                    # deferred bookkeeping for requests that decode on
                    # (fused_decode_steps defers like the speculative path);
                    # retiring ones flush in _retire_finished
                    seq = self._engine._state_manager.get_sequence(req.uid)
                    self._engine._register_pending(seq)
                    self._engine._model.maybe_free_kv(seq)
        if self._obs is not None:
            self._obs.fused_dispatches.inc()
            self._obs.fused_tokens.inc(wave_tokens)
            self._obs.wave_span([r.uid for r in fused], t0,
                                time.monotonic(), K, len(fused),
                                "greedy" if all_greedy else "sampled",
                                flops=self._engine._model.last_wave_flops(),
                                ctx_tokens=h.ctx_tokens)
        return fused

    def _spec_fusable(self, r: _Request) -> bool:
        """Speculative rows the device can own end-to-end: drafting from
        the ring buffer, verification, and (for sampled requests) the
        rejection-sampling accept all run inside the fused scan. Host
        ``logits_processor`` callbacks are rejected at submit; a gate-off
        or an over-wide ngram keeps the per-token host path — the parity
        oracle."""
        if r.speculative is None or not self._fused_spec:
            return False
        if r.draft_ngram > self._spec_max_ngram:
            return False
        return r.temperature == 0.0 or self._device_sampling

    def _fused_spec_tick(self, decodes) -> list:
        """K speculative draft/verify windows for the given rows in one
        dispatch per (draft width, ngram) group — the feed geometry
        ``1 + num_draft_tokens`` is a static of the compiled program, so
        heterogeneous widths run as separate waves (one dispatch each;
        workloads are typically homogeneous). Token accounting: the device
        emits between K and K*(1+d) tokens per row; ``fed`` advances by
        the emitted count so the pending==1 decode invariant holds, and
        the accept counters feed the per-request + /health observability."""
        advanced = []
        for sw in self._fused_spec_begin(decodes, self._fused_window):
            advanced.extend(self._fused_spec_harvest(sw))
        self._retire_finished()
        return advanced

    def _fused_spec_begin(self, decodes, cap: int) -> list:
        """Partition + async dispatch of the speculative wave(s), one per
        (draft width, ngram) group. Returns a list of
        ``(fused_reqs, K, engine_handle, all_greedy, t_dispatch)``
        handles — possibly empty under KV pressure (the per-token tick
        owns eviction)."""
        groups = {}
        for r in decodes:
            groups.setdefault((r.num_draft_tokens, r.draft_ngram),
                              []).append(r)
        waves = []
        for (d, ng), rows in groups.items():
            with self._tracer.scope("ds.tick.assemble", rows=len(rows)):
                fusable_uids, K, _solo = self._engine.fused_spec_partition(
                    [r.uid for r in rows],
                    [r.max_new_tokens - len(r.outputs) for r in rows],
                    d, cap)
            if K < 2:
                continue
            fusable_set = set(fusable_uids)
            fused = [r for r in rows if r.uid in fusable_set]
            all_greedy = all(r.temperature == 0.0 for r in fused)
            try:
                h = self._engine.fused_spec_decode_begin(
                    [r.uid for r in fused], [r.feed for r in fused], K,
                    num_draft_tokens=d, draft_ngram=ng,
                    specs=None if all_greedy
                    else [self._spec_for(r) for r in fused])
            except SchedulingError:
                continue  # KV pressure: the per-token tick owns eviction
            waves.append((fused, K, h, all_greedy, time.monotonic()))
        return waves

    def _fused_spec_harvest(self, swave) -> list:
        """Fetch + emit one dispatched speculative wave."""
        fused, K, h, all_greedy, t0 = swave
        toks_lists, drafted, accepted = \
            self._engine.fused_spec_decode_harvest(h)
        if not all_greedy:  # one split per verified window, K windows
            for req in fused:
                req.key_burns += K
        self._trace["fused_dispatches"] += 1
        self._trace["fused_k_sum"] += K
        wave_tokens = wave_dr = wave_ac = 0
        with self._tracer.scope("ds.tick.emit", rows=len(fused)):
            for req, row, dr, ac in zip(fused, toks_lists, drafted,
                                        accepted):
                req.fed += len(row)
                req.drafted += dr
                req.accepted += ac
                self._trace["spec_drafted"] += dr
                self._trace["spec_accepted"] += ac
                wave_dr += dr
                wave_ac += ac
                emitted = self._emit_many(req, row)
                self._trace["fused_tokens"] += emitted
                self._trace["decode_tokens"] += emitted
                wave_tokens += emitted
                if not self._engine.decode_finished(
                        req.uid, req.outputs, req.max_new_tokens,
                        req.eos_token_id, req.stop):
                    # deferred bookkeeping exactly like _fused_tick:
                    # retiring rows flush in _retire_finished instead
                    seq = self._engine._state_manager.get_sequence(req.uid)
                    self._engine._register_pending(seq)
                    self._engine._model.maybe_free_kv(seq)
        if self._obs is not None:
            self._obs.fused_dispatches.inc()
            self._obs.fused_tokens.inc(wave_tokens)
            self._obs.spec_drafted.inc(wave_dr)
            self._obs.spec_accepted.inc(wave_ac)
            self._obs.wave_span([r.uid for r in fused], t0,
                                time.monotonic(), K, len(fused), "spec",
                                drafted=wave_dr, accepted=wave_ac,
                                flops=self._engine._model.last_wave_flops(),
                                ctx_tokens=h.ctx_tokens)
        return fused

    def _tick_put(self, reqs, chunks, drafted) -> Optional[bool]:
        """One ragged put + row processing. Returns None if KV exhaustion
        evicted a sequence (the tick must end: the eviction may have
        invalidated any other pending put group)."""
        use_window = bool(drafted)
        while True:
            try:
                # do_checks stays ON: chunks always fit the ragged limits
                # under the SplitFuse budget, and the feasibility check is
                # what turns KV exhaustion into a catchable SchedulingError
                logits = self._engine.put(
                    [r.uid for r in reqs], chunks,
                    window_logits=use_window,
                    defer_register=(frozenset(drafted)
                                    if use_window else frozenset()))
                with self._tracer.scope("ds.tick.harvest", rows=len(reqs)):
                    logits = np.asarray(logits)  # the host waits here
                break
            except SchedulingError:
                if use_window:
                    # drafts don't justify evicting a healthy sequence:
                    # retry the put draft-free (generate()'s rule)
                    chunks = [c[:1] if r.uid in drafted else c
                              for r, c in zip(reqs, chunks)]
                    drafted, use_window = {}, False
                    continue
                # KV exhausted mid-tick: evict the NEWEST live sequence
                # (generate()'s recovery). A lone sequence held the WHOLE
                # cache when it died, so its replay could never prefill —
                # finish it truncated (generate()'s lone-sequence
                # semantics) instead of requeueing it into a guaranteed
                # admission error discarding the tokens already streamed.
                # EVICTION FENCE: a member of an in-flight fused wave is
                # untouchable — the device program is still writing its KV
                # pages — so the victim is the newest NON-wave sequence;
                # with only wave members live the fill simply yields (the
                # post-harvest pass owns eviction with the fence down).
                # prefill-group residents are fenced like wave members:
                # their decode-pool blocks are mid-handoff (they free via
                # degrade/abort, never via this eviction path)
                vi = next((i for i in range(len(self._live) - 1, -1, -1)
                           if self._live[i].uid not in self._in_flight
                           and self._live[i].uid not in self._on_prefill),
                          None)
                if vi is None:
                    return None
                victim = self._live.pop(vi)
                self._engine.flush(victim.uid)
                victim.fed = 0
                if self._live:
                    self._waiting.insert(0, victim)
                    self._queue_readd(victim)
                elif victim.outputs:
                    self._finish(victim, flush=False)
                else:
                    victim.error = SchedulingError(
                        SchedulingResult.KVCacheLimitExceeded)
                    self._finish(victim, flush=False)
                return None
        device_wave = []  # (req, logits_row) — one batched sample dispatch
        # host-side sampling and the per-row emission it feeds, one span a put
        with self._tracer.scope("ds.tick.sample", rows=len(reqs)):
            for req, chunk, row in zip(reqs, chunks, logits):
                spec_sampled = (req.speculative is not None
                                and req.temperature != 0.0)
                d = drafted.get(req.uid, [])
                if d:
                    if spec_sampled:
                        new_toks, m = self._engine.accept_drafts_sampled(
                            req.uid, d, row, self._spec_for(req),
                            req.num_draft_tokens)
                        req.key_burns += 1  # one split per verified window
                    else:
                        new_toks, m = self._engine.accept_drafts(req.uid, d, row)
                    req.fed += 1 + m
                    req.drafted += len(d)
                    req.accepted += m
                    self._trace["spec_drafted"] += len(d)
                    self._trace["spec_accepted"] += m
                    self._trace["decode_tokens"] += self._emit_many(req,
                                                                    new_toks)
                else:
                    req.fed += len(chunk)
                    if req.pending == 0:  # feed complete: row is the next token
                        last = row[len(chunk) - 1] if use_window else row
                        if spec_sampled:
                            # a draft-free step of a sampled speculative request
                            # still burns its per-WINDOW key (accept with an
                            # empty draft) so the key chain advances once per
                            # step on every path, fused or not
                            new_toks, _ = self._engine.accept_drafts_sampled(
                                req.uid, [], last, self._spec_for(req),
                                req.num_draft_tokens)
                            req.key_burns += 1  # draft-free window still burns
                            self._trace["decode_tokens"] += self._emit_many(
                                req, new_toks)
                        elif self._device_eligible(req):
                            device_wave.append((req, last))
                        else:
                            self._emit(req, last)
                if use_window:
                    # window puts defer the trailing-window KV free for EVERY
                    # sequence in the batch — resume it here
                    seq = self._engine._state_manager.get_sequence(req.uid)
                    if seq is not None:
                        self._engine._model.maybe_free_kv(seq)
        if device_wave:
            with self._tracer.scope("ds.tick.sample", rows=len(device_wave),
                             device=True):
                self._emit_device(device_wave)
        return True

    def _stream_put(self, req: _Request, tok: int) -> None:
        """Token delivery through the (possibly bounded) stream queue. A
        full queue means the consumer stopped draining — a disconnected or
        wedged client — so the request is cancelled instead of buffering
        its remaining decode without bound. The token is still appended to
        ``outputs`` by the caller; only stream delivery is dropped."""
        inj = get_fault_injector()
        if inj.enabled and inj.fire("serve.slow_consumer",
                                    uid=req.uid) is not None:
            req.cancelled = True
            self._trace["slow_consumer_cancels"] += 1
            return
        try:
            req.stream_q.put_nowait(tok)
        except queue.Full:
            req.cancelled = True
            self._trace["slow_consumer_cancels"] += 1
            logger.warning(f"[serving] request {req.uid} cancelled: stream "
                           f"consumer stopped draining "
                           f"({req.stream_q.maxsize} tokens undelivered)")

    def _mark_emit(self, req: _Request) -> None:
        """Timestamp bookkeeping for one about-to-append token: ``t_first``
        on the first (feeding the TTFT histogram unless the request is a
        journal replay, whose submit anchor predates the restart), the
        inter-token gap histogram on every later one."""
        now = time.monotonic()
        obs = self._obs
        if not req.outputs:
            req.t_first = now
            if obs is not None:
                obs.first_token(req.t_submit, now, req.replayed,
                                tenant=req.tenant)
        elif obs is not None and req.t_last > 0.0:
            obs.token_gap(now - req.t_last)
        req.t_last = now
        self._tenant_delivered[req.tenant] = \
            self._tenant_delivered.get(req.tenant, 0) + 1
        if obs is not None:
            obs.tokens.inc()
            obs.decode_tokens.inc()
            obs.tenant_token(req.tenant)
            if req.adapter_id is not None:
                obs.adapter_token(req.adapter_id)

    def _emit_device(self, wave, engine: Optional[InferenceEngineV2] = None
                     ) -> None:
        """ONE batched on-device sampling dispatch for every device-eligible
        row of a per-token tick (engine.sample_rows) — the N sampled
        decodes of a tick cost one host round-trip, not N. ``engine``
        points the dispatch at the prefill group's engine for first
        tokens sampled there (same program, same key chain → same bits)."""
        eng = engine if engine is not None else self._engine
        toks, lps = eng.sample_rows(
            [r.uid for r, _ in wave], [row for _, row in wave],
            [self._spec_for(r) for r, _ in wave])
        for (req, _), tok, lp in zip(wave, toks, lps):
            req.key_burns += 1  # sample_rows splits each row's key once
            if req.return_logprobs:
                req.logprobs.append(float(lp))
            self._mark_emit(req)
            req.outputs.append(int(tok))
            self._trace["decode_tokens"] += 1
            self._stream_put(req, int(tok))

    def _emit(self, req: _Request, logits_row) -> None:
        block_eos = len(req.outputs) < req.min_new_tokens
        if (req.repetition_penalty != 1.0 or block_eos
                or req.logits_processor is not None):
            logits_row = self._engine.process_logits(
                logits_row, req.prompt + req.outputs,
                repetition_penalty=req.repetition_penalty,
                eos_token_id=req.eos_token_id,
                block_eos=block_eos,
                logits_processor=req.logits_processor)
        tok, lp = self._engine._sample_with_logprob(
            logits_row, req.temperature, req.rng, req.top_k, req.top_p,
            want_lp=req.return_logprobs)
        if req.return_logprobs:
            req.logprobs.append(lp)
        self._mark_emit(req)
        req.outputs.append(int(tok))
        self._trace["decode_tokens"] += 1
        self._stream_put(req, int(tok))

    def _emit_many(self, req: _Request, toks, lps=None) -> int:
        """Stream a verified draft run or fused window, applying the
        eos/stop/max cuts so tokens past a cut never surface (generate()'s
        truncation rules; the overshot KV needs no rollback — the request
        retires and flushes). Returns the token count that actually
        surfaced (the occupancy counters' feed)."""
        emitted = 0
        for i, t in enumerate(toks):
            if len(req.outputs) >= req.max_new_tokens:
                break
            self._mark_emit(req)
            if req.return_logprobs:
                req.logprobs.append(float(lps[i]) if lps is not None
                                    else None)
            req.outputs.append(int(t))
            emitted += 1
            self._stream_put(req, int(t))
            if req.eos_token_id is not None and int(t) == req.eos_token_id:
                break
            if req.stop and self._engine.hit_stop(req.outputs, req.stop):
                break
        return emitted

    def _retire_finished(self) -> None:
        with self._tracer.scope("ds.tick.emit"):
            for req in list(self._live):
                if req.uid in self._in_flight:
                    continue  # fused wave in flight: judge/flush after harvest
                if req.uid in self._on_prefill:
                    # prefill-group resident: no decode-side descriptor yet —
                    # an eos-on-first-token finish lands at takeover instead
                    continue
                if not req.outputs or req.pending > 1:
                    continue  # still (re)prefilling — nothing sampled to judge
                if self._engine._state_manager.get_sequence(req.uid) is None:
                    continue  # admitted this tick, nothing fed yet
                if self._engine.decode_finished(req.uid, req.outputs,
                                                req.max_new_tokens,
                                                req.eos_token_id, req.stop):
                    self._live.remove(req)
                    self._finish(req)

    def _finish(self, req: _Request, flush: bool = True) -> None:
        if self._disagg is not None and req.uid in self._on_prefill:
            # prefill-group resident: its engine state is the prefill
            # seq + handoff (no decode-side descriptor to flush)
            self._on_prefill.discard(req.uid)
            self._disagg.abort(req.uid)
            flush = False
        if flush:
            self._engine.flush(req.uid)
        elif req.adapter_id is not None:
            # flush=False paths (queue expiry, replay error-finish) never
            # touched the engine, but the submit/replay pin is real
            reg = getattr(self._engine, "adapters", None)
            if reg is not None:
                reg.unpin(req.uid)
        if (self._journal is not None and not req.journal_skip
                and not self._preserve_journal):
            # crash/handoff keeps entries alive for the next boot's replay;
            # every normal finish (done/cancel/error/expiry) retires them
            try:
                self._journal.record_finish(req.uid)
            except OSError as e:
                logger.warning(f"[journal] finish record failed for "
                               f"request {req.uid}: {e}")
        req.t_done = time.monotonic()
        with self._lock:  # stats()/drain read under the same lock
            self._active -= 1
            if req.queued:  # finished straight out of the waiting queue
                req.queued = False
                self._tq_dec(req)
                self._queued_n -= 1
                self._queued_tokens -= len(req.prompt)
            if req.error is None and not req.cancelled:
                self._completed.append(
                    (req.t_submit, req.t_first, req.t_done,
                     len(req.outputs), req.replayed))
        if self._obs is not None:
            if req.error is None and not req.cancelled:
                outcome = "ok"
            elif req.cancelled:
                outcome = "cancelled"
            elif isinstance(req.error, DeadlineExceeded):
                outcome = "expired"
            else:
                outcome = "error"
            self._obs.request_finished(req.uid, req.t_submit, req.t_done,
                                       outcome, len(req.outputs),
                                       req.replayed, tenant=req.tenant,
                                       adapter=req.adapter_id)
            # keep the last 256 finished requests reconnectable by uid,
            # then let them go so the registry stays bounded
            self._done_order.append(req.uid)
            while len(self._done_order) > 256:
                old = self._done_order.popleft()
                r = self._requests.get(old)
                if r is not None and r.done.is_set():
                    self._requests.pop(old, None)
        req.done.set()
        while True:
            try:
                req.stream_q.put_nowait(_END)
                break
            except queue.Full:
                # bounded stream of a dead consumer: drop its oldest
                # undelivered token so the sentinel always lands
                try:
                    req.stream_q.get_nowait()
                except queue.Empty:
                    pass


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------


def create_http_server(scheduler: ServingScheduler, host: str = "127.0.0.1",
                       port: int = 8000, tokenizer=None) -> ThreadingHTTPServer:
    """ThreadingHTTPServer over a running scheduler.

    POST /generate body (JSON):
      {"prompt": [ids]} or {"text": "..."} (requires tokenizer),
      optional max_new_tokens / temperature / top_k / top_p / eos_token_id /
      seed / stream. ``stream: true`` answers chunked, one JSON line per
      token; otherwise one JSON object with the full output.
    GET /health: scheduler stats.
    Observability (404 with the ``observability`` config block disabled):
      GET /metrics — Prometheus text exposition of the process registry;
      GET /requests/<uid>/trace — the request's span timeline as JSON;
      GET /debug/trace?last=N — recent waves + live timelines as Chrome
      ``trace_event`` JSON (Perfetto-loadable);
      POST /debug/profile — start a bounded jax.profiler capture
      (409 while one runs); POST /debug/profile/stop — end it early.
    """

    class Handler(BaseHTTPRequestHandler):
        # chunked Transfer-Encoding is an HTTP/1.1 construct; the default
        # HTTP/1.0 status line would make real clients mis-parse streams
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):  # quiet by default
            pass

        def _json(self, code: int, obj, headers=()) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in headers:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/health":
                # readiness vs liveness: "draining" (stop(drain=True) in
                # progress) and "degraded" (watchdog saw a stuck tick)
                # answer 503 so load balancers stop routing here, while
                # the payload still carries the full stats for operators
                stats = scheduler.stats
                if stats["migrating"]:
                    # checked before "stopped": an export stops the loop,
                    # but the router must see a handoff in progress (with
                    # journal_export_depth), not a plain shutdown
                    status = "migrating"
                elif stats["stopped"]:
                    status = "stopped"
                elif stats["draining"]:
                    status = "draining"
                elif stats["degraded"]:
                    status = "degraded"
                else:
                    status = "ok"
                self._json(200 if status == "ok" else 503,
                           {"status": status, **stats})
            elif self.path == "/metrics":
                obs = scheduler.observability
                if obs is None:
                    self._json(404, {"error": "observability disabled"})
                    return
                body = obs.registry.render_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path.startswith("/debug/trace"):
                obs = scheduler.observability
                if obs is None:
                    self._json(404, {"error": "observability disabled"})
                    return
                from urllib.parse import parse_qs, urlparse
                q = parse_qs(urlparse(self.path).query)
                try:
                    last = int(q.get("last", ["0"])[0]) or None
                except ValueError:
                    self._json(400, {"error": "bad last"})
                    return
                self._json(200, obs.tracer.chrome_trace(last))
            elif self.path == "/journal/export":
                # migration drain: hand every unfinished journal entry to
                # the caller (the fleet router) as the WAL's own portable
                # CRC-frame stream; this replica stops serving first
                try:
                    frames = scheduler.export_journal()
                except RuntimeError as e:
                    self._json(409, {"error": str(e)})
                    return
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(len(frames)))
                self.send_header("X-DS-Journal-Depth",
                                 str(scheduler.stats["journal_export_depth"]))
                self.end_headers()
                self.wfile.write(frames)
            elif self.path.startswith("/requests/"):
                self._do_request_get()
            else:
                self._json(404, {"error": "not found"})

        def _do_request_get(self):
            """Reconnect surface: ``GET /requests/<uid>`` blocks for the
            full result (a non-streaming wait re-attach);
            ``GET /requests/<uid>/stream?from_token=N`` resumes a chunked
            token stream at the client's own high-water mark. Both work
            across a daemon warm restart (replay keeps original uids)."""
            from urllib.parse import parse_qs, urlparse
            parsed = urlparse(self.path)
            parts = [p for p in parsed.path.split("/") if p]
            try:
                uid = int(parts[1])
            except (IndexError, ValueError):
                self._json(400, {"error": "bad request id"})
                return
            if len(parts) > 2 and parts[2] == "trace":
                # post-hoc reconstruction: the span timeline survives the
                # request itself (bounded ring), so no live handle needed
                tl = scheduler.trace_timeline(uid)
                if tl is None:
                    self._json(404, {"error": f"no trace for request {uid}"})
                    return
                self._json(200, tl)
                return
            handle = scheduler.lookup(uid)
            if handle is None:
                self._json(404, {"error": f"unknown request {uid}"})
                return
            if len(parts) > 2 and parts[2] == "stream":
                try:
                    from_token = int(
                        parse_qs(parsed.query).get("from_token", ["0"])[0])
                except ValueError:
                    self._json(400, {"error": "bad from_token"})
                    return
                self.send_response(200)
                self.send_header("Content-Type", "application/jsonl")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("X-DS-Request-Id", str(uid))
                self.end_headers()
                try:
                    for tok in handle.stream_from(
                            from_token,
                            timeout=scheduler.wait_timeout(handle)):
                        line = json.dumps({"token": tok}).encode() + b"\n"
                        self.wfile.write(hex(len(line))[2:].encode()
                                         + b"\r\n" + line + b"\r\n")
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    pass  # reconnectors never cancel the request
                except Exception:  # noqa: BLE001 — timeout/req error: the
                    try:           # streamed tokens stand, end chunking
                        self.wfile.write(b"0\r\n\r\n")
                    except OSError:
                        pass
                return
            try:
                tokens = handle.result(
                    timeout=scheduler.wait_timeout(handle))
            except DeadlineExceeded as e:
                self._json(504, {"error": str(e)})
                return
            except TimeoutError:
                self._json(504, {"error": f"request {uid} did not "
                                          "complete in time"})
                return
            except Exception as e:  # noqa: BLE001 — surfaced to client
                self._json(500, {"error": str(e)})
                return
            self._json(200, {"uid": uid, "tokens": tokens})

        def _do_profile(self):
            """``POST /debug/profile`` starts a bounded ``jax.profiler``
            capture (body: optional ``{"seconds": N, "dir": ...}``); a
            second start while one runs answers 409. ``/stop`` ends a
            capture early (the auto-stop timer otherwise does)."""
            obs = scheduler.observability
            if obs is None:
                self._json(404, {"error": "observability disabled"})
                return
            if self.path.endswith("/stop"):
                info = obs.profiler.stop()
                if info is None:
                    self._json(200, {"status": "idle"})
                else:
                    self._json(200, {"status": "stopped", **info})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                seconds = body.get("seconds")
                seconds = float(seconds) if seconds is not None else None
                directory = body.get("dir")
            except (ValueError, TypeError):
                self._json(400, {"error": "bad profile request body"})
                return
            try:
                info = obs.profiler.start(seconds, directory)
            except ProfilerBusy as e:
                self._json(409, {"error": str(e)})
                return
            except Exception as e:  # noqa: BLE001 — profiler backends vary
                self._json(500, {"error": f"profiler start failed: {e}"})
                return
            self._json(200, {"status": "started", **info})

        def _do_adapters(self):
            """``POST /adapters/load`` (``{"path": dir, "name": ...}``) and
            ``POST /adapters/unload`` (``{"adapter": name_or_id}``) — the
            hot-swap surface: factors land in (or leave) the running bank
            via value-only slot writes, so the daemon never restarts and
            the fused programs never recompile."""
            reg = getattr(scheduler.engine, "adapters", None)
            if reg is None:
                self._json(404, {"error": "adapters disabled "
                                          "(adapters.enabled is off)",
                                 "reason": "adapters_disabled"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
            except ValueError:
                self._json(400, {"error": "bad JSON body"})
                return
            try:
                if self.path == "/adapters/load":
                    path = body.get("path")
                    if not path:
                        raise ValueError("missing 'path' (adapter "
                                         "checkpoint dir)")
                    aid = reg.load(str(path), name=body.get("name"))
                    self._json(200, {"status": "loaded", "adapter": aid})
                else:
                    target = body.get("adapter") or body.get("name")
                    if not target:
                        raise ValueError("missing 'adapter' (name or "
                                         "name@version)")
                    aid = reg.unload(str(target))
                    self._json(200, {"status": "unloaded", "adapter": aid})
            except KeyError as e:
                self._json(400, {"error": str(e),
                                 "reason": "unknown_adapter"})
            except ValueError as e:
                err = {"error": str(e)}
                reason = error_reason(e)
                err["reason"] = reason or "bad_adapter"
                self._json(400, err)
            except OSError as e:
                self._json(400, {"error": f"adapter load failed: {e}",
                                 "reason": "adapter_io_error"})

        def do_POST(self):
            if self.path in ("/adapters/load", "/adapters/unload"):
                self._do_adapters()
                return
            if self.path in ("/debug/profile", "/debug/profile/stop"):
                self._do_profile()
                return
            if self.path == "/journal/import":
                # migration adopt: the body is a peer's exported frame
                # stream; unfinished requests re-admit here mid-run with
                # their original uids and byte-identical continuations
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    result = scheduler.import_journal_frames(
                        self.rfile.read(n))
                except RuntimeError as e:
                    self._json(409, {"error": str(e)})
                    return
                self._json(200, {"status": "imported", **result})
                return
            if self.path not in ("/generate", "/v1/completions",
                                 "/v1/chat/completions"):
                self._json(404, {"error": "not found"})
                return
            chat = self.path == "/v1/chat/completions"
            openai = chat or self.path == "/v1/completions"
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if openai:
                    # OpenAI completions field names -> native ones
                    if "max_tokens" in body:
                        body.setdefault("max_new_tokens", body["max_tokens"])
                    if isinstance(body.get("prompt"), str):
                        body.setdefault("text", body.pop("prompt"))
                if chat:
                    if body.get("stream"):
                        raise UnsupportedFeature(
                            "streaming chat completions are not supported; "
                            "use /generate with stream for token streaming",
                            reason="streaming_chat_unsupported")
                    msgs = body.get("messages")
                    if not msgs:
                        raise ValueError("chat completions need 'messages'")
                    if tokenizer is None or not hasattr(
                            tokenizer, "apply_chat_template"):
                        raise UnsupportedFeature(
                            "chat completions need a tokenizer with a chat "
                            "template", reason="chat_template_unavailable")
                    try:
                        body["prompt"] = tokenizer.apply_chat_template(
                            msgs, add_generation_prompt=True)
                    except Exception as e:  # noqa: BLE001 — template errors
                        raise ValueError(f"malformed messages: {e}") from e
                prompt = body.get("prompt")
                if prompt is None and "text" in body:
                    if tokenizer is None:
                        raise ValueError("text input needs a tokenizer; "
                                         "pass token ids as 'prompt'")
                    prompt = tokenizer.encode(body["text"])
                if not prompt:
                    raise ValueError("missing 'prompt' (token ids) or 'text'")
                stop = body.get("stop")
                if isinstance(stop, str):
                    stop = [stop]
                if stop and any(isinstance(s, str) for s in stop):
                    if tokenizer is None:
                        raise ValueError("string stop sequences need a "
                                         "tokenizer; pass token ids")
                    from .pipeline import _encode_stop
                    stop = [_encode_stop(tokenizer, s)
                            if isinstance(s, str) else s for s in stop]
                handle = scheduler.submit(
                    prompt,
                    max_new_tokens=int(body.get("max_new_tokens", 32)),
                    temperature=float(body.get("temperature", 0.0)),
                    top_k=int(body.get("top_k", 0)),
                    top_p=float(body.get("top_p", 1.0)),
                    eos_token_id=body.get("eos_token_id"),
                    seed=int(body.get("seed", 0)),
                    stop=stop,
                    min_new_tokens=int(body.get("min_new_tokens", 0)),
                    repetition_penalty=float(
                        body.get("repetition_penalty", 1.0)),
                    speculative=body.get("speculative"),
                    num_draft_tokens=int(body.get("num_draft_tokens", 4)),
                    draft_ngram=int(body.get("draft_ngram", 2)),
                    return_logprobs=bool(body.get("logprobs")),
                    deadline_s=body.get("deadline_s"),
                    queue_ttl_s=body.get("queue_ttl_s"),
                    stream=bool(body.get("stream")),
                    tenant=body.get("tenant"),
                    adapter=body.get("adapter"))
            except SchedulerOverloaded as e:
                self._json(429, {"error": str(e),
                                 "retry_after_s": e.retry_after_s},
                           headers=(("Retry-After",
                                     str(max(1, round(e.retry_after_s)))), ))
                return
            except (ValueError, SchedulingError) as e:
                err = {"error": str(e)}
                reason = error_reason(e)
                if reason:  # machine-readable slug: clients branch on it
                    err["reason"] = reason
                self._json(400, err)
                return
            except RuntimeError as e:
                # stopped / draining / migrating: this replica no longer
                # admits — tell the client (or the router) to go elsewhere
                self._json(503, {"error": str(e)},
                           headers=(("Retry-After", "1"), ))
                return
            if body.get("stream"):
                self.send_response(200)
                self.send_header("Content-Type", "application/jsonl")
                self.send_header("Transfer-Encoding", "chunked")
                # the reconnect key: a dropped client re-attaches at
                # GET /requests/<uid>/stream?from_token=<tokens seen>
                self.send_header("X-DS-Request-Id", str(handle.uid))
                self.end_headers()
                try:
                    for tok in handle.stream(
                            timeout=scheduler.wait_timeout(handle)):
                        line = json.dumps({"token": tok}).encode() + b"\n"
                        self.wfile.write(hex(len(line))[2:].encode()
                                         + b"\r\n" + line + b"\r\n")
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    handle.cancel()
                except (DeadlineExceeded, queue.Empty):
                    # deadline hit mid-stream / scheduler wedged: the
                    # tokens already streamed stand — end the chunk stream
                    # cleanly so the client sees a complete HTTP response
                    handle.cancel()
                    try:
                        self.wfile.write(b"0\r\n\r\n")
                    except OSError:
                        pass
                return
            try:
                # pinned to the request deadline (or http_timeout_s): a
                # hung scheduler answers 504 instead of pinning this HTTP
                # thread forever
                tokens = handle.result(
                    timeout=scheduler.wait_timeout(handle))
            except DeadlineExceeded as e:
                self._json(504, {"error": str(e)})
                return
            except TimeoutError:
                handle.cancel()
                self._json(504, {"error": f"request {handle.uid} did not "
                                          "complete in time"})
                return
            except Exception as e:  # noqa: BLE001 — surfaced to the client
                self._json(500, {"error": str(e)})
                return
            text = tokenizer.decode(tokens) if tokenizer is not None else None
            if openai:
                # OpenAI completions / chat-completions response shapes
                finish = ("length" if len(tokens)
                          >= int(body.get("max_new_tokens", 32)) else "stop")
                choice = {"index": 0, "tokens": tokens,
                          "finish_reason": finish}
                if chat:
                    choice["message"] = {"role": "assistant",
                                         "content": text or ""}
                else:
                    choice["text"] = text if text is not None else ""
                self._json(200, {
                    "id": f"ds-{handle.uid}",
                    "object": ("chat.completion" if chat
                               else "text_completion"),
                    "choices": [choice],
                    "usage": {"completion_tokens": len(tokens)}})
                return
            out = {"uid": handle.uid, "tokens": tokens}
            if body.get("speculative"):
                out["spec"] = handle.stats  # drafted/accepted/accept_rate
            if body.get("logprobs"):
                out["logprobs"] = handle.result_with_logprobs()[1]
            if text is not None:
                out["text"] = text
            self._json(200, out)

    return ThreadingHTTPServer((host, port), Handler)


def install_sigterm_handoff(sched: ServingScheduler, httpd) -> bool:
    """SIGTERM → journal checkpoint + clean handoff: the handler stops the
    scheduler WITHOUT retiring journal entries (``handoff()``) and shuts
    the HTTP server down, so a supervisor relaunch replays every in-flight
    request. Signal handlers only install from the main thread; returns
    whether the handler is in place."""
    import signal
    if threading.current_thread() is not threading.main_thread():
        return False

    def _on_term(signum, frame):
        logger.warning("[serving] SIGTERM: journal handoff + shutdown")
        # shutdown() blocks until serve_forever exits — which runs on THIS
        # thread when blocking — so it must be called from another one
        threading.Thread(target=httpd.shutdown, daemon=True).start()
        sched.handoff()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # non-main interpreter contexts
        return False
    return True


def serve(engine: InferenceEngineV2, host: str = "127.0.0.1", port: int = 8000,
          tokenizer=None, block: bool = True,
          fused_decode_window: Optional[int] = None,
          disagg: Optional[DisaggServing] = None):
    """One-call deployment: start the scheduler + HTTP server (mii.serve)."""
    sched = ServingScheduler(
        engine, fused_decode_window=fused_decode_window,
        disagg=disagg).start()
    httpd = create_http_server(sched, host, port, tokenizer)
    install_sigterm_handoff(sched, httpd)
    if not block:
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        return sched, httpd
    try:
        httpd.serve_forever()
    finally:
        sched.stop()
    return sched, httpd
