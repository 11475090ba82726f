"""InferenceEngineV2 — ragged continuous-batching serving engine.

Reference: ``deepspeed/inference/v2/engine_v2.py:30 InferenceEngineV2``.
Same contract: ``put(uids, tokens)`` runs one ragged forward returning one
logits row per sequence; ``query``/``can_schedule`` expose the Dynamic
SplitFuse feasibility math to the scheduler (MII-equivalent); ``flush``
drops a sequence's KV. TPU-side, a forward is one jitted program per shape
bucket (see ragged_wrapper) and the KV cache is donated functional state.
"""

import os
import pickle
import time
from dataclasses import dataclass
from typing import Iterable, List, Optional, Tuple

import numpy as np
import jax

from ...models.llama import LlamaConfig, init_llama
from ...observability import get_registry, get_tracer
from ...utils.fault_injection import InjectedFault, get_fault_injector
from .config_v2 import RaggedInferenceEngineConfig
from .model import RaggedLlamaModel
from .ragged.ragged_manager import DSStateManager
from .ragged.ragged_wrapper import RaggedBatchWrapper
from .ragged.sequence_descriptor import PlaceholderSequenceDescriptor
from .scheduling_utils import SchedulingError, SchedulingResult

# Host-boundary timings (process registry, resolved once at import): the
# engine never timestamps device-side work — ``dispatch`` is the async
# enqueue half of a fused wave, ``harvest`` the blocking device_get, and
# ``put`` one whole ragged forward including its fetch.
_obs = get_registry()
_put_seconds = _obs.histogram(
    "ds_engine_put_seconds", "One ragged forward (put), dispatch + fetch")
_dispatch_seconds = _obs.histogram(
    "ds_engine_dispatch_seconds",
    "Async enqueue of a fused wave (begin half, no fetch)")
_harvest_seconds = _obs.histogram(
    "ds_engine_harvest_seconds",
    "Blocking fetch of a dispatched fused wave (device_get)")
_dispatches_total = _obs.counter(
    "ds_engine_dispatches_total", "Fused wave dispatches (plain + spec)")
_harvests_total = _obs.counter(
    "ds_engine_harvests_total", "Fused wave harvests (plain + spec)")
# prefix-cache effectiveness, previously visible only as host-side
# descriptor attrs: one hit per new sequence that adopted a cached
# prefix, plus the block count it skipped recomputing
_prefix_hits = _obs.counter(
    "ds_prefix_cache_hits_total",
    "New sequences that adopted a cached full-block prefix")
_prefix_adopted_blocks = _obs.counter(
    "ds_prefix_adopted_blocks_total",
    "KV blocks adopted from the prefix cache (prefill skipped)")
_prefix_saved_tokens = _obs.counter(
    "ds_prefix_saved_prefill_tokens_total",
    "Prompt tokens kept out of prefill by prefix adoption + COW forks "
    "(mirrors PrefixKVCache.stats['saved_tokens'] exactly)")
_prefix_cow_forks = _obs.counter(
    "ds_prefix_cow_forks_total",
    "Mid-block prompt divergences resolved by a copy-on-write block fork")


@dataclass
class SampleSpec:
    """Per-sequence sampling parameters for the ON-DEVICE sampler
    (ops/sampling) — the host-side description one row of a batched
    ``sample_rows`` dispatch or one lane of a sampled fused-decode scan is
    built from. ``history`` (prompt + outputs) is only consulted when
    ``repetition_penalty != 1`` (it becomes the [vocab] presence mask);
    ``block_eos`` is the per-token path's precomputed min_new gate, while
    the fused path derives it in-trace from ``n_out``/``min_new`` per scan
    step. ``seed`` initializes the sequence's PRNG key on first use."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: Optional[int] = None
    block_eos: bool = False
    history: Optional[List[int]] = None
    seed: int = 0
    want_logprobs: bool = False
    n_out: int = 0
    min_new: int = 0


@dataclass
class _InFlightWave:
    """A dispatched-but-unfetched fused decode program: the device is
    running (or queued to run) the K-step scan while the scheduler's
    overlap window feeds prefill chunks; ``out``/``lps``/``new_keys`` are
    lazy jax arrays until :meth:`InferenceEngineV2.fused_decode_harvest`
    blocks on them. Sequences' host bookkeeping was already advanced at
    dispatch (plain waves grow deterministically by ``n_steps``)."""
    uids: list
    seqs: list
    tokens: "np.ndarray"   # [S] input tokens (padded row order)
    out: object            # lazy [n_steps, S] device tokens
    lps: object            # lazy [n_steps, S] logprobs (sampled waves)
    new_keys: object       # lazy [S, 2] advanced PRNG keys (sampled waves)
    n_steps: int
    sampled: bool
    ctx_tokens: int = 0    # sum of the rows' context lengths at dispatch


@dataclass
class _InFlightSpecWave:
    """Speculative sibling of :class:`_InFlightWave`. Host bookkeeping is
    wholly deferred to harvest — how far each sequence advanced is itself
    a device result (the accepted counts)."""
    uids: list
    seqs: list
    tokens: "np.ndarray"
    out: object            # lazy [n_steps, S, 1+d] emitted tokens
    n_emit: object         # lazy [n_steps, S] per-window emit counts
    dlen: object           # lazy [n_steps, S] per-window draft lengths
    new_keys: object       # lazy [S, 2] advanced keys (None when greedy)
    n_steps: int
    ctx_tokens: int = 0    # sum of the rows' context lengths at dispatch


_FF_KEY = None


def _fast_forward_key(key, n: int):
    """Advance a PRNG key by ``n`` chain burns: iterated ``split(key, 2)[0]``
    — the exact per-dispatch advance of ``sample_core`` and
    ``spec_verify_window``. Jitted once (dynamic trip count) so replaying a
    long stream costs one dispatch, not ``n``."""
    global _FF_KEY
    if _FF_KEY is None:
        _FF_KEY = jax.jit(lambda k, m: jax.lax.fori_loop(
            0, m, lambda i, kk: jax.random.split(kk, 2)[0], k))
    return _FF_KEY(key, np.int32(n))


def _fire_request_poison(uids) -> None:
    """``serve.request_poison`` fault site: a configured request uid makes
    ANY device dispatch whose batch contains it raise — per-token put,
    windowed verify, and fused scan alike — the deterministic stand-in for
    "this request's shape/content wedges the engine". The raise happens
    before any engine state mutates, so co-batched sequences stay intact.
    Inert (not even visit-counted) unless a fault plan is installed."""
    inj = get_fault_injector()
    if not inj.enabled:
        return
    uids = list(uids)
    args = inj.fire("serve.request_poison", uids=uids)
    if args is not None:
        uid = args.get("uid")
        if uid is None or uid in uids:
            raise InjectedFault(f"injected poison in request {uid}")


class InferenceEngineV2:

    def __init__(self, model: RaggedLlamaModel, engine_config: RaggedInferenceEngineConfig):
        self._config = engine_config
        self._model = model
        # the serving programs persist where the trainer's do
        from ...runtime.compiler import configure_compile_cache
        configure_compile_cache()

        kv_config = model.kv_cache_config()
        self._batch = RaggedBatchWrapper(engine_config.state_manager,
                                         block_size=kv_config.block_size)
        prefix_caching = engine_config.enable_prefix_caching
        self._prefix_disable_reason = None if prefix_caching else "not_enabled"
        if prefix_caching and getattr(model.config, "sliding_window", None):
            from ...utils.logging import logger
            logger.warning("prefix caching disabled: sliding-window models "
                           "release trailing KV blocks mid-sequence, which "
                           "would free shared prefix blocks")
            prefix_caching = False
            self._prefix_disable_reason = "sliding_window_model"
        self._state_manager = DSStateManager(engine_config.state_manager, kv_config,
                                             num_blocks=engine_config.num_kv_blocks,
                                             enable_prefix_caching=prefix_caching)
        self._model.set_state_manager(self._state_manager)
        # per-sequence PRNG key state for the on-device sampler — lives
        # next to the KV cache in lifecycle terms (seeded lazily at first
        # sample, advanced one split per generated token, dropped on
        # flush). Kept as host uint32[2] rows; each dispatch carries the
        # batch's keys in and the advanced keys out.
        self._sample_keys = {}
        # Multi-LoRA: the adapter registry attached to the model (None =
        # adapter-free engine). Slot assignment is per-uid and lives in the
        # registry's pin table; KV and scheduling accounting never see it.
        self._adapters = getattr(model, "_adapters", None)
        # the span API's tracer (observability/tracing.py) for this engine's
        # halves of a tick: the process-wide one until a ServingScheduler
        # hands over its own (NO_TRACER with its observability off)
        self.tracer = get_tracer()

    # ---- multi-LoRA (inference/v2/adapters) ----

    @property
    def adapters(self):
        """The attached :class:`AdapterRegistry`, or None."""
        return self._adapters

    def set_request_adapter(self, uid: int, name_or_id: str) -> int:
        """Pin ``uid`` to an adapter for its lifetime (resolve + device
        slot + pin; released by :meth:`flush`). Returns the slot. Raises
        KeyError (unknown adapter) or AdapterSlotsExhausted."""
        if self._adapters is None:
            raise RuntimeError("engine built without an adapter registry "
                               "(adapters.enabled is off)")
        return self._adapters.pin(uid, name_or_id)

    def _adapter_slot_rows(self, batch_uids, n_rows: int):
        """Bucketed per-row slot array for one dispatch (None when the
        engine is adapter-free — the model then omits the bank operand).
        Padding rows carry slot 0: the identity adapter's zero factors
        make them an exact no-op."""
        if self._adapters is None:
            return None
        slots = np.zeros(n_rows, np.int32)
        for i, u in enumerate(batch_uids):
            slots[i] = self._adapters.slot_for_uid(u)
        return slots

    # ---- properties (reference engine_v2.py:47-66) ----

    @property
    def free_blocks(self) -> int:
        return self._state_manager.free_blocks

    @property
    def n_kv_cache_groups(self) -> int:
        return 1

    def model(self) -> RaggedLlamaModel:
        return self._model

    def prefix_cache_report(self) -> dict:
        """State + effectiveness of the radix prefix cache for /health,
        env_report and the bench cross-check: ``state`` is enabled/disabled
        with a machine-readable ``reason`` when disabled (e.g. a
        sliding-window model makes shared blocks unsafe to retain)."""
        pc = self._state_manager.prefix_cache
        if pc is None:
            return {"state": "disabled",
                    "reason": self._prefix_disable_reason or "not_enabled"}
        rep = pc.report()
        rep["state"] = "enabled"
        return rep

    # ---- serving (reference :107 put) ----

    def put(self, batch_uids: Iterable[int], batch_tokens: Iterable, do_checks: bool = True,
            window_logits: bool = False, defer_register=frozenset(),
            adopt_prefix: bool = True):
        """One ragged forward; returns logits [n_seqs_padded, vocab] — row i is
        the next-token distribution for batch_uids[i].

        ``window_logits``: return [n_seqs_padded, N, vocab] logits at EVERY
        fed token instead (speculative verification); trailing-window KV
        frees are deferred to the caller (who frees after rollback, when
        ``seen_tokens`` is truthful again). ``defer_register``: uids whose
        feed contains draft tokens — their prefix-cache registration is
        deferred until the caller has rolled back rejections (a rejected
        chain must never enter the cache; its blocks are overwritten in
        place)."""
        batch_uids = list(batch_uids)
        _fire_request_poison(batch_uids)
        with self.tracer.scope("ds.tick.assemble", rows=len(batch_uids)):
            batch = self._assemble_put(batch_uids, batch_tokens, do_checks,
                                       adopt_prefix)
        t0 = time.monotonic()
        with self.tracer.scope("ds.tick.dispatch", rows=len(batch_uids)):
            logits = self._model.forward(
                batch, window_logits=window_logits,
                adapter_slots=self._adapter_slot_rows(
                    batch_uids, batch.q_tok_idx.shape[0]))
        _put_seconds.record(time.monotonic() - t0)

        pc = self._state_manager.prefix_cache
        for uid in batch_uids:
            seq = self._state_manager.get_sequence(uid)
            seq.post_forward()
            # sequences whose feed carried draft tokens defer registration:
            # the caller rolls back rejections (history AND pending) and
            # then calls _register_pending itself
            if pc is not None and uid not in defer_register:
                self._register_pending(seq)
            if not window_logits:
                # draft steps also defer the trailing-window KV free: seen
                # is inflated by unverified drafts here, and a block freed
                # against the inflated window could still be needed after
                # rollback (free is irreversible — the caller frees once
                # seen is truthful)
                self._model.maybe_free_kv(seq)
        return logits

    def _assemble_put(self, batch_uids, batch_tokens, do_checks: bool,
                      adopt_prefix: bool):
        """The host half of :meth:`put` before the dispatch: feasibility,
        prefix adoption, KV allocation and the finalized ``RaggedBatch``."""
        batch_tokens = [np.asarray(t, dtype=np.int32).reshape(-1) for t in batch_tokens]

        if do_checks:
            token_lens = [t.size for t in batch_tokens]
            schedule_check = self.can_schedule(batch_uids, token_lens)
            if schedule_check != SchedulingResult.Success:
                raise SchedulingError(schedule_check)

        pc = self._state_manager.prefix_cache
        self._batch.clear()
        for i, (uid, tokens) in enumerate(zip(batch_uids, batch_tokens)):
            host_seq_desc = self._state_manager.get_sequence(uid)
            if (pc is not None and adopt_prefix and host_seq_desc is None
                    and tokens.size > 1):
                # NEW sequence: adopt the longest cached full-block prefix —
                # its KV already exists, so only the suffix is fed/computed.
                # At least one token must stay fed (logits come from it).
                matched, chain_key, fork = pc.match_fork(tokens[:tokens.size - 1])
                dst = None
                if fork is not None:
                    # mid-block divergence: COW-copy the fork source so the
                    # shared page stays read-only and this sequence writes
                    # its tail into a PRIVATE block. The transient pin taken
                    # by match_fork keeps the source alive even while it is
                    # an eviction candidate; dropped once the copy is in the
                    # device stream (later reuse of the source block orders
                    # after the copy program).
                    _src_key, src_block, fork_p = fork
                    try:
                        dst = self._state_manager.allocate_blocks(1)
                    except SchedulingError:
                        pc.release([src_block])  # abort fork: pool exhausted
                        fork = None
                    else:
                        self._model.cow_copy_block(src_block, int(dst[0]))
                        pc.commit_fork(fork_p)
                        pc.release([src_block])
                if matched or fork is not None:
                    _prefix_hits.inc()
                    _prefix_adopted_blocks.inc(len(matched))
                    host_seq_desc = self._state_manager.get_or_create_sequence(uid)
                    if matched:
                        host_seq_desc.extend_kv_cache(matched)
                    host_seq_desc.adopted_blocks = set(matched)
                    host_seq_desc.chain_key = chain_key
                    host_seq_desc.chain_blocks = len(matched)
                    skip = len(matched) * self._state_manager.block_size
                    if fork is not None:
                        _prefix_cow_forks.inc()
                        host_seq_desc.extend_kv_cache(dst)  # private COW block
                        # the forked run must reach the cache when this block
                        # completes: stage it ahead of the fed suffix so
                        # _register_pending sees the block's true contents
                        host_seq_desc.pending_tokens = np.asarray(
                            tokens[skip:skip + fork_p], np.int32)
                        skip += fork_p
                    _prefix_saved_tokens.inc(skip)
                    host_seq_desc.pre_forward(skip)
                    host_seq_desc.post_forward()  # history = cached prefix
                    tokens = tokens[skip:]
            if host_seq_desc is None:
                host_seq_desc = self._state_manager.get_or_create_sequence(uid)
            if pc is not None:
                # stage fed tokens for block registration post-forward; only
                # the sub-block tail is ever retained (O(block) per step,
                # not O(history))
                self._append_pending(host_seq_desc, tokens)
            batch_tokens[i] = tokens
            self._model.maybe_allocate_kv(host_seq_desc, tokens.size)
            host_seq_desc.pre_forward(tokens.size)
            self._batch.insert_sequence(host_seq_desc, tokens, do_checks=do_checks)

        return self._batch.finalize(
            total_slots=self._state_manager.kv_cache.num_blocks *
            self._state_manager.kv_cache.block_size)

    def score(self, batch_uids: Iterable[int], batch_tokens: Iterable,
              flush: bool = True):
        """Teacher-forced log-probabilities (the MII/RLHF scoring surface):
        for each NEW sequence, returns an array of length ``len(tokens)-1``
        with ``log p(tokens[j+1] | tokens[:j+1])`` — one ragged forward via
        window logits, no decode loop. ``flush=True`` releases the scoring
        KV afterwards (set False to continue decoding from the scored
        prefix with ``put``)."""
        batch_uids = list(batch_uids)
        batch_tokens = [np.asarray(t, dtype=np.int32).reshape(-1)
                        for t in batch_tokens]
        for uid in batch_uids:
            if self._state_manager.get_sequence(uid) is not None:
                raise ValueError(
                    f"score() expects NEW sequences (uid {uid} is live): "
                    "the first fed token's score would need the previous "
                    "step's logits")
        # adoption would skip prefill for cached prefixes — but scoring
        # needs logits at EVERY position, so every token must be fed
        logits = np.asarray(self.put(batch_uids, batch_tokens,
                                     window_logits=True, adopt_prefix=False))
        out = []
        for i, toks in enumerate(batch_tokens):
            rows = logits[i, :toks.size - 1].astype(np.float64)  # [T-1, V]
            logz = np.log(np.exp(rows - rows.max(-1, keepdims=True))
                          .sum(-1)) + rows.max(-1)
            out.append(rows[np.arange(toks.size - 1), toks[1:]] - logz)
        if flush:
            for uid in batch_uids:
                self.flush(uid)
        return out

    def fused_window(self, uids, output_budgets, cap: int) -> int:
        """Largest power-of-two K <= ``cap`` that EVERY sequence can absorb
        (remaining output budget and context room); < 2 means the per-step
        path should run. The power-of-two snap bounds fused-program
        compiles at O(log cap) per bucket. Whole-batch predicate — callers
        that can split a mixed-progress wave use :meth:`fused_partition`
        instead, so one near-budget request doesn't demote the rest."""
        sm = self._config.state_manager
        K = min(cap, min(output_budgets),
                min(sm.max_context
                    - self._state_manager.get_sequence(u).seen_tokens
                    for u in uids))
        while K >= 2 and K & (K - 1):
            K &= K - 1
        return K

    def fused_partition(self, uids, output_budgets, cap: int):
        """Split a decode wave into ``(fusable, K, solo)`` so one
        near-budget request can't demote the WHOLE batch off fused
        dispatch: ``fusable`` keeps every sequence with >= 2 tokens of room
        (output budget AND context), ``K`` is the largest power-of-two
        window <= ``cap`` they can ALL absorb, and ``solo`` holds the
        constrained sequences that must tick per-step — they are within a
        token of retiring, so the caller advances them alone for the one
        or two steps they have left. Shared by generate() and the serving
        daemon's fused tick."""
        sm = self._config.state_manager
        room = {u: min(b, sm.max_context
                       - self._state_manager.get_sequence(u).seen_tokens)
                for u, b in zip(uids, output_budgets)}
        fusable = [u for u in uids if room[u] >= 2]
        solo = [u for u in uids if room[u] < 2]
        if not fusable:
            return [], 0, solo
        K = min(cap, min(room[u] for u in fusable))
        while K >= 2 and K & (K - 1):
            K &= K - 1
        if K < 2:  # cap itself forbids fusing — everything ticks per-step
            return [], 0, uids
        return fusable, K, solo

    def fused_spec_partition(self, uids, output_budgets, draft_tokens: int,
                             cap: int):
        """Speculative analog of :meth:`fused_partition`: each fused window
        can write up to ``1 + draft_tokens`` KV positions (worst case all
        drafts accepted), so a row's window room is its CONTEXT headroom
        divided by the window width, while the output-budget bound stays
        per-window (each window emits at least one token; overshoot past
        the budget is trimmed at retirement like the plain fused path).
        Returns ``(fusable, K, solo)`` with K the largest power-of-two
        window count every fusable row can absorb."""
        sm = self._config.state_manager
        w = 1 + max(1, int(draft_tokens))
        room = {}
        for u, b in zip(uids, output_budgets):
            ctx = sm.max_context \
                - self._state_manager.get_sequence(u).seen_tokens
            room[u] = min(b, ctx // w)
        fusable = [u for u in uids if room[u] >= 2]
        solo = [u for u in uids if room[u] < 2]
        if not fusable:
            return [], 0, solo
        K = min(cap, min(room[u] for u in fusable))
        while K >= 2 and K & (K - 1):
            K &= K - 1
        if K < 2:
            return [], 0, uids
        return fusable, K, solo

    def decode_finished(self, uid, outputs, max_new_tokens,
                        eos_token_id, stop) -> bool:
        """The ONE retire predicate: output budget spent, eos emitted, a
        stop sequence hit, or the context ceiling reached (retiring before
        the next decode put would raise for the whole batch). Shared by
        generate()'s retirement scan, both fused paths, and the daemon."""
        seq = self._state_manager.get_sequence(uid)
        return (len(outputs) >= max_new_tokens
                or (eos_token_id is not None and outputs
                    and outputs[-1] == eos_token_id)
                or (bool(stop) and self.hit_stop(outputs, stop))
                or seq.seen_tokens + 1 > self._config.state_manager.max_context)

    @staticmethod
    def _append_pending(seq, tokens) -> None:
        """Stage fed tokens on the descriptor for prefix-cache registration
        (shared by put() and fused_decode_steps)."""
        pend = getattr(seq, "pending_tokens", None)
        if pend is None:
            pend = np.zeros(0, np.int32)
        seq.pending_tokens = np.concatenate(
            [pend, np.asarray(tokens, np.int32)])

    def _register_pending(self, seq) -> None:
        """Register the sequence's newly completed full KV blocks with the
        prefix cache as a chain continuation — each block is hashed exactly
        once over the sequence's lifetime (O(block) per step)."""
        pc = self._state_manager.prefix_cache
        if pc is None:
            return
        bs = self._state_manager.block_size
        full = len(seq.pending_tokens) // bs
        if full:
            start = getattr(seq, "chain_blocks", 0)
            seq.chain_key, _ = pc.register_from(
                getattr(seq, "chain_key", None),
                seq.pending_tokens[:full * bs],
                seq.kv_blocks[start:start + full])
            seq.chain_blocks = start + full
            seq.pending_tokens = seq.pending_tokens[full * bs:]

    # ---- scheduling feasibility (reference :158 query / :184 can_schedule) ----

    def query(self, uid: int, max_request_tokens: int, max_request_blocks: int) -> Tuple[int, int]:
        seq_desc = self._state_manager.get_sequence(uid)
        if seq_desc is None:
            if self._state_manager.n_tracked_sequences >= \
                    self._config.state_manager.max_tracked_sequences:
                return (0, 0)
            seq_desc = PlaceholderSequenceDescriptor()
        return self._model.get_kv_requirements(seq_desc, max_request_tokens, max_request_blocks)

    def can_schedule(self, uids: Iterable[int], lengths: Iterable[int]) -> SchedulingResult:
        uids, lengths = list(uids), list(lengths)
        cur_seqs = self._state_manager.n_tracked_sequences
        free_blocks = self._state_manager.free_blocks
        batch_len = 0

        if len(uids) > self._config.state_manager.max_ragged_sequence_count:
            return SchedulingResult.BatchSequenceLimitExceeded

        for uid, length in zip(uids, lengths):
            seq_desc = self._state_manager.get_sequence(uid)
            if seq_desc is None:
                cur_seqs += 1
                seq_desc = PlaceholderSequenceDescriptor()
            if seq_desc.seen_tokens + length > self._config.state_manager.max_context:
                return SchedulingResult.SequenceTokenLimitExceeded
            sched_len, sched_blocks = self._model.get_kv_requirements(seq_desc, length, free_blocks)
            if sched_len != length:
                return SchedulingResult.KVCacheLimitExceeded
            batch_len += length
            free_blocks -= sched_blocks

        if cur_seqs > self._config.state_manager.max_tracked_sequences:
            return SchedulingResult.EngineSequenceLimitExceeded
        if batch_len > self._config.state_manager.max_ragged_batch_size:
            return SchedulingResult.BatchTokenLimitExceeded
        return SchedulingResult.Success

    def get_remaining_block_capacity(self, uid: int) -> int:
        seq_desc = self._state_manager.get_sequence(uid)
        if seq_desc is None:
            return 0
        return self._model.get_remaining_block_capacity(seq_desc)

    def warmup(self, prefill_lens=(128, ), batch_sizes=(1, ),
               draft_tokens: int = 0, fused_windows=(),
               fused_sampled_windows=(), fused_spec_windows=(),
               spec_draft_tokens: int = 4, spec_draft_ngram: int = 2,
               decode_context: int = 0) -> int:
        """Precompile the bucketed forward programs serving will hit, so the
        first real request doesn't pay compile latency (the reference's
        CUDA-graph warmup analog). Runs scratch sequences through put() —
        prefill at each length, plus the decode (1-token) program at each
        concurrent batch size — then flushes them. ``draft_tokens``: also
        warm the window-logits verify program speculative decoding uses
        (1 + draft_tokens fed tokens). ``fused_windows``: K values whose
        fused multi-step decode program (fused_decode_steps) should compile
        per batch size — the serving daemon's steady-state tick.
        ``decode_context``: prefill the batched scratch sequences to this
        length first so the decode/fused programs compile at the production
        BLOCK-TABLE bucket — the compile key includes the block bucket B,
        and a 1-token scratch sequence (B=1) would warm a program the
        ctx-length traffic never hits. Returns the number of compiled
        programs cached."""
        base = 1 << 28  # scratch uid space clear of real uids
        for n in prefill_lens:
            uid = base
            # adopt_prefix=False + defer_register: warmup must neither adopt
            # cached blocks (an earlier warmup prefill would shrink this
            # bucket's fed-token count, leaving the real bucket uncompiled)
            # nor pollute the prefix cache with zero-token entries
            self.put([uid], [np.zeros(int(n), np.int32)], do_checks=False,
                     adopt_prefix=False, defer_register={uid})
            self.put([uid], [[0]], defer_register={uid})  # decode bucket
            if draft_tokens:
                self.put([uid], [[0] * (1 + draft_tokens)],
                         window_logits=True, defer_register={uid})
                seq = self._state_manager.get_sequence(uid)
                seq.rollback(draft_tokens)
            self._scrub_pending(uid)
            self.flush(uid)
        for bs in batch_sizes:
            uids = list(range(base + 1, base + 1 + bs))
            scratch = frozenset(uids)
            for u in uids:
                feed = np.zeros(max(1, int(decode_context)), np.int32)
                self.put([u], [feed], do_checks=False, adopt_prefix=False,
                         defer_register=scratch)
            self.put(uids, [[0]] * bs,  # batched decode bucket
                     defer_register=scratch)
            for K in fused_windows:
                self.fused_decode_steps(uids, [0] * bs, int(K))
            for K in fused_sampled_windows:
                # warm the SAMPLED scan program (logprobs on — the superset
                # compile the serving daemon's mixed waves hit)
                self.fused_decode_steps(
                    uids, [0] * bs, int(K),
                    specs=[SampleSpec(temperature=1.0, want_logprobs=True)
                           for _ in uids])
            for K in fused_spec_windows:
                # warm the fused speculative programs (greedy + sampled):
                # the scratch sequences' zero-token histories draft real
                # windows (every ngram matches), so the compiled shapes are
                # exactly the production ones
                hists = [[0] * (self._state_manager.get_sequence(u)
                                .seen_tokens + 1) for u in uids]
                self.fused_spec_decode_steps(
                    uids, hists, int(K),
                    num_draft_tokens=spec_draft_tokens,
                    draft_ngram=spec_draft_ngram)
                self.fused_spec_decode_steps(
                    uids, hists, int(K),
                    num_draft_tokens=spec_draft_tokens,
                    draft_ngram=spec_draft_ngram,
                    specs=[SampleSpec(temperature=1.0) for _ in uids])
            for u in uids:
                self._scrub_pending(u)
                self.flush(u)
        return len(self._model._fwd_cache)

    def _scrub_pending(self, uid) -> None:
        """Drop a scratch sequence's staged registration tail: warmup
        sequences feed zeros, and letting flush register that tail would
        seed the radix cache with entries real zero-prefixed traffic could
        adopt (warmup must stay invisible to the cache)."""
        seq = self._state_manager.get_sequence(uid)
        if seq is not None:
            seq.pending_tokens = np.zeros(0, np.int32)

    # ---- convenience decode loop (the MII surface over FastGen) ----

    @staticmethod
    def _sample_with_logprob(row: np.ndarray, temperature: float, rng,
                             top_k: int = 0, top_p: float = 1.0,
                             want_lp: bool = True) -> Tuple[int, float]:
        """Returns (token, logprob-of-token) under the temperature-scaled,
        top-k/top-p-filtered distribution (MII returns logprobs; greedy
        logprobs come from the raw softmax). ``want_lp=False`` skips the
        O(vocab) softmax pass — the default generate() path pays nothing
        for the logprob surface it isn't using."""

        def lp_at(logits, tok):
            if not want_lp:
                return 0.0
            # exp(-inf - m) is 0, so this is also correct on FILTERED
            # logits (the renormalized nucleus/top-k distribution)
            m = np.max(logits)
            return float(logits[tok] - m
                         - np.log(np.sum(np.exp(logits - m))))

        raw = row.astype(np.float64)
        if temperature <= 0:
            tok = int(np.argmax(raw))
            return tok, lp_at(raw, tok)
        logits = raw / temperature
        if top_k > 0 and top_k < logits.size:  # <=0 = disabled (vLLM style)
            kth = np.partition(logits, -top_k)[-top_k]
            logits = np.where(logits < kth, -np.inf, logits)
        if 0.0 < top_p < 1.0:
            # nucleus: keep the smallest set of tokens whose softmax mass
            # reaches top_p (the highest-prob token always survives:
            # cumsum(p)-p < top_p is True at the first position for any
            # positive top_p)
            order = np.argsort(logits)[::-1]
            p = np.exp(logits[order] - np.max(logits))
            p = p / p.sum()
            keep = np.cumsum(p) - p < top_p
            drop = np.ones_like(logits, dtype=bool)
            drop[order[keep]] = False
            logits = np.where(drop, -np.inf, logits)
        elif top_p <= 0.0:
            tok = int(np.argmax(logits))  # degenerate nucleus = greedy
            return tok, lp_at(logits, tok)
        # Gumbel-max: argmax(logits + G) ~ softmax(logits) sample
        # (-inf + G stays -inf, so filtered tokens can never win)
        g = rng.gumbel(size=logits.shape)
        tok = int(np.argmax(logits + g))
        return tok, lp_at(logits, tok)

    @classmethod
    def _sample(cls, row: np.ndarray, temperature: float, rng,
                top_k: int = 0, top_p: float = 1.0) -> int:
        return cls._sample_with_logprob(row, temperature, rng, top_k, top_p,
                                        want_lp=False)[0]

    # ---- on-device sampling (ops/sampling; numpy above stays the oracle) ----

    def seed_sampler(self, uid: int, seed: int = 0, key=None) -> None:
        """(Re)initialize a sequence's device PRNG key. The key stream is a
        pure function of the initial key, so the per-token and fused paths
        replay identical streams from the same seed."""
        if key is None:
            key = jax.random.PRNGKey(int(seed))
        self._sample_keys[uid] = np.asarray(key, np.uint32)

    def _sampler_key(self, uid: int, seed: int) -> np.ndarray:
        k = self._sample_keys.get(uid)
        if k is None:
            self.seed_sampler(uid, seed)
            k = self._sample_keys[uid]
        return k

    def fast_forward_sampler(self, uid: int, seed: int, burns: int) -> None:
        """Recreate a sequence's device PRNG key at chain position ``burns``:
        the state after that many counted key burns (one per sampled
        per-token dispatch, one per verified speculative window, one per
        fused scan step). Every sampling path advances keys the same way —
        ``split(key, 2)[0]`` — so iterating that split from ``PRNGKey(seed)``
        lands exactly where an uninterrupted run would be, and a replayed
        request's stream continues bit-identically (journal warm restart,
        eviction re-admission)."""
        key = jax.random.PRNGKey(int(seed))
        n = int(burns)
        if n > 0:
            key = _fast_forward_key(key, n)
        self.seed_sampler(uid, key=key)

    def spec_ring_window(self, num_draft_tokens: int) -> int:
        """Effective token-history window for prompt-lookup drafting. The
        device ring must hold at least one full speculative window plus a
        matchable pattern, so tiny ``spec_history_window`` configs get
        widened — the host fallback scan uses the SAME bound so both sides
        see (and miss) exactly the same matches."""
        scfg = getattr(self._config, "sampling", None)
        d = max(1, int(num_draft_tokens))
        max_ngram = int(scfg.spec_max_ngram) if scfg is not None else 8
        base = int(scfg.spec_history_window) if scfg is not None else 128
        return max(base, 2 * (1 + d) + max_ngram)

    @staticmethod
    def _spec_statics(specs):
        """Static compile flags a batch of SampleSpecs resolves to — part
        of the jit cache key, so an all-plain wave never pays for controls
        it doesn't use."""
        use_pen = any(s.repetition_penalty != 1.0 for s in specs)
        use_eos = any(s.eos_token_id is not None
                      and (s.block_eos or s.min_new > s.n_out)
                      for s in specs)
        want_lp = any(s.want_logprobs for s in specs)
        return use_pen, use_eos, want_lp

    def _spec_arrays(self, batch_uids, specs, S, V, use_pen):
        """Bucketed per-row control arrays shared by ``sample_rows`` and
        the sampled fused path. Padding rows are inert (temperature 0,
        penalty 1, no eos)."""
        temps = np.zeros(S, np.float32)
        top_ks = np.zeros(S, np.int32)
        top_ps = np.ones(S, np.float32)
        pens = np.ones(S, np.float32)
        eos = np.full(S, -1, np.int32)
        keys = np.zeros((S, 2), np.uint32)
        mask = np.zeros((S, V), bool) if use_pen else None
        for i, (u, s) in enumerate(zip(batch_uids, specs)):
            temps[i] = s.temperature
            top_ks[i] = s.top_k
            top_ps[i] = s.top_p
            pens[i] = s.repetition_penalty
            if s.eos_token_id is not None:
                eos[i] = int(s.eos_token_id)
            keys[i] = self._sampler_key(u, s.seed)
            if use_pen and s.repetition_penalty != 1.0 and s.history:
                mask[i, np.asarray(s.history, np.int64)] = True
        return temps, top_ks, top_ps, pens, eos, keys, mask

    def sample_rows(self, batch_uids, rows, specs):
        """ONE batched on-device sampling dispatch for logits rows fetched
        by a per-token tick: logit controls → temperature/top-k/top-p
        Gumbel-max → selected-token logprob, identical op-for-op to the
        fused scan's in-trace sampler, so a request keeps a bit-identical
        token stream when the scheduler moves it between paths. Advances
        each sequence's PRNG key by one split. Returns ``(tokens, logprobs)``
        lists of length ``len(batch_uids)``."""
        from ...ops import sampling as dsamp
        from .ragged.ragged_wrapper import _bucket
        batch_uids = list(batch_uids)
        rows = [np.asarray(r, np.float32).reshape(-1) for r in rows]
        n, V = len(batch_uids), rows[0].size
        S = _bucket(n, floor=1)
        use_pen, use_eos, want_lp = self._spec_statics(specs)
        temps, top_ks, top_ps, pens, eos, keys, mask = self._spec_arrays(
            batch_uids, specs, S, V, use_pen)
        blk = np.zeros(S, bool)
        logits = np.zeros((S, V), np.float32)
        for i, (row, s) in enumerate(zip(rows, specs)):
            logits[i] = row
            blk[i] = s.block_eos
        toks, lps, new_keys = dsamp.sample_step(
            logits, keys, temps, top_ks, top_ps, mask, pens, eos, blk,
            want_logprobs=want_lp, use_penalty=use_pen,
            use_eos_mask=use_eos)
        toks, lps, new_keys = jax.device_get((toks, lps, new_keys))
        for i, u in enumerate(batch_uids):
            self._sample_keys[u] = np.asarray(new_keys[i], np.uint32)
        return ([int(t) for t in toks[:n]], [float(l) for l in lps[:n]])

    @staticmethod
    def process_logits(row, history, *, repetition_penalty: float = 1.0,
                       eos_token_id=None, block_eos: bool = False,
                       logits_processor=None):
        """Pre-sampling logit controls (HF-generate parity for serving):
        CTRL-style repetition penalty over the full history, eos masking
        until ``min_new_tokens``, then an arbitrary user processor.
        Returns ``row`` itself when every control is off."""
        if (repetition_penalty == 1.0 and not block_eos
                and logits_processor is None):
            return row
        row = np.array(row, np.float32, copy=True)
        if repetition_penalty != 1.0:
            idx = np.unique(np.asarray(history, np.int64))
            vals = row[idx]
            row[idx] = np.where(vals > 0, vals / repetition_penalty,
                                vals * repetition_penalty)
        if block_eos and eos_token_id is not None:
            row[int(eos_token_id)] = -np.inf  # filtered tokens never win
        if logits_processor is not None:
            row = np.asarray(logits_processor(history, row), np.float32)
        return row

    @staticmethod
    def prompt_lookup_draft(history, *, draft_ngram: int, max_tokens: int,
                            match_window: int = 0, match_cache=None):
        """Prompt-lookup drafting (Saxena): propose the tokens that
        followed the most recent earlier occurrence of the trailing
        n-gram. No draft model — the history IS the drafter.

        The backward scan is bounded two ways (it used to rescan the FULL
        history every generated token — O(history × draft) per step):
        ``match_window`` > 0 restricts candidates to the trailing window
        (the device ring buffer's twin — same window, same drafts), and
        ``match_cache`` (a per-request dict) remembers the last match
        position: the most recent occurrence can only move FORWARD, so a
        still-valid cached match floors the scan and the per-token cost
        drops to O(new_tokens_since_last_match × ngram)."""
        if max_tokens <= 0 or len(history) <= draft_ngram:
            return []
        pat = history[-draft_ngram:]
        # the window bound matches the device ring's retention exactly
        # (candidate start within the trailing W tokens), so host and
        # fused drafting agree token-for-token inside the window
        lo = max(0, len(history) - match_window) if match_window > 0 else 0
        if match_cache is not None:
            p = match_cache.get("pos")
            if (p is not None and lo <= p <= len(history) - draft_ngram - 1
                    and history[p:p + draft_ngram] == pat):
                lo = p  # a match exists here; nothing older can win
        for s in range(len(history) - draft_ngram - 1, lo - 1, -1):
            if history[s:s + draft_ngram] == pat:
                if match_cache is not None:
                    match_cache["pos"] = s
                return [int(t) for t in
                        history[s + draft_ngram:s + draft_ngram + max_tokens]]
        return []

    def accept_drafts(self, uid: int, draft, window_row):
        """Greedy draft verification against one sequence's window logits
        (``[N, vocab]``, rows 0..len(draft) valid): accept the longest
        agreeing prefix plus the correction/bonus token, roll the rejected
        tail back in place (KV + prefix-cache pending tokens), and resume
        the deferred chain registration. Returns (new_tokens, n_accepted).
        Shared by ``generate()`` and the serving daemon — ONE copy of the
        rollback protocol."""
        k = len(draft)
        new_toks, m = [], 0
        for j in range(k + 1):
            t = int(window_row[j].argmax())
            if j < k and draft[j] == t:
                new_toks.append(t)
                m += 1
                continue
            new_toks.append(t)
            break
        seq = self._state_manager.get_sequence(uid)
        rejected = k - m
        if rejected:
            seq.rollback(rejected)
            if self._state_manager.prefix_cache is not None:
                seq.pending_tokens = \
                    seq.pending_tokens[:len(seq.pending_tokens) - rejected]
        if k:
            # deferred registration now that seen is truthful
            self._register_pending(seq)
        return new_toks, m

    def accept_drafts_sampled(self, uid: int, draft, window_rows, spec,
                              d_static: int):
        """Rejection-sampling draft verification for SAMPLED speculative
        requests — the host twin (and parity oracle) of one window of the
        fused speculative program. Runs the exact same op chain
        (``ops/sampling.spec_verify_window``) on this one row: accept each
        point-mass draft with the target probability of its token under
        the temperature/top-k/top-p distribution, sample the correction
        from the residual (or the bonus from the full distribution), and
        advance the sequence's PRNG key by exactly one split. ``d_static``
        must be the request's ``num_draft_tokens`` — the window's
        randomness is derived via a fixed ``split(sub, d_static + 1)``
        regardless of how many drafts were actually found, so the key
        stream stays in lockstep with the fused program (which always
        runs at the static width). Rollback bookkeeping matches
        ``accept_drafts``. Returns (new_tokens, n_accepted)."""
        from ...ops import sampling as dsamp
        d = max(1, int(d_static))
        k = len(draft)
        rows = np.asarray(window_rows, np.float32)
        if rows.ndim == 1:
            rows = rows[None, :]
        wl = np.zeros((1, 1 + d, rows.shape[-1]), np.float32)
        wl[0, :k + 1] = rows[:k + 1]
        drafts = np.zeros((1, d), np.int32)
        drafts[0, :k] = draft
        key = self._sampler_key(uid, spec.seed)
        out, n_emit, new_key = dsamp.spec_verify_window(
            wl, drafts, np.asarray([k], np.int32), key[None],
            np.asarray([spec.temperature], np.float32),
            np.asarray([spec.top_k], np.int32),
            np.asarray([spec.top_p], np.float32), d=d)
        out, n_emit, new_key = jax.device_get((out, n_emit, new_key))
        self._sample_keys[uid] = np.asarray(new_key[0], np.uint32)
        m = int(n_emit[0]) - 1
        new_toks = [int(t) for t in out[0, :m + 1]]
        seq = self._state_manager.get_sequence(uid)
        rejected = k - m
        if rejected:
            seq.rollback(rejected)
            if self._state_manager.prefix_cache is not None:
                seq.pending_tokens = \
                    seq.pending_tokens[:len(seq.pending_tokens) - rejected]
        if k:
            self._register_pending(seq)
        return new_toks, m

    def fused_decode_steps(self, batch_uids, last_tokens, n_steps: int,
                           specs=None):
        """``n_steps`` decode steps for live sequences in ONE device
        dispatch (model.fused_decode: lax.scan over the single-token forward
        — the TPU analog of the reference v1 engine's CUDA-graph decode
        replay, ``inference/engine.py:527``). Amortizes the per-dispatch host
        latency over K steps.

        Host contract: every uid is LIVE (has prefilled history), every
        sequence has room for ``n_steps`` more tokens (context ceiling is the
        caller's check), and KV blocks for all ``n_steps`` are allocated up
        front here — raises SchedulingError(KVCacheLimitExceeded) without
        side effects if they don't fit. Like the speculative window path,
        prefix-cache registration and trailing-window frees are DEFERRED:
        the caller trims to eos/stop and then runs ``_register_pending`` /
        ``maybe_free_kv`` for sequences that stay live (retiring sequences
        just flush).

        ``specs=None`` runs the original greedy program and returns int32
        [n_seqs, n_steps] generated tokens. With one :class:`SampleSpec`
        per uid, sampling (and logit controls) run ON DEVICE inside the
        scan — temperature/top-k/top-p/repetition-penalty/eos-mask
        requests advance K tokens per dispatch too — and the call returns
        ``(tokens [n_seqs, n_steps], logprobs [n_seqs, n_steps])``, with
        each sequence's PRNG key advanced by exactly ``n_steps`` splits
        (the same count the per-token path would burn)."""
        return self.fused_decode_harvest(
            self.fused_decode_begin(batch_uids, last_tokens, n_steps,
                                    specs=specs))

    def fused_decode_begin(self, batch_uids, last_tokens, n_steps: int,
                           specs=None):
        """DISPATCH half of :meth:`fused_decode_steps` — the continuous
        fusion scheduler's entry point. Feasibility-checks and allocates
        every one of the wave's ``n_steps`` KV blocks (allocation IS the
        KV partition: an overlap-window prefill put can only draw from
        what the wave left), enqueues the fused program WITHOUT blocking
        on the fetch, advances the sequences' host bookkeeping
        (``pre_forward``/``post_forward`` — so allocator projections made
        during the overlap window already see the wave's growth), and
        returns an in-flight handle for :meth:`fused_decode_harvest`.
        Host work needing device values (sampler-key stores, prefix-cache
        pending appends) is deferred to harvest."""
        t0 = time.monotonic()
        batch_uids = list(batch_uids)
        _fire_request_poison(batch_uids)
        with self.tracer.scope("ds.tick.assemble", rows=len(batch_uids)):
            seqs = []
            for uid in batch_uids:
                seq = self._state_manager.get_sequence(uid)
                if seq is None or seq.seen_tokens == 0:
                    raise ValueError(f"fused_decode_steps: uid {uid} is not a "
                                     "live prefilled sequence")
                seqs.append(seq)
            if len(seqs) > self._config.state_manager.max_ragged_sequence_count:
                raise SchedulingError(SchedulingResult.BatchSequenceLimitExceeded)
            sm = self._config.state_manager
            # feasibility before ANY allocation: the whole wave must fit —
            # get_kv_requirements is the allocator's own arithmetic
            free = self._state_manager.free_blocks
            for seq in seqs:
                if seq.seen_tokens + n_steps > sm.max_context:
                    raise SchedulingError(SchedulingResult.SequenceTokenLimitExceeded)
                n_fit, req = self._model.get_kv_requirements(seq, n_steps, free)
                if n_fit != n_steps:
                    raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                free -= req
            for seq in seqs:
                self._model.maybe_allocate_kv(seq, n_steps)

            from .ragged.ragged_wrapper import _bucket
            S = _bucket(len(seqs), floor=1)
            B = _bucket(max(s.cur_allocated_blocks for s in seqs), floor=1)
            tokens = np.zeros(S, np.int32)
            seq_lens = np.zeros(S, np.int32)
            liv = np.zeros(S, np.int32)
            block_table = np.zeros((S, B), np.int32)
            for i, (seq, t) in enumerate(zip(seqs, last_tokens)):
                tokens[i] = int(t)
                seq_lens[i] = seq.seen_tokens
                liv[i] = 1
                block_table[i] = seq.block_table(B)
            aslots = self._adapter_slot_rows(batch_uids, S)
        lps = new_keys = None
        with self.tracer.scope("ds.tick.dispatch", rows=len(batch_uids)):
            if specs is None:
                out = self._model.fused_decode(tokens, seq_lens, liv, block_table,
                                               n_steps, fetch=False,
                                               adapter_slots=aslots)  # [K, S]
            else:
                V = int(self._model.config.vocab_size)
                use_pen, use_eos, want_lp = self._spec_statics(specs)
                temps, top_ks, top_ps, pens, eos, keys, mask = self._spec_arrays(
                    batch_uids, specs, S, V, use_pen)
                n_out = np.zeros(S, np.int32)
                min_new = np.zeros(S, np.int32)
                for i, s in enumerate(specs):
                    n_out[i] = s.n_out
                    min_new[i] = s.min_new
                out, lps, new_keys = self._model.fused_decode(
                    tokens, seq_lens, liv, block_table, n_steps,
                    sampling=dict(keys=keys, temps=temps, top_ks=top_ks,
                                  top_ps=top_ps, penalties=pens, eos_ids=eos,
                                  n_out=n_out, min_new=min_new, seen_mask=mask,
                                  want_logprobs=want_lp, use_penalty=use_pen,
                                  use_eos_mask=use_eos),
                    fetch=False, adapter_slots=aslots)
        for seq in seqs:
            seq.pre_forward(n_steps)
            seq.post_forward()
        _dispatch_seconds.record(time.monotonic() - t0)
        _dispatches_total.inc()
        return _InFlightWave(uids=batch_uids, seqs=seqs, tokens=tokens,
                             out=out, lps=lps, new_keys=new_keys,
                             n_steps=n_steps, sampled=specs is not None,
                             ctx_tokens=int(seq_lens.sum()))

    def fused_decode_harvest(self, wave: "_InFlightWave"):
        """FETCH half of :meth:`fused_decode_steps`: block on the wave's
        device arrays, store advanced sampler keys, stage prefix-cache
        pending appends, and return the per-token contract — int32
        ``[n_seqs, n_steps]`` tokens (plus ``[n_seqs, n_steps]`` logprobs
        for a sampled wave)."""
        t0 = time.monotonic()
        n, n_steps = len(wave.seqs), wave.n_steps
        lps = None
        with self.tracer.scope("ds.tick.harvest", rows=n):
            if wave.sampled:
                out, lps, new_keys = jax.device_get(
                    (wave.out, wave.lps, wave.new_keys))
                for i, u in enumerate(wave.uids):
                    self._sample_keys[u] = np.asarray(new_keys[i], np.uint32)
                lps = np.asarray(lps)[:, :n].T  # [n_seqs, K]
            else:
                out = jax.device_get(wave.out)
            out = np.asarray(out)[:, :n].T  # [n_seqs, K]

        pc = self._state_manager.prefix_cache
        if pc is not None:
            for i, seq in enumerate(wave.seqs):
                # fed tokens this dispatch = the input token plus every
                # generated token except the last (it is fed by the NEXT
                # dispatch) — mirrors one put() append per step
                self._append_pending(
                    seq, np.concatenate([[wave.tokens[i]], out[i, :-1]]))
        _harvest_seconds.record(time.monotonic() - t0)
        _harvests_total.inc()
        if wave.sampled:
            return out, lps
        return out

    def fused_spec_decode_steps(self, batch_uids, histories, n_steps: int, *,
                                num_draft_tokens: int, draft_ngram: int,
                                specs=None):
        """``n_steps`` speculative draft/verify windows in ONE device
        dispatch with ONE host fetch — the speculative sibling of
        :meth:`fused_decode_steps` (model.fused_spec_decode). Drafting
        (ring-buffer prompt lookup), window verification, acceptance, and
        rejection-sampling all run inside the scan; host sync drops from
        one round-trip per window to one per K windows, i.e.
        O(new_tokens / (K × mean_accepted)) for the request.

        ``histories[i]`` is uid i's full prompt+output token list, whose
        LAST element is the next token to feed (the per-token path's
        ``last_tok``); the trailing ``spec_history_window`` tokens seed the
        device ring. KV for the worst case ``n_steps * (1 + d)`` tokens is
        reserved up front (feasibility checked before any allocation, like
        the plain fused path); rejected tails cost nothing — their slots
        are overwritten in place by the next window.

        ``specs=None`` verifies greedily (byte-identical to the per-token
        ``accept_drafts`` stream). With one :class:`SampleSpec` per uid,
        verification is rejection sampling against the point-mass drafts
        (``ops/sampling.spec_verify_window``) and each sequence's PRNG key
        advances by exactly ``n_steps`` splits — one per window, the same
        count the host ``accept_drafts_sampled`` fallback burns.

        Returns ``(tokens, drafted, accepted)``: per-uid emitted token
        lists (variable length — between ``n_steps`` and
        ``n_steps * (1 + d)``), and per-uid totals of drafted / accepted
        tokens across the K windows (the accept-rate observability feed)."""
        return self.fused_spec_decode_harvest(
            self.fused_spec_decode_begin(
                batch_uids, histories, n_steps,
                num_draft_tokens=num_draft_tokens, draft_ngram=draft_ngram,
                specs=specs))

    def fused_spec_decode_begin(self, batch_uids, histories, n_steps: int, *,
                                num_draft_tokens: int, draft_ngram: int,
                                specs=None):
        """DISPATCH half of :meth:`fused_spec_decode_steps`. Worst-case
        KV for all ``n_steps * (1 + d)`` tokens is allocated before the
        dispatch (the KV partition invariant, like
        :meth:`fused_decode_begin`), but — unlike the plain wave — the
        sequences' ``pre_forward`` advance depends on the device's
        accepted counts, so ALL host bookkeeping is deferred to
        :meth:`fused_spec_decode_harvest`; during the overlap window the
        wave members' ``seen_tokens`` are stale-low, which only makes
        admission projections conservative (their worst-case blocks are
        already taken)."""
        t0 = time.monotonic()
        batch_uids = list(batch_uids)
        _fire_request_poison(batch_uids)
        with self.tracer.scope("ds.tick.assemble", rows=len(batch_uids)):
            d = max(1, int(num_draft_tokens))
            scfg = getattr(self._config, "sampling", None)
            max_ngram = int(scfg.spec_max_ngram) if scfg is not None else 8
            if draft_ngram > max_ngram:
                raise ValueError(f"draft_ngram {draft_ngram} exceeds "
                                 f"spec_max_ngram {max_ngram}")
            W = self.spec_ring_window(d)
            seqs = []
            for uid in batch_uids:
                seq = self._state_manager.get_sequence(uid)
                if seq is None or seq.seen_tokens == 0:
                    raise ValueError(f"fused_spec_decode_steps: uid {uid} is "
                                     "not a live prefilled sequence")
                seqs.append(seq)
            if len(seqs) > self._config.state_manager.max_ragged_sequence_count:
                raise SchedulingError(SchedulingResult.BatchSequenceLimitExceeded)
            sm = self._config.state_manager
            worst = n_steps * (1 + d)
            free = self._state_manager.free_blocks
            for seq in seqs:
                if seq.seen_tokens + worst > sm.max_context:
                    raise SchedulingError(
                        SchedulingResult.SequenceTokenLimitExceeded)
                n_fit, req = self._model.get_kv_requirements(seq, worst, free)
                if n_fit != worst:
                    raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                free -= req
            for seq in seqs:
                self._model.maybe_allocate_kv(seq, worst)

            from .ragged.ragged_wrapper import _bucket
            S = _bucket(len(seqs), floor=1)
            B = _bucket(max(s.cur_allocated_blocks for s in seqs), floor=1)
            tokens = np.zeros(S, np.int32)
            seq_lens = np.zeros(S, np.int32)
            liv = np.zeros(S, np.int32)
            block_table = np.zeros((S, B), np.int32)
            hist = np.zeros((S, W), np.int32)
            hist_len = np.zeros(S, np.int32)
            ngrams = np.zeros(S, np.int32)
            max_d = np.zeros(S, np.int32)
            for i, (seq, h) in enumerate(zip(seqs, histories)):
                tokens[i] = int(h[-1])
                seq_lens[i] = seq.seen_tokens
                liv[i] = 1
                block_table[i] = seq.block_table(B)
                L = len(h)
                tail = np.asarray(h[max(0, L - W):], np.int32)
                p = np.arange(L - tail.size, L)
                hist[i, p % W] = tail  # logical position p lives in slot p % W
                hist_len[i] = L
                ngrams[i] = int(draft_ngram)
                max_d[i] = d
            sampling = None
            if specs is not None:
                temps = np.zeros(S, np.float32)
                top_ks = np.zeros(S, np.int32)
                top_ps = np.ones(S, np.float32)
                keys = np.zeros((S, 2), np.uint32)
                for i, (u, s) in enumerate(zip(batch_uids, specs)):
                    temps[i] = s.temperature
                    top_ks[i] = s.top_k
                    top_ps[i] = s.top_p
                    keys[i] = self._sampler_key(u, s.seed)
                sampling = dict(keys=keys, temps=temps, top_ks=top_ks,
                                top_ps=top_ps)
        with self.tracer.scope("ds.tick.dispatch", rows=len(batch_uids)):
            out, n_emit, dlen, new_keys = self._model.fused_spec_decode(
                tokens, seq_lens, liv, block_table, hist, hist_len, ngrams,
                max_d, n_steps, d, max_ngram, sampling=sampling, fetch=False,
                adapter_slots=self._adapter_slot_rows(batch_uids, S))
        _dispatch_seconds.record(time.monotonic() - t0)
        _dispatches_total.inc()
        return _InFlightSpecWave(uids=batch_uids, seqs=seqs, tokens=tokens,
                                 out=out, n_emit=n_emit, dlen=dlen,
                                 new_keys=new_keys, n_steps=n_steps,
                                 ctx_tokens=int(seq_lens.sum()))

    def fused_spec_decode_harvest(self, wave: "_InFlightSpecWave"):
        """FETCH half of :meth:`fused_spec_decode_steps`: block on the
        wave, store advanced keys, run the deferred per-sequence
        bookkeeping against the device's accepted counts, and return
        ``(tokens, drafted, accepted)``."""
        t0 = time.monotonic()
        n_steps, tokens, seqs = wave.n_steps, wave.tokens, wave.seqs
        with self.tracer.scope("ds.tick.harvest", rows=len(seqs)):
            if wave.new_keys is not None:
                out, n_emit, dlen, new_keys = jax.device_get(
                    (wave.out, wave.n_emit, wave.dlen, wave.new_keys))
                for i, u in enumerate(wave.uids):
                    self._sample_keys[u] = np.asarray(new_keys[i], np.uint32)
            else:
                out, n_emit, dlen = jax.device_get(
                    (wave.out, wave.n_emit, wave.dlen))

        pc = self._state_manager.prefix_cache
        toks_lists, drafted, accepted = [], [], []
        for i, seq in enumerate(seqs):
            emitted = []
            for w in range(n_steps):
                emitted.extend(int(t) for t in out[w, i, :n_emit[w, i]])
            # seen advances by exactly what the device's lens did — the
            # accepted tokens; worst-case blocks stay allocated for the
            # next window (or free at flush)
            seq.pre_forward(len(emitted))
            seq.post_forward()
            if pc is not None:
                self._append_pending(
                    seq, np.asarray([int(tokens[i])] + emitted[:-1],
                                    np.int32))
            toks_lists.append(emitted)
            drafted.append(int(dlen[:, i].sum()))
            accepted.append(len(emitted) - n_steps)
        _harvest_seconds.record(time.monotonic() - t0)
        _harvests_total.inc()
        return toks_lists, drafted, accepted

    @staticmethod
    def normalize_stop(stop):
        """``stop`` → list of token-id sequences (one flat list = one
        sequence; None/empty = no stop sequences)."""
        if not stop:
            return []
        if all(isinstance(t, (int, np.integer)) for t in stop):
            stop = [stop]
        out = [[int(t) for t in s] for s in stop]
        if any(not s for s in out):
            raise ValueError("empty stop sequence")
        return out

    @staticmethod
    def hit_stop(outputs, stop_seqs) -> bool:
        return any(len(outputs) >= len(s) and outputs[-len(s):] == s
                   for s in stop_seqs)

    def generate(self, prompts, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 return_logprobs: bool = False,
                 seed: int = 0,
                 speculative: Optional[str] = None,
                 num_draft_tokens: int = 4,
                 draft_ngram: int = 2,
                 num_return_sequences: int = 1,
                 stop=None,
                 min_new_tokens: int = 0,
                 repetition_penalty: float = 1.0,
                 logits_processor=None,
                 fused_decode_window: Optional[int] = None):
        """Continuous-batching decode: admit prompts in scheduler-feasible
        waves (Dynamic SplitFuse ``can_schedule`` gating), decode every live
        sequence in ONE ragged batch per step (the N=1 fast path), free KV on
        completion. Returns the generated token list per prompt (no prompt
        echo).

        Admission reserves DECODE headroom, not just prompt KV: a sequence
        only enters when blocks for ``len(feed) + max_new_tokens`` fit after
        the projected growth of every live sequence, so the decode ``put``
        cannot run the allocator dry mid-generation. If it still does (e.g.
        admission fell back to best-effort), the newest live sequence is
        evicted and later replayed (prompt + tokens so far) instead of the
        whole batch crashing.

        Sampling controls (HF-generate parity): ``stop`` — token-id
        sequence(s) that end generation when the output tail matches (the
        matched tokens are included); ``min_new_tokens`` masks eos until
        reached; ``repetition_penalty`` is the CTRL rule over
        prompt+output history; ``logits_processor(history, row) -> row``
        runs last, before sampling.

        ``speculative="prompt_lookup"`` (greedy only; beyond the reference):
        each decode step drafts up to ``num_draft_tokens`` by matching the
        trailing ``draft_ngram`` against earlier context (Saxena's
        prompt-lookup decoding — no draft model) and verifies them in ONE
        forward via window logits; accepted drafts land m+1 tokens per
        dispatch, rejected ones roll back in place. Memory-bound decode is
        where this pays: the verify pass re-reads the same weights a plain
        step would.

        ``fused_decode_window``: cap on greedy multi-step fused decode (K
        steps per dispatch, ``fused_decode_steps``). Default: 16 on TPU
        (per-dispatch latency dominates single-token steps there), 1 (off)
        on CPU. Applies only to plain greedy generation — any sampling
        control, logprobs, or speculative mode uses the per-step path."""
        stop = self.normalize_stop(stop)
        if fused_decode_window is None:
            from ...ops.registry import on_tpu
            fused_steps_cap = 16 if on_tpu() else 1
        else:
            fused_steps_cap = int(fused_decode_window)
        if speculative is not None:
            if speculative != "prompt_lookup":
                raise ValueError(f"unknown speculative mode {speculative!r}")
            if return_logprobs:
                # the rejection-sampled token's "logprob" under the target
                # distribution is not the probability it was emitted with
                # — refuse rather than report a misleading number
                raise ValueError("speculative decoding does not return "
                                 "logprobs")
            if (min_new_tokens or repetition_penalty != 1.0
                    or logits_processor is not None):
                # temperature/top-k/top-p COMPOSE (rejection sampling
                # against the point-mass drafts — see
                # ops/sampling.spec_verify_window), but history-dependent
                # LOGIT edits would make the verified distribution
                # position-dependent in ways the single window forward
                # can't reproduce. (``stop`` composes: it only truncates
                # outputs at retirement, like eos.)
                raise ValueError("speculative decoding does not compose "
                                 "with min_new_tokens/"
                                 "repetition_penalty/logits_processor")

        def _controls(row, u):
            block_eos = len(outputs[u]) < min_new_tokens
            if (repetition_penalty == 1.0 and not block_eos
                    and logits_processor is None):
                return row  # controls off: skip the O(context) history copy
            return self.process_logits(
                row, prompts[u] + outputs[u],
                repetition_penalty=repetition_penalty,
                eos_token_id=eos_token_id,
                block_eos=block_eos,
                logits_processor=logits_processor)

        rng = np.random.default_rng(seed)
        # on-device sampling (ops/sampling): any request the host-only
        # logits_processor doesn't claim runs controls + sampling in ONE
        # batched device dispatch per step — and becomes eligible for the
        # fused K-step program below. Plain greedy without logprobs keeps
        # the zero-dispatch host argmax.
        scfg = getattr(self._config, "sampling", None)
        device_sampled = (scfg is not None and scfg.device_sampling
                          and logits_processor is None
                          and (temperature != 0.0 or return_logprobs
                               or repetition_penalty != 1.0
                               or min_new_tokens > 0))
        base_key = jax.random.PRNGKey(int(seed)) if device_sampled else None
        spec_sampled = speculative is not None and temperature != 0.0
        if spec_sampled and not device_sampled:
            # the rejection-sampling verify draws from the per-sequence
            # jax key chains; without them there is no reproducible (or
            # fused-parity) stream to offer
            raise ValueError("speculative sampling requires "
                             "sampling.device_sampling")
        # accept-rate observability for the convenience loop (the serving
        # daemon keeps its own per-request counters)
        self.last_spec_stats = {"drafted": 0, "accepted": 0}
        spec_match_window = (self.spec_ring_window(num_draft_tokens)
                             if speculative is not None else 0)
        spec_match_cache = {}

        def _spec(u):
            return SampleSpec(
                temperature=temperature, top_k=top_k, top_p=top_p,
                repetition_penalty=repetition_penalty,
                eos_token_id=eos_token_id,
                block_eos=len(outputs[u]) < min_new_tokens,
                history=(prompts[u] + outputs[u])
                if repetition_penalty != 1.0 else None,
                want_logprobs=return_logprobs,
                n_out=len(outputs[u]), min_new=min_new_tokens)

        def _ensure_keys(us):
            # per-sequence streams derived from the one generate() seed —
            # decorrelated across sequences, reproducible per (seed, u)
            for u in us:
                if u not in self._sample_keys:
                    self.seed_sampler(u, key=jax.random.fold_in(base_key, u))

        def _sample_wave(us, rows):
            """(token, logprob) per row: one batched device dispatch for
            eligible configs, the numpy oracle otherwise."""
            if device_sampled:
                _ensure_keys(us)
                toks, lps = self.sample_rows(us, rows,
                                             [_spec(u) for u in us])
                return list(zip(toks, lps))
            return [self._sample_with_logprob(
                _controls(rows[i], u), temperature, rng, top_k, top_p,
                want_lp=return_logprobs) for i, u in enumerate(us)]

        if num_return_sequences > 1:
            # parallel sampling (MII n-sampling): N samples per prompt,
            # flattened [p0_s0, p0_s1, ..., p1_s0, ...]. With prefix caching
            # on, each unique prompt's prefill is computed ONCE up front and
            # every sample adopts the cached blocks.
            pc0 = self._state_manager.prefix_cache
            if pc0 is not None:
                scratch = 1 << 27
                seen_prompts = set()
                for p in prompts:
                    arr = np.asarray(p, np.int32).reshape(-1)
                    key = arr.tobytes()
                    if (key in seen_prompts
                            or arr.size <= self._state_manager.block_size):
                        continue
                    seen_prompts.add(key)
                    try:
                        self.put([scratch], [arr], do_checks=False)
                    except SchedulingError:
                        break  # cache full; samples just recompute
                    self.flush(scratch)  # blocks stay cached for adoption
                    scratch += 1
            prompts = [p for p in prompts for _ in range(num_return_sequences)]
        prompts = [list(map(int, np.asarray(p).reshape(-1))) for p in prompts]
        uids = list(range(len(prompts)))
        outputs = {u: [] for u in uids}
        logprobs = {u: [] for u in uids}
        # tokens to prefill on (re)admission: prompt, or prompt + generated
        # so far after an eviction
        feed = {u: list(prompts[u]) for u in uids}
        waiting = list(uids)
        live: list = []
        last_tok = {}
        sm = self._config.state_manager
        max_batch_tokens = sm.max_ragged_batch_size
        # the decode batch feeds one token per live sequence, so live count is
        # bounded by BOTH sequence and token limits
        max_seqs = min(sm.max_ragged_sequence_count, max_batch_tokens)

        def _future_blocks(seq_desc, extra: int) -> int:
            # the allocator's own arithmetic, not a re-derivation: blocks
            # `extra` more tokens would need given an unlimited budget
            _, req = self._model.get_kv_requirements(seq_desc, extra, 1 << 30)
            return req

        def _live_reserve() -> int:
            return sum(
                _future_blocks(self._state_manager.get_sequence(u),
                               max(0, max_new_tokens - len(outputs[u])))
                for u in live)

        def _prefill_chunked(u) -> None:
            """Solo SplitFuse prefill for a feed longer than one ragged batch
            (an evicted replay); only the final chunk's logits matter."""
            for ofs in range(0, len(feed[u]), max_batch_tokens):
                logits = np.asarray(self.put(
                    [u], [feed[u][ofs:ofs + max_batch_tokens]],
                    do_checks=False))[0]
            (last_tok[u], lp), = _sample_wave([u], [logits])
            outputs[u].append(last_tok[u])
            logprobs[u].append(lp)
            live.append(u)

        while waiting or live:
            free = self._state_manager.free_blocks - _live_reserve()
            admit, admit_blocks = [], 0
            for u in list(waiting):
                if len(live) + len(admit) >= max_seqs:
                    break
                if len(feed[u]) > sm.max_context:
                    # chunked prefill bypasses put()'s checks, so the context
                    # ceiling must be enforced here (a mid-chunk ValueError
                    # from extend_kv_cache would leak the allocated blocks)
                    raise SchedulingError(SchedulingResult.SequenceTokenLimitExceeded)
                if _future_blocks(PlaceholderSequenceDescriptor(), len(feed[u])) \
                        > self._state_manager.kv_cache.num_blocks:
                    # can never prefill even with the whole cache to itself
                    raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                need = _future_blocks(
                    PlaceholderSequenceDescriptor(),
                    len(feed[u]) + max(0, max_new_tokens - len(outputs[u])))
                if len(feed[u]) > max_batch_tokens:
                    if admit or need > free:
                        break
                    waiting.remove(u)
                    _prefill_chunked(u)
                    break
                trial = admit + [u]
                if self.can_schedule(trial, [len(feed[t]) for t in trial]) \
                        != SchedulingResult.Success:
                    break
                if admit_blocks + need > free:
                    break
                admit.append(u)
                admit_blocks += need
                waiting.remove(u)
            if not admit and not live and waiting:
                # full decode headroom will never fit — admit ONE sequence on
                # prefill feasibility alone (the eviction path below truncates
                # it if the cache truly runs out) rather than deadlocking
                u = waiting[0]
                if len(feed[u]) > max_batch_tokens:
                    # chunked prefill bypasses put()'s checks: the FEED must
                    # fit the blocks actually free NOW (external put()-created
                    # sequences may pin part of the cache), else the
                    # allocator would raise a raw error mid-chunk
                    if _future_blocks(PlaceholderSequenceDescriptor(),
                                      len(feed[u])) \
                            > self._state_manager.free_blocks:
                        raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                    waiting.remove(u)
                    _prefill_chunked(u)
                else:
                    check = self.can_schedule([u], [len(feed[u])])
                    if check != SchedulingResult.Success:
                        raise SchedulingError(check)
                    admit = [waiting.pop(0)]
            if admit:
                logits = np.asarray(self.put(admit, [feed[u] for u in admit],
                                             do_checks=False))
                picks = _sample_wave(admit, [logits[i]
                                             for i in range(len(admit))])
                for i, u in enumerate(admit):
                    last_tok[u], lp = picks[i]
                    outputs[u].append(last_tok[u])
                    logprobs[u].append(lp)
                    live.append(u)
            for u in list(live):
                if self.decode_finished(u, outputs[u], max_new_tokens,
                                        eos_token_id, stop):
                    live.remove(u)
                    self.flush(u)
            if not live:
                continue

            def _absorb_new_tokens(u, new_toks, new_lps=None):
                """Shared trim protocol for multi-token waves (fused decode
                and speculative verification): append, cut at the earliest
                eos, then at the earliest stop-sequence END inside the
                appended window, cap at the output budget. Overshot KV needs
                no rollback — a trimmed sequence retires and flushes."""
                outputs[u].extend(new_toks)
                logprobs[u].extend(new_lps if new_lps is not None
                                   else [None] * len(new_toks))
                if eos_token_id is not None and eos_token_id in new_toks:
                    cut = len(outputs[u]) - len(new_toks) \
                        + new_toks.index(eos_token_id) + 1
                    outputs[u] = outputs[u][:cut]
                if stop:
                    out = outputs[u]
                    first = len(out) - len(new_toks) + 1
                    for end in range(max(first, 1), len(out) + 1):
                        if self.hit_stop(out[:end], stop):
                            outputs[u] = out[:end]
                            break
                if len(outputs[u]) > max_new_tokens:
                    outputs[u] = outputs[u][:max_new_tokens]
                if len(logprobs[u]) > len(outputs[u]):
                    logprobs[u] = logprobs[u][:len(outputs[u])]
                last_tok[u] = outputs[u][-1]

            # fused multi-step fast path runs K steps per dispatch — the
            # CUDA-graph-replay analog (see fused_decode_steps). Plain
            # greedy uses the original argmax program; device-sampled
            # requests (temperature/top-k/top-p/logprobs/penalty/min_new)
            # ride the sampled scan program — only host-only
            # logits_processor callbacks and speculative drafting stay
            # per-token. eos and ``stop`` compose by trim-and-retire:
            # overshoot tokens belong to sequences that retire this wave,
            # so their KV needs no rollback (same argument as the
            # speculative window-overshoot path below).
            fused_plain = (speculative is None and temperature == 0.0
                           and not return_logprobs and min_new_tokens == 0
                           and repetition_penalty == 1.0
                           and logits_processor is None)
            fused_ok = fused_steps_cap > 1 and (
                fused_plain or (device_sampled and speculative is None
                                and scfg.fused_sampled_decode))
            if fused_ok:
                # mixed-progress waves SPLIT rather than demote: sequences
                # with >= 2 tokens of room fuse at the largest window THEY
                # support; a near-budget straggler (solo) ticks per-step in
                # the SAME iteration — it is within a token or two of
                # retiring, so the inline single put is bounded, and the
                # fused subset keeps streaming K tokens per dispatch
                fusable, K, solo = self.fused_partition(
                    live, [max_new_tokens - len(outputs[u]) for u in live],
                    fused_steps_cap)
                toks = lps_wave = None
                if K >= 2:
                    try:
                        if fused_plain:
                            toks = self.fused_decode_steps(
                                fusable, [last_tok[u] for u in fusable], K)
                        else:
                            _ensure_keys(fusable)
                            toks, lps_wave = self.fused_decode_steps(
                                fusable, [last_tok[u] for u in fusable], K,
                                specs=[_spec(u) for u in fusable])
                    except SchedulingError:
                        pass  # KV pressure: the single-step path below owns
                        # the evict-and-replay protocol
                if toks is not None:
                    for i, u in enumerate(fusable):
                        _absorb_new_tokens(
                            u, list(map(int, toks[i])),
                            list(map(float, lps_wave[i]))
                            if lps_wave is not None else None)
                        if not self.decode_finished(u, outputs[u],
                                                    max_new_tokens,
                                                    eos_token_id, stop):
                            # deferred bookkeeping for sequences that decode
                            # on; retiring ones just flush at the top of the
                            # loop (pending garbage past eos never registers)
                            seq = self._state_manager.get_sequence(u)
                            self._register_pending(seq)
                            self._model.maybe_free_kv(seq)
                    for u in solo:
                        try:
                            logits_u = np.asarray(
                                self.put([u], [[last_tok[u]]]))[0]
                        except SchedulingError:
                            continue  # replayed by the per-step path's
                            # evict-and-replay protocol next iteration
                        (last_tok[u], lp), = _sample_wave([u], [logits_u])
                        outputs[u].append(last_tok[u])
                        logprobs[u].append(lp)
                    # retirement for both groups happens at the top of the
                    # next loop iteration (the shared decode_finished scan)
                    continue

            # fused SPECULATIVE fast path: drafting, verification, and
            # (for sampled requests) rejection sampling all run inside one
            # K-window scan — one dispatch and one host fetch per
            # K × (accepted+1) tokens (fused_spec_decode_steps). Gate-off
            # (fused_speculative_decode=False) keeps the per-token window
            # path below as the parity oracle.
            fused_spec_ok = (speculative is not None and fused_steps_cap > 1
                             and scfg is not None
                             and scfg.fused_speculative_decode
                             and logits_processor is None
                             and draft_ngram <= scfg.spec_max_ngram)
            if fused_spec_ok:
                fusable, K, solo = self.fused_spec_partition(
                    live, [max_new_tokens - len(outputs[u]) for u in live],
                    num_draft_tokens, fused_steps_cap)
                res = None
                if K >= 2:
                    try:
                        sp = None
                        if spec_sampled:
                            _ensure_keys(fusable)
                            sp = [_spec(u) for u in fusable]
                        res = self.fused_spec_decode_steps(
                            fusable,
                            [prompts[u] + outputs[u] for u in fusable], K,
                            num_draft_tokens=num_draft_tokens,
                            draft_ngram=draft_ngram, specs=sp)
                    except SchedulingError:
                        pass  # KV pressure: the per-token path below owns
                        # the evict-and-replay protocol
                if res is not None:
                    toks_lists, drafted_n, accepted_n = res
                    for i, u in enumerate(fusable):
                        self.last_spec_stats["drafted"] += drafted_n[i]
                        self.last_spec_stats["accepted"] += accepted_n[i]
                        _absorb_new_tokens(u, toks_lists[i])
                        if not self.decode_finished(u, outputs[u],
                                                    max_new_tokens,
                                                    eos_token_id, stop):
                            seq = self._state_manager.get_sequence(u)
                            self._register_pending(seq)
                            self._model.maybe_free_kv(seq)
                    for u in solo:
                        # near-retirement rows tick per-step draft-free —
                        # they have at most a token or two left
                        try:
                            logits_u = np.asarray(
                                self.put([u], [[last_tok[u]]]))[0]
                        except SchedulingError:
                            continue
                        (last_tok[u], lp), = _sample_wave([u], [logits_u])
                        outputs[u].append(last_tok[u])
                        logprobs[u].append(lp)
                    continue

            # total drafted tokens are bounded by the ragged-batch budget
            # (each live seq is guaranteed its 1 real token first) and each
            # sequence's room by its context AND output budgets
            draft_budget = max(0, max_batch_tokens - len(live)) \
                if speculative else 0

            def _draft(u, budget):
                seq = self._state_manager.get_sequence(u)
                room = min(num_draft_tokens, budget,
                           sm.max_context - seq.seen_tokens - 2,
                           max_new_tokens - len(outputs[u]) - 1)
                return self.prompt_lookup_draft(
                    prompts[u] + outputs[u], draft_ngram=draft_ngram,
                    max_tokens=room, match_window=spec_match_window,
                    match_cache=spec_match_cache.setdefault(u, {}))

            drafts = {}
            for u in live:
                drafts[u] = _draft(u, draft_budget) if speculative else []
                draft_budget -= len(drafts[u])
            use_window = any(drafts[u] for u in live)
            while live:
                try:
                    step_feed = [[last_tok[u]] + drafts[u] for u in live]
                    logits = np.asarray(self.put(
                        live, step_feed, window_logits=use_window,
                        defer_register=(
                            {u for u in live if drafts[u]}
                            if use_window else frozenset())))
                    break
                except SchedulingError:
                    if use_window:
                        # drafts don't justify evicting a healthy sequence:
                        # retry the step draft-free before giving up KV
                        drafts = {u: [] for u in live}
                        use_window = False
                        continue
                    u = live.pop()  # newest first: oldest finish soonest
                    self.flush(u)
                    if live:
                        feed[u] = prompts[u] + outputs[u]
                        waiting.insert(0, u)  # replay once blocks free up
                    # else: lone sequence exhausted the whole cache — its
                    # generation is truncated at the tokens produced so far
            if not live:
                continue
            if use_window:
                # draft verification: greedy rows accept the longest
                # argmax-agreeing prefix (accept_drafts — shared with the
                # serving daemon); sampled rows run the rejection-sampling
                # verify (accept_drafts_sampled — the fused program's host
                # twin). Both emit the correction/bonus token and roll the
                # rejected tail back in place.
                if spec_sampled:
                    _ensure_keys(live)
                for i, u in enumerate(live):
                    if spec_sampled:
                        new_toks, m = self.accept_drafts_sampled(
                            u, drafts[u], logits[i], _spec(u),
                            num_draft_tokens)
                    else:
                        new_toks, m = self.accept_drafts(u, drafts[u],
                                                         logits[i])
                    self.last_spec_stats["drafted"] += len(drafts[u])
                    self.last_spec_stats["accepted"] += m
                    seq = self._state_manager.get_sequence(u)
                    # window puts defer the trailing-window free for EVERY
                    # sequence in the batch — resume it here
                    self._model.maybe_free_kv(seq)
                    _absorb_new_tokens(u, new_toks)
            elif spec_sampled:
                # a draft-free step of a SAMPLED speculative request still
                # verifies through the window math (with zero drafts): the
                # per-window key discipline must match the fused program's,
                # which burns one split per window regardless of drafts
                _ensure_keys(live)
                for i, u in enumerate(live):
                    new_toks, _ = self.accept_drafts_sampled(
                        u, [], logits[i], _spec(u), num_draft_tokens)
                    _absorb_new_tokens(u, new_toks)
            else:
                picks = _sample_wave(live, [logits[i]
                                            for i in range(len(live))])
                for i, u in enumerate(live):
                    last_tok[u], lp = picks[i]
                    outputs[u].append(last_tok[u])
                    logprobs[u].append(lp)
        if return_logprobs:
            return [outputs[u] for u in uids], [logprobs[u] for u in uids]
        return [outputs[u] for u in uids]

    def adopt_handoff(self, uid: int, tokens, blocks, seen_tokens: int) -> None:
        """Take over a sequence whose prefix KV was computed on ANOTHER
        engine (disaggregated prefill) and landed into ``blocks`` of THIS
        engine's paged pool: create the descriptor with its history marked
        seen, and register the landed full blocks with the prefix cache so
        adoption/eviction accounting treats them exactly like locally
        computed prefill. ``blocks`` must already be allocated from this
        engine's state manager; ``tokens`` is the seen history (prompt +
        force-fed replay outputs) backing those blocks."""
        sm = self._state_manager
        if sm.get_sequence(uid) is not None:
            raise ValueError(f"uid {uid} already tracked; cannot adopt handoff")
        seq = sm.get_or_create_sequence(uid)
        seq.extend_kv_cache(np.asarray(blocks, np.int64))
        seq.pre_forward(int(seen_tokens))
        seq.post_forward()
        if sm.prefix_cache is not None:
            tokens = np.asarray(tokens, np.int32).reshape(-1)[:int(seen_tokens)]
            self._append_pending(seq, tokens)
            self._register_pending(seq)

    def flush(self, uid: int) -> None:
        self._state_manager.flush_sequence(uid)
        self._sample_keys.pop(uid, None)
        if self._adapters is not None:
            self._adapters.unpin(uid)

    def serialize(self, save_path: str) -> None:
        """Flat param snapshot (reference :251 → flat_model_helpers)."""
        os.makedirs(save_path, exist_ok=True)
        flat, treedef = jax.tree_util.tree_flatten(self._model.params)
        np.savez(os.path.join(save_path, "params.npz"),
                 **{str(i): np.asarray(x) for i, x in enumerate(flat)})
        with open(os.path.join(save_path, "metadata.pkl"), "wb") as f:
            pickle.dump({"treedef": treedef, "config": self._model.config}, f)


def load_engine(save_path: str, builder=None, **engine_kwargs):
    """Rebuild a serving engine from an ``InferenceEngineV2.serialize`` dir
    (params.npz + metadata.pkl). ``engine_kwargs`` forward to the builder
    (engine_config, kv_cache_dtype, ...) — :func:`build_llama_engine` by
    default; pass ``disagg.build_disagg_llama`` to stand up the
    disaggregated prefill/decode pair from the same snapshot."""
    with open(os.path.join(save_path, "metadata.pkl"), "rb") as f:
        meta = pickle.load(f)
    with np.load(os.path.join(save_path, "params.npz")) as z:
        flat = [z[str(i)] for i in range(len(z.files))]
    params = jax.tree_util.tree_unflatten(meta["treedef"], flat)
    builder = builder if builder is not None else build_llama_engine
    return builder(meta["config"], params=params, **engine_kwargs)


def build_llama_engine(config: Optional[LlamaConfig] = None,
                       params=None,
                       engine_config: Optional[RaggedInferenceEngineConfig] = None,
                       seed: int = 0,
                       dtype=None,
                       kv_block_size: int = 64,
                       quantize=None,
                       kv_cache_dtype=None,
                       attn_backend: str = "auto",
                       devices=None) -> InferenceEngineV2:
    """Factory (reference ``engine_factory.py build_hf_engine``): build a
    ragged engine from a Llama config + trained params (random if None)."""
    import jax.numpy as jnp
    config = config or LlamaConfig.tiny()
    engine_config = engine_config or RaggedInferenceEngineConfig()
    mode = engine_config.quantization.quantization_mode
    if mode:
        # the reference spells WoQ via quantization_mode ('wf6af16' =
        # weight-fp6 / activation-fp16, the FP6-LLM mode) — map it onto the
        # model's quantize knob instead of accepting-and-ignoring it
        mapped = {"wf6af16": "fp6", "fp6": "fp6",
                  "int8": "int8", "int4": "int4"}.get(mode)
        if mapped is None:
            raise ValueError(f"unknown quantization_mode {mode!r}; "
                             "supported: wf6af16 (fp6), fp6, int8, int4")
        if quantize is not None and quantize != mapped:
            raise ValueError(
                f"quantize={quantize!r} conflicts with "
                f"quantization_mode={mode!r} (= {mapped!r}) — set one")
        quantize = mapped
    if params is None:
        _, params = init_llama(config, seed=seed)
    tp_cfg = engine_config.tensor_parallel
    model = RaggedLlamaModel(config, params, dtype=dtype or jnp.bfloat16,
                             kv_block_size=kv_block_size, quantize=quantize,
                             attn_backend=attn_backend,
                             kv_cache_dtype=kv_cache_dtype,
                             tp_size=tp_cfg.tp_size,
                             tp_wire_dtype=tp_cfg.tp_wire_dtype,
                             tp_wire_overrides=tp_cfg.tp_wire_overrides,
                             tp_wire_block=tp_cfg.tp_wire_block,
                             devices=devices)
    if engine_config.adapters.enabled:
        # attach BEFORE the engine warms up: the bank operand is part of
        # every traced program's signature, so it must exist before the
        # first dispatch (hot loads after that are pure value writes)
        from .adapters import AdapterRegistry
        model.set_adapter_registry(AdapterRegistry(engine_config.adapters,
                                                   model))
    return InferenceEngineV2(model, engine_config)
