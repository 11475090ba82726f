"""Persistent state manager for ragged serving.

Reference: ``deepspeed/inference/v2/ragged/ragged_manager.py:19 DSStateManager``
— tracks live sequences, owns the block allocator + paged KV cache.
"""

from typing import Dict, Optional

from ..config_v2 import DSStateManagerConfig, KVCacheConfig
from .blocked_allocator import BlockedAllocator
from .kv_cache import BlockedKVCache
from .prefix_cache import PrefixKVCache
from .sequence_descriptor import DSSequenceDescriptor


class DSStateManager:

    def __init__(self,
                 config: DSStateManagerConfig,
                 kv_config: KVCacheConfig,
                 num_blocks: Optional[int] = None,
                 enable_prefix_caching: bool = False):
        self._config = config
        self._kv_config = kv_config
        if num_blocks is None:
            num_blocks = self._size_from_memory_config(config, kv_config)
        self._allocator = BlockedAllocator(num_blocks)
        self._kv_cache = BlockedKVCache(kv_config, num_blocks)
        self._seqs: Dict[int, DSSequenceDescriptor] = {}
        self.prefix_cache = (PrefixKVCache(kv_config.block_size)
                             if enable_prefix_caching else None)

    @staticmethod
    def _size_from_memory_config(config: DSStateManagerConfig,
                                 kv_config: KVCacheConfig) -> int:
        """Reference memory_config sizing (manager_configs.py): 'allocate' =
        memory_config_size IS the block count; 'reserve' = that fraction of
        free HBM becomes KV blocks. Reserve engages only on a real TPU
        (PJRT memory stats), where a device that reports no free memory is
        an error; elsewhere the deterministic default keeps CPU tests from
        sizing a cache off host RAM."""
        if config.memory_config_mode == "allocate":
            return max(1, int(config.memory_config_size))
        from ....ops.registry import on_tpu
        if not on_tpu():
            return max(64, config.max_tracked_sequences)
        from ....accelerator import get_accelerator
        from .kv_cache import estimate_kv_blocks
        free = get_accelerator().available_memory()
        if free <= 0:
            raise RuntimeError(
                f"cannot size the KV pool: the device reports {free} free "
                f"bytes (memory stats missing, or the weights fill it)")
        return estimate_kv_blocks(kv_config, free, config.memory_config_size)

    # ---- sequence tracking (reference ragged_manager.py:96-160) ----

    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    @property
    def tracked_sequences(self) -> Dict[int, DSSequenceDescriptor]:
        return self._seqs

    def get_sequence(self, uid: int) -> Optional[DSSequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        seq = self._seqs.get(uid)
        if seq is not None:
            return seq
        return self._create_sequence(uid)

    def _create_sequence(self, uid: int) -> DSSequenceDescriptor:
        if uid in self._seqs:
            raise ValueError(f"Sequence {uid} already exists")
        if len(self._seqs) >= self._config.max_tracked_sequences:
            raise RuntimeError("max_tracked_sequences exceeded")
        max_blocks = (self._config.max_context + self._kv_config.block_size - 1) \
            // self._kv_config.block_size
        seq = DSSequenceDescriptor(uid, max_blocks)
        self._seqs[uid] = seq
        return seq

    def flush_sequence(self, uid: int) -> None:
        """Free a sequence's KV blocks + tracking (reference :147). With
        prefix caching on, adopted blocks drop their reference and
        registered blocks transfer ownership to the cache instead of
        returning to the allocator."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            return
        blocks = seq.kv_blocks
        if self.prefix_cache is not None:
            adopted = set(getattr(seq, "adopted_blocks", ()))
            self.prefix_cache.release([b for b in blocks if b in adopted])
            self._register_tail(seq, blocks, adopted)
            kept = set(self.prefix_cache.take_ownership(
                [b for b in blocks if b not in adopted]))
            blocks = [b for b in blocks if b not in adopted and b not in kept]
        if blocks:
            self._allocator.free(blocks)

    def _register_tail(self, seq, blocks, adopted) -> None:
        """At flush, hand the sequence's sub-block TAIL to the radix cache
        as a partial (fork-source) entry: the common "system prompt shorter
        than a block" case would otherwise evaporate on every flush. Runs
        before take_ownership so the tail block transfers with the rest.
        The seen_tokens consistency check skips sequences whose staged
        tail no longer reflects block contents (mid-rollback flushes)."""
        pend = getattr(seq, "pending_tokens", None)
        start = int(getattr(seq, "chain_blocks", 0))
        bs = self.block_size
        if (pend is None or not 0 < len(pend) < bs or start >= len(blocks)
                or seq.seen_tokens != start * bs + len(pend)):
            return
        tail_block = blocks[start]
        if tail_block in adopted:
            return
        self.prefix_cache.register_tail(
            getattr(seq, "chain_key", None), pend, tail_block)

    # ---- KV accounting ----

    @property
    def free_blocks(self) -> int:
        """Allocator-free plus what prefix-cache eviction could reclaim —
        the scheduling view (allocate_blocks evicts on demand)."""
        n = self._allocator.free_blocks
        if self.prefix_cache is not None:
            n += self.prefix_cache.reclaimable_blocks
        return n

    @property
    def kv_cache(self) -> BlockedKVCache:
        return self._kv_cache

    @property
    def block_size(self) -> int:
        return self._kv_config.block_size

    def reset_prefix_cache(self) -> None:
        """Invalidate all cached prefixes (the hybrid engine's weight swap:
        KV content computed under old weights must never be adopted).

        Live sequences are flushed FIRST: their entire KV history is
        old-weight state too (continuing them post-swap would mix weights),
        and flushing through the normal path settles every refcount and
        chain bookkeeping — so clear() only ever frees blocks with no live
        adopters, and no stale chain_key can re-register contaminated KV
        into the fresh cache."""
        if self.prefix_cache is None:
            return
        for uid in list(self._seqs):
            self.flush_sequence(uid)
        freed = self.prefix_cache.clear()
        if freed:
            self._allocator.free(freed)

    def allocate_blocks(self, n_blocks: int):
        if (self.prefix_cache is not None
                and n_blocks > self._allocator.free_blocks):
            # evict LRU cached prefixes back to the allocator on demand
            evicted = self.prefix_cache.evict(
                n_blocks - self._allocator.free_blocks)
            if evicted:
                self._allocator.free(evicted)
            if n_blocks > self._allocator.free_blocks:
                # free_blocks promised space eviction couldn't deliver (or
                # the scheduler was raced) — surface the catchable scheduling
                # error, not the allocator's raw ValueError, so generate()'s
                # evict-and-replay recovery can engage
                from ..scheduling_utils import SchedulingError, SchedulingResult
                raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
        return self._allocator.allocate(n_blocks)

    def release_blocks(self, blocks) -> None:
        """Return individual blocks mid-sequence (trailing-window release,
        model.maybe_free_kv) without touching sequence tracking."""
        if len(blocks):
            self._allocator.free(blocks)
