"""Pallas paged-attention (blocked flash decode) for the ragged engine.

Reference capability: ``deepspeed/inference/v2/kernels/ragged_ops/
blocked_flash/`` (attention_atom.h — per-atom block-table flash over a paged
KV cache). TPU design, rather than a port of the CUDA atom machinery:

- Grid ``(seqs, query tiles, pages)``: ONE grid step streams one whole KV
  page — ALL heads — against every query head of one tile of new tokens
  (static in-kernel head unroll). The page loop is innermost so an online
  softmax (running max / sum / accumulator in VMEM scratch) streams the
  sequence's history one page at a time; no [S, L, ...] gather is ever
  materialized. The query tile (``_query_tile``) bounds what is resident:
  with the whole ``[N, H, D]`` run in one block the v5e compiler refuses
  N >= 256 at 32 heads of 128 (24 MB of scoped VMEM against a 16 MB limit).
  A tile also skips the pages that lie wholly after its last query.
- The *block table is scalar-prefetched*: the BlockSpec index map reads
  ``block_table[s, page]`` to DMA exactly the pages the sequence owns,
  straight from the full cache in HBM — the layer index is prefetched too,
  so the cache is never sliced per layer (which would copy).
- Pages past a sequence's length clamp to the previous page id: Pallas skips
  the re-fetch of an identical block, so short sequences don't pay the
  bucketed page count in bandwidth.
- GQA is native: queries arrive ``[S, N, H, D]`` with H = KV*G in kv-major
  order (the natural q head order) and each kv head's G query rows contract
  against its page slice — KV is never expanded to Q heads.
- Sliding-window (Mistral local attention) masks in-kernel and SKIPS pages
  entirely older than the window; ALiBi (BLOOM) adds the per-head slope bias
  to the scores in the ``[N, G, page]`` view (no gathers); ``attn_scale``
  overrides 1/sqrt(D) (GPT-Neo uses 1.0).

Cache layout: ``[2*layers, num_slots, kv_heads*head_dim]`` with k at row
``2l``, v at row ``2l+1`` and ``num_slots = num_pages * page_size``. This is
the SCATTER-NATIVE layout: the model's per-token KV append is a single
in-place donated scatter along the slot dim (the earlier
``[L, 2, KV, slots, D]`` layout made XLA materialize TWO transposed copies
of the entire cache per forward — 2.01 GB of HLO temps on a 1 GB cache,
measured 8/1; the 32k-context serving sweep OOMed on exactly that copy).
The kernel views it as ``[2L, num_pages, page_size, KV*D]`` (a free
middle-dim reshape) and DMAs one ``(2, page_size, KV*D)`` k+v page block
per (layer, page) — every block's minor dims are (sublane mult-of-8,
lane == array dim), the Mosaic-legal pattern; per-head slices inside the
kernel are STATIC lane offsets.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(layer_ref, bt_ref, seen_ref, lens_ref,  # scalar prefetch
                       q_ref, kv_ref, *rest,
                       page_size: int, num_kv: int, groups: int, scale: float,
                       window: Optional[int], has_alibi: bool,
                       softcap: Optional[float] = None,
                       has_scales: bool = False):
    rest = list(rest)
    scales_ref = rest.pop(0) if has_scales else None
    slopes_ref = rest.pop(0) if has_alibi else None
    o_ref, m_scr, l_scr, acc_scr = rest
    s = pl.program_id(0)
    b = pl.program_id(2)
    n_pages = pl.num_programs(2)
    D = q_ref.shape[-1]
    N = q_ref.shape[1]  # the query TILE, not the whole new-token run
    ng = N * groups

    @pl.when(b == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute position of this tile's first query; keys after the tile's
    # last query are causally dead for every row of it
    seen = seen_ref[s] + pl.program_id(1) * N
    hist_len = jnp.minimum(lens_ref[s], seen + N)

    live = b * page_size < hist_len
    if window is not None:
        # the whole page is older than the window for EVERY query row
        # (earliest query is at absolute position `seen`)
        live = live & ((b + 1) * page_size - 1 > seen - window)

    @pl.when(live)
    def _accumulate():
        # q block: [1, N, H, D]; kv block: [2, 1, page, KV*D]. Operands
        # stay in the cache dtype: the MXU fast path is bf16 x bf16 with
        # fp32 accumulation (preferred_element_type); pre-casting to fp32
        # would run the dots several-fold slower.
        q_all = q_ref[0]  # [N, H, D]
        # positional masks are shared by every head — build once per page
        key_pos1 = b * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (ng, page_size), 1)
        q_abs1 = seen + jax.lax.broadcasted_iota(
            jnp.int32, (ng, page_size), 0) // groups
        mask = (key_pos1 <= q_abs1) & (key_pos1 < hist_len)
        if window is not None:
            mask &= key_pos1 > q_abs1 - window
        for h in range(num_kv):  # static unroll: one page DMA, all heads
            q = q_all[:, h * groups:(h + 1) * groups, :].reshape(ng, D)
            k = kv_ref[0, 0, :, h * D:(h + 1) * D]  # [page, D] static slice
            v = kv_ref[1, 0, :, h * D:(h + 1) * D]
            if has_scales:
                # int8 KV: dequantize the page in-registers (per-slot-
                # vector scales, [page, 1] slice broadcast over head_dim)
                k = k.astype(jnp.bfloat16) * \
                    scales_ref[0, 0, :, h:h + 1].astype(jnp.bfloat16)
                v = v.astype(jnp.bfloat16) * \
                    scales_ref[1, 0, :, h:h + 1].astype(jnp.bfloat16)

            scores = jax.lax.dot_general(
                q, k, (((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [NG, page]
            if softcap is not None:  # Gemma-2: cap BEFORE masks/bias
                from .attention import softcap_scores
                scores = softcap_scores(scores, softcap)
            if has_alibi:
                # [N, G, page] view: slope varies over G, distance (N, page)
                s3 = scores.reshape(N, groups, page_size)
                kp3 = b * page_size + jax.lax.broadcasted_iota(
                    jnp.int32, s3.shape, 2)
                qa3 = seen + jax.lax.broadcasted_iota(jnp.int32, s3.shape, 0)
                bias = slopes_ref[0, h][None, :, None] * \
                    (kp3 - qa3).astype(jnp.float32)
                scores = (s3 + bias).reshape(ng, page_size)

            r = slice(h * ng, (h + 1) * ng)  # this head's scratch rows
            m_prev = m_scr[r]
            l_prev = l_scr[r]
            masked = jnp.where(mask, scores, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(masked, axis=-1,
                                                keepdims=True))
            # keep the running max finite so exp() never sees inf-inf
            m_new = jnp.maximum(m_new, NEG_INF)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(masked - m_new), 0.0)  # [NG, page]
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[r] = acc_scr[r] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[r] = m_new
            l_scr[r] = l_new

    @pl.when(b == n_pages - 1)
    def _finalize():
        for h in range(num_kv):
            r = slice(h * ng, (h + 1) * ng)
            l = l_scr[r]  # [NG, 1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            out = jnp.where(l > 0, acc_scr[r] / safe_l, 0.0)
            o_ref[0, :, h * groups:(h + 1) * groups, :] = \
                out.reshape(N, groups, D).astype(o_ref.dtype)


# Scoped VMEM the resident blocks may take. The v5e compiler's limit is
# 16 MB; the page blocks and in-kernel temporaries need the rest.
_RESIDENT_VMEM_BYTES = 11 << 20


def _query_tile(n: int, heads: int, head_dim: int, itemsize: int) -> int:
    """New tokens per grid step: the largest divisor of ``n`` by a power of
    two whose resident blocks fit ``_RESIDENT_VMEM_BYTES``. Per query row
    (one token, one head): the fp32 accumulator, the running max and sum
    (one value each, padded to a 128-lane fp32 row), and the q and out
    blocks, double-buffered."""
    per_row = head_dim * 4 + 2 * 128 * 4 + 2 * 2 * head_dim * itemsize
    tile = n
    while tile * heads * per_row > _RESIDENT_VMEM_BYTES and tile % 2 == 0:
        tile //= 2
    return tile


@functools.partial(jax.jit, static_argnames=("page_size", "interpret", "window",
                                             "attn_scale", "use_alibi",
                                             "softcap"))
def paged_attention(q, cache, layer, block_table, seq_seen, seq_lens,
                    *, page_size: int, interpret: bool = False,
                    window: Optional[int] = None,
                    attn_scale: Optional[float] = None,
                    use_alibi: bool = False,
                    slopes=None,
                    cache_scales=None,
                    softcap: Optional[float] = None):
    """Blocked-flash attention over a paged KV cache.

    Args:
      q: ``[S, N, H, D]`` queries (N new tokens per sequence; H = KV*G in
        the natural kv-major head order).
      cache: ``[2L, num_slots, KV*D]`` full paged cache (k row 2l, v row
        2l+1; never sliced — see module docstring for why this layout).
      layer: scalar int — which layer's pages to read.
      block_table: ``[S, B]`` int32 page ids per sequence.
      seq_seen: ``[S]`` history length before this step.
      seq_lens: ``[S]`` seen + n_new (valid key region).
      window: sliding-window size (None = global); ``attn_scale`` overrides
      1/sqrt(D); ``use_alibi`` adds BLOOM-style slope bias per query head.
      slopes: optional explicit ``[KV, G]`` ALiBi slopes (implies alibi) —
      under TP the caller passes each shard its GLOBAL-head slice (reference
      sharding/attn.py keeps head identity across shards); None derives them
      from local head indices, correct only unsharded.
      cache_scales: optional ``[2L, num_slots, KV]`` per-slot-vector
      dequant scales for an int8 ``cache`` — pages dequantize in-kernel.
    Returns:
      ``[S, N, H, D]`` in q.dtype.
    """
    S, N, H, D = q.shape
    B = block_table.shape[1]
    L2, slots, KVD = cache.shape
    KV = KVD // D
    G = H // KV
    scale = attn_scale if attn_scale is not None else 1.0 / (D ** 0.5)
    n_pages = slots // page_size
    TN = _query_tile(N, H, D, q.dtype.itemsize)
    # free reshape (middle-dim split): one (layer, page) DMA block is
    # [2, page_size, KV*D] — k and v pages for every head arrive together
    kv_pages = cache.reshape(L2, n_pages, page_size, KVD)

    def q_map(s, t, b, layer_r, bt_r, seen_r, lens_r):
        return (s, t, 0, 0)

    def kv_map(s, t, b, layer_r, bt_r, seen_r, lens_r):
        # clamp trailing pages to the last page this tile can see: identical
        # consecutive block indices skip the DMA re-fetch
        visible = jax.lax.min(lens_r[s], seen_r[s] + (t + 1) * TN)
        needed = jax.lax.max((visible + page_size - 1) // page_size, 1)
        page = bt_r[s, jax.lax.min(b, needed - 1)]
        return (layer_r[0], page, 0, 0)

    in_specs = [
        pl.BlockSpec((1, TN, H, D), q_map),
        pl.BlockSpec((2, 1, page_size, KVD), kv_map),
    ]
    inputs = [q, kv_pages]
    has_scales = cache_scales is not None
    if has_scales:
        # scales ride the SAME page lookup as their kv page (kv_map, one
        # copy of the clamp): [2L, slots, KV] viewed as [2L, n_pages, page,
        # KV] — block minor dims (page, KV) are (mult-of-8 sublane,
        # lane == array dim), Mosaic-legal
        in_specs.append(pl.BlockSpec((2, 1, page_size, KV), kv_map))
        inputs.append(cache_scales.reshape(L2, n_pages, page_size, KV))
    has_alibi = use_alibi or slopes is not None
    if has_alibi:
        if slopes is None:
            from ..models.llama import alibi_slopes
            slopes = jnp.asarray(alibi_slopes(H)).reshape(KV, G)
        # [1, KV, G] with block (1, KV, G): the last two block dims equal
        # the array dims, which Mosaic lowers for any KV/G
        in_specs.append(pl.BlockSpec((1, KV, G), lambda s, t, b, *_: (0, 0, 0)))
        inputs.append(slopes.astype(jnp.float32).reshape(1, KV, G))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, N // TN, B),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TN, H, D), q_map),
        scratch_shapes=[
            # rows grouped kv-head-major: head h owns [h*NG, (h+1)*NG)
            pltpu.VMEM((TN * H, 1), jnp.float32),  # running max
            pltpu.VMEM((TN * H, 1), jnp.float32),  # running sum
            pltpu.VMEM((TN * H, D), jnp.float32),  # accumulator
        ],
    )

    kernel = functools.partial(_paged_attn_kernel, page_size=page_size,
                               num_kv=KV, groups=G, scale=scale,
                               window=window, softcap=softcap,
                               has_alibi=has_alibi, has_scales=has_scales)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, N, H, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray([layer], jnp.int32), block_table.astype(jnp.int32),
      seq_seen.astype(jnp.int32), seq_lens.astype(jnp.int32), *inputs)


def paged_attention_reference(q, cache, layer, block_table, seq_seen, seq_lens,
                              *, page_size: int, window: Optional[int] = None,
                              attn_scale: Optional[float] = None,
                              use_alibi: bool = False,
                              slopes=None,
                              cache_scales=None,
                              softcap: Optional[float] = None):
    """Dense-gather XLA reference (the round-1 path) for numerics tests."""
    S, N, H, D = q.shape
    B = block_table.shape[1]
    L = B * page_size
    KV = cache.shape[-1] // D
    G = H // KV
    scale = attn_scale if attn_scale is not None else 1.0 / (D ** 0.5)
    j = jnp.arange(L, dtype=jnp.int32)
    slot_grid = block_table[:, j // page_size] * page_size + j % page_size
    # cache [2L, slots, KV*D]: gather the window rows, unfold the head dim
    k_h = cache[2 * layer][slot_grid].reshape(S, L, KV, D)    # [S, L, KV, D]
    v_h = cache[2 * layer + 1][slot_grid].reshape(S, L, KV, D)
    if cache_scales is not None:  # int8 cache: dequant the gathered window
        k_sc = cache_scales[2 * layer][slot_grid]             # [S, L, KV]
        v_sc = cache_scales[2 * layer + 1][slot_grid]
        k_h = k_h.astype(jnp.float32) * k_sc[..., None].astype(jnp.float32)
        v_h = v_h.astype(jnp.float32) * v_sc[..., None].astype(jnp.float32)
    k_h = jnp.moveaxis(k_h, 2, 1).astype(jnp.float32)          # [S, KV, L, D]
    v_h = jnp.moveaxis(v_h, 2, 1).astype(jnp.float32)
    qf = q.reshape(S, N, KV, G, D).astype(jnp.float32)
    scores = jnp.einsum("snkgd,skld->snkgl", qf, k_h) * scale
    if softcap is not None:
        from .attention import softcap_scores
        scores = softcap_scores(scores, softcap)
    key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]
    q_abs = seq_seen[:, None] + jnp.arange(N, dtype=jnp.int32)[None, :]
    mask = (key_pos <= q_abs[:, :, None]) & (key_pos < seq_lens[:, None, None])
    if window is not None:
        mask &= key_pos > q_abs[:, :, None] - window
    if use_alibi or slopes is not None:
        if slopes is None:
            from ..models.llama import alibi_slopes
            slopes = jnp.asarray(alibi_slopes(H)).reshape(KV, G)
        dist = (key_pos[:, :, None, None, :]
                - q_abs[:, :, None, None, None]).astype(jnp.float32)
        scores = scores + slopes[None, None, :, :, None].astype(jnp.float32) * dist
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    any_visible = mask.any(-1)[:, :, None, None, None]
    out = jnp.einsum("snkgl,skld->snkgd", probs, v_h)
    return jnp.where(any_visible, out, 0.0).reshape(S, N, H, D).astype(q.dtype)


from .registry import registry  # noqa: E402

registry.register("paged_attention", "pallas", True,
                  "ragged blocked-flash decode over paged KV (block tables, "
                  "window/ALiBi/scale in-kernel; reference ragged_ops)")
