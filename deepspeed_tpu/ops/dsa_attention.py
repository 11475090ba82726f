"""Learned sparse attention as it trains (DeepSeek Sparse Attention): an
indexer scores every earlier token for each query, the query attends the
``topk`` tokens of largest score, and all heads of a query share that choice.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])     s <= t, float32
    S_t     = the topk keys of largest I[t, .] (all of them when t < topk;
              ties to the lower index)
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, g(h)] * scale) v[s, g(h)]

The choice passes no gradient: q, k and v get theirs through the chosen
pairs, the indexer's operands none.

On one TPU device the indexer's scores are made ONCE a layer and step, by
``dsa_index``, which writes its choice out as a packed bit mask; the three
attention kernels read the mask (``dsa_attention``):

``dsa_index``  a query tile's scores over its causal keys, held in VMEM as
    order-preserving int32 keys ([key tiles, BQ, BK]: 16 MiB at 32,768 keys
    and 128 queries; never in HBM), then each row's ``topk``-th largest by
    bisection over the key's 32 bits (a count of ``key >= candidate`` a
    bit): the threshold ``tau``. Where more keys than the row may still take
    equal ``tau`` (float32 scores do collide at 32k keys a row), a second
    bisection over the position finds ``tie``, the last position a key equal
    to ``tau`` is taken at, so that exactly ``topk`` are chosen. Then one more
    walk over the keys it still holds writes the choice ``I > tau | (I == tau
    & s <= tie)`` as words of 32 queries (``mask_layout``: T * T / 8 bytes a
    row of the batch, 134 MB at 32,768; no [T, T] array), and with it the
    pairs each row chose and its smallest chosen score.
``dsa_fwd``  the flash sweep over the causal key tiles under the mask: a
    tile's words expand by a sublane broadcast, a shift and an AND. A grid
    step holds all KV heads of a (query, key) tile, so the words are expanded
    once for them; a tile whose words are all zero skips its matmuls.
``dsa_bwd``  the backward under the same words, rebuilt from the saved
    log-sum-exp, on transposed tiles as ``flash_dkdv`` (the expanded tile is
    transposed: one orientation is stored): dQ, dK and dV from ONE walk over
    a KV head's live (query, key) tiles, query-major, five products a tile.
    dQ is held a query tile at a time; the head's float32 dK and dV of ALL
    keys stay in VMEM through its walk (2 * T * D * 4 bytes: 32 MiB at 32,768
    keys of 128, an eighth of its dQ at a group of 8) and leave in the last
    query tile's sweep. The walk is a table of the live tiles in SMEM: no
    step is spent past the causal diagonal.
``dsa_bwd_dq``, ``dsa_bwd_dkdv``  the same backward as a pair, seven
    products a tile, which holds no accumulator longer than a tile: where
    dK and dV pass the VMEM cap (65,536 keys at head 128;
    ``kernel_dispatch.resolve_dsa_bwd``) and under ``bwd="pair"``. Its
    gradients are the one walk's bit for bit.
``dsa_mask``  the words again from ``tau`` and ``tie``: one pass of scores by
    ``dsa_index``'s code at its tile shape (bit-equal), no selection. Only in
    the backward of a recomputed layer that did not keep its mask.

Across a recomputation the thresholds are kept by name with the kernel's
output and log-sum-exp (``ops/remat.py``: ``DSA_CHOICE``), and the mask is a
priced candidate (``DSA_MASK``, the walk's first: a scoring pass returned for
T * T / 8 bytes): a layer that keeps it hands it to its backward, which then
needs neither the thresholds nor the indexer's operands; one that does not
calls ``dsa_mask`` once. Either way the selection runs once a step.

The attention kernels compute every causal pair's q . k of a tile that holds
a chosen pair: with a choice scattered over the sequence that is all of them
(a late query keeps 2,048 of 32,768 keys, so a key is chosen by none of a
tile's 128 queries with chance 0.9375^128: the union of a query tile's keys
is every key). What they save is HBM (no [T, T] array) and, for a choice that
clusters, the skipped tiles.

Anywhere else (a CPU, a mesh of more than one device) ``dsa_attention`` is
the dense form by hand, a block of queries at a time: scores, ``lax.top_k``,
a mask; correct and unmeasured.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import remat
from .attention import (LSE_MASKED, NEG_INF, RESIDUAL_NAMES, STAT_LANES,
                        _compiler_params, _lanes)
from .registry import use_pallas

INT_MIN = -2**31
INT_MAX = 2**31 - 1


def index_scores(qi, ki, w):
    """I[b, t, s] in float32, every pair: qi [B, Tq, HI, DI], ki [B, Tk, DI],
    w [B, Tq, HI] -> [B, Tq, Tk]."""
    x = jnp.einsum("bqhd,bsd->bqhs", qi, ki, preferred_element_type=jnp.float32)
    return jnp.einsum("bqhs,bqh->bqs", jnp.maximum(x, 0.0), w.astype(jnp.float32))


def chosen_keys(qi, ki, w, kth, positions, topk: int):
    """The keys some queries chose, rebuilt from what the layer sows under
    ``dsa_choice``: qi [B, n, HI, DI], w [B, n, HI] and kth [B, n] (each
    query's smallest chosen score) of the queries at ``positions`` [B, n], ki
    [B, T, DI] -> [B, n, T] bool. Key ``s <= t`` is chosen where ``I[t, s] >
    kth[t]``, and of those equal to it the lowest positions, ``min(t + 1,
    topk)`` in all; equal to the last place but one, since the scores are
    made again here by another program than the one ``kth`` came from. A
    check's tool, not a step's."""
    scores = index_scores(qi, ki, w)
    T = scores.shape[-1]
    causal = jnp.arange(T)[None, None, :] <= positions[:, :, None]
    above = causal & (scores > kth[..., None])
    equal = causal & ~above & jnp.isclose(scores, kth[..., None], rtol=1e-5, atol=1e-7)
    need = jnp.minimum(positions + 1, topk)[..., None] - above.sum(-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= need))


def _query_block(seq: int, cap: int) -> int:
    b = min(cap, seq)
    while seq % b:
        b -= 1
    return b


def dense_dsa(q, k, v, qi, ki, w, topk: int, scale: float, block_q: int = 256):
    """The layer's equations by hand, a block of queries at a time, so that
    [block, T] and never [T, T] is held: -> (o [B, T, H, D], chosen [B, T]
    int32: pairs a row chose, kth [B, T] float32: its smallest chosen
    score). ``lax.top_k`` puts the lower index first among equals."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = _query_block(T, block_q)
    kk = min(topk, T)
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))

    def block(args):
        q_b, qi_b, w_b, start = args
        t = start + jnp.arange(bq)
        causal = jnp.arange(T)[None, :] <= t[:, None]                 # [bq, T]
        sc = jnp.where(causal[None], index_scores(qi_b, ki, w_b), -jnp.inf)
        with jax.named_scope("ds.dsa.select"):
            _, idx = jax.lax.top_k(sc, kk)
            chosen = jnp.zeros((B, bq, T), bool).at[
                jnp.arange(B)[:, None, None], jnp.arange(bq)[None, :, None],
                idx].set(True)
            chosen = chosen & causal[None]
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_b.reshape(B, bq, KV, G, D),
                       k).astype(jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, NEG_INF), axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
        return (o.reshape(B, bq, H, v.shape[-1]), chosen.sum(-1).astype(jnp.int32),
                jnp.where(chosen, sc, jnp.inf).min(-1))

    def blocks(a):      # [B, T, ...] -> [T / bq, B, bq, ...]
        return jnp.moveaxis(a.reshape(B, T // bq, bq, *a.shape[2:]), 1, 0)

    o, chosen, kth = jax.lax.map(
        block, (blocks(q), blocks(qi), blocks(w), jnp.arange(0, T, bq)))

    def whole(a):
        return jnp.moveaxis(a, 0, 1).reshape(B, T, *a.shape[3:])

    return whole(o), whole(chosen), whole(kth)


# ---------------------------------------------------------------------------
# a tile's scores and what is folded from them
# ---------------------------------------------------------------------------


def _sortable(s):
    """float32 -> int32 of the same order (an involution on the bits)."""
    b = jax.lax.bitcast_convert_type(s, jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(INT_MAX))


def sortable_to_float(key):
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & jnp.int32(INT_MAX)), jnp.float32)


def _tile_keys(qi_ref, ki, w):
    """The indexer's scores of one tile as sortable keys [BQ, BK]. qi_ref
    [1, HI, BQ, DI]; ki [BK, DI]; w [BQ, HI], float32. The heads are summed
    in their order, in float32, ``dsa_index`` and ``dsa_mask`` alike (at one
    tile shape the two are bit-equal); a sum of ``w * 0`` terms that came
    out -0.0 is made +0.0, which is what a matmul's sum gives and what sorts
    equal to it."""
    acc = None
    for j in range(qi_ref.shape[1]):
        x = jax.lax.dot_general(qi_ref[0, j], ki, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(x, 0.0)
        acc = term if acc is None else acc + term
    return _sortable(jnp.where(acc == 0.0, 0.0, acc))


def _chosen(key, tau, tie, q_pos, k_pos):
    return (k_pos <= q_pos) & ((key > tau) | ((key == tau) & (k_pos <= tie)))


def _last_key_tile(i, block_q, block_k):
    return (i * block_q + block_q - 1) // block_k


def _positions(i, j, block_q, block_k):
    shape = (block_q, block_k)
    return (i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _lane_fold(x, op, empty):
    """[rows, n * 128] -> [rows, 128]: the lane tiles folded by ``op`` (``jnp.
    add``, ``jnp.minimum``); a short test tile: its fold in lane 0 and
    ``empty``, ``op``'s neutral value, in the others."""
    n = x.shape[1]
    if n % STAT_LANES:
        fold = x.sum if op is jnp.add else x.min
        lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], STAT_LANES), 1)
        return jnp.where(lane == 0, fold(axis=1, keepdims=True), empty)
    out = x[:, :STAT_LANES]
    for c in range(1, n // STAT_LANES):
        out = op(out, x[:, c * STAT_LANES:(c + 1) * STAT_LANES])
    return out


def _lane_sum(x):
    return _lane_fold(x, jnp.add, 0)


def _row_total(x):
    """[rows, 128] partial sums -> the row's total, lane-replicated."""
    return jnp.broadcast_to(x.sum(axis=1, keepdims=True), x.shape)


# ---------------------------------------------------------------------------
# the choice as a bit mask
# ---------------------------------------------------------------------------
#
# mask [B, key tiles, T / bits, BK] int32: bit ``b`` of word [., c, r, s] says
# whether query ``bits * r + b`` chose key ``c * BK + s``. A word packs the
# queries, the sublanes of the forward's [BQ, BK] tile, so that a tile's
# words [BQ / bits, BK] expand by a sublane broadcast, a shift and an AND; the
# backward pair, which walks transposed tiles, transposes the expanded tile.
# 32 queries a word where the query tile allows (T * T / 8 bytes a row of the
# batch); a query tile of 128 is 4 word rows, half of the 8 a block of int32
# needs, so a block then holds two query tiles' rows and a kernel takes its
# own (``_word_rows``). The layout is the call's: every kernel of one
# ``dsa_attention`` has the same tiles.


def mask_layout(seq: int, block_q: int):
    """-> (bits of a word, word rows a query tile, word rows a block)."""
    bits = 32
    while block_q % bits:
        bits //= 2
    rows, total = block_q // bits, seq // bits
    if rows % 8 == 0 or rows == total:
        return bits, rows, rows
    return bits, rows, 8 if 8 % rows == 0 and total % 8 == 0 else total


def _mask_shape(rows: int, seq: int, blocks):
    block_q, block_k, _, num_k = _tiles(seq, blocks)
    return rows, num_k, seq // mask_layout(seq, block_q)[0], block_k


def _word_rows(i, mask_ref, block_q, bits):
    """Where query tile ``i``'s word rows start in its block of ``mask_ref``
    [1, key tiles, rows, BK], and how many they are."""
    rows, block = block_q // bits, mask_ref.shape[2]
    return (0 if block == rows else (i * rows) % block), rows


def _put_words(mask_ref, c, i, chosen, bits):
    """Key tile ``c`` of query tile ``i``'s choice [BQ, BK] into its words.
    Mosaic takes no sublane offset it cannot prove a multiple of 8: the
    block's rows are read, the tile's own replaced by a select, and written."""
    at, rows = _word_rows(i, mask_ref, chosen.shape[0], bits)
    bit = jax.lax.broadcasted_iota(jnp.int32, chosen.shape, 0) & (bits - 1)
    shifted = jax.lax.shift_left(chosen.astype(jnp.int32), bit)
    # the bits are disjoint: their sum is their OR
    words = [shifted[r * bits:(r + 1) * bits].sum(axis=0, keepdims=True)
             for r in range(rows)]
    block = mask_ref[0, c]
    row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    for r, word in enumerate(words):
        block = jnp.where(row == at + r, jnp.broadcast_to(word, block.shape), block)
    mask_ref[0, c] = block


def _tile_words(mask_ref, i, block_q, bits):
    """Query tile ``i``'s words [BQ / bits, BK] of the key tile ``mask_ref``
    [1, 1, rows, BK] holds: of the tiles that share the block, its own."""
    at, rows = _word_rows(i, mask_ref, block_q, bits)
    block = mask_ref[0, 0]
    words = block[:rows]
    for n in range(1, block.shape[0] // rows):
        words = jnp.where(at == n * rows, block[n * rows:(n + 1) * rows], words)
    return words


def _expand(words, bits):
    """Words [BQ / bits, BK] -> the choice [BQ, BK] as int32 0 / 1."""
    rows, block_k = words.shape
    wide = [jnp.broadcast_to(words[r:r + 1], (bits, block_k)) for r in range(rows)]
    wide = jnp.concatenate(wide, axis=0) if rows > 1 else wide[0]
    bit = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 0) & (bits - 1)
    return jax.lax.shift_right_logical(wide, bit) & 1


def _clear_first(mask_ref, i, j, block_q, bits):
    """A block's first visit zeroes it: the words of a key tile past a query
    tile's causal range are never written (and never read)."""
    at, _ = _word_rows(i, mask_ref, block_q, bits)

    @pl.when((j == 0) & (at == 0))
    def _clear():
        mask_ref[...] = jnp.zeros_like(mask_ref)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _index_kernel(qi_ref, ki_ref, w_ref, tau_ref, tie_ref, cnt_ref, kth_ref, mask_ref,
                  keys, *, topk, block_q, block_k, pos_bits, bits):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_key_tile(i, block_q, block_k)
    _clear_first(mask_ref, i, j, block_q, bits)

    @pl.when(j <= last)
    def _score():
        key = _tile_keys(qi_ref, ki_ref[0], w_ref[0])
        q_pos, k_pos = _positions(i, j, block_q, block_k)
        keys[j] = jnp.where(k_pos <= q_pos, key, jnp.int32(INT_MIN))

    @pl.when(j == last)
    def _select():
        shape = (block_q, STAT_LANES)

        def count(pred):
            """The row's keys of its live tiles with ``pred(tile, positions)``."""
            def body(c, acc):
                k_pos = c * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                return acc + _lane_sum(pred(keys[c], k_pos).astype(jnp.int32))
            return _row_total(jax.lax.fori_loop(0, last + 1, body,
                                                jnp.zeros(shape, jnp.int32)))

        def wide(x):
            return _lanes(x, block_k)

        # the topk-th largest key, bit by bit from the top, in the unsigned
        # order (the signed key with its sign bit flipped): a causally
        # masked entry is unsigned 0 and never counted
        def value_bit(b, prefix):
            cand = prefix | jnp.left_shift(jnp.int32(1), 31 - b)
            signed = cand ^ jnp.int32(INT_MIN)
            n = count(lambda tile, _: tile >= wide(signed))
            return jnp.where(n >= topk, cand, prefix)

        prefix = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(shape, jnp.int32))
        tau = prefix ^ jnp.int32(INT_MIN)
        tau_ref[0] = tau[:, :1]
        tie_ref[0] = jnp.full((block_q, 1), INT_MAX, jnp.int32)
        # a row of fewer than topk candidates (prefix 0) takes them all
        above = count(lambda tile, _: tile > wide(tau))
        equal = count(lambda tile, _: tile == wide(tau))
        need = topk - above
        tied = (prefix != 0) & (equal > need)

        @pl.when(jnp.max(tied.astype(jnp.int32)) > 0)
        def _ties():
            # the largest position X with fewer than ``need`` keys equal to
            # tau before it: the need-th of them sits at X
            def pos_bit(b, x):
                cand = x | jnp.left_shift(jnp.int32(1), pos_bits - 1 - b)
                n = count(lambda tile, k_pos: (tile == wide(tau))
                          & (k_pos < wide(cand)))
                return jnp.where(n < need, cand, x)

            x = jax.lax.fori_loop(0, pos_bits, pos_bit, jnp.zeros(shape, jnp.int32))
            tie_ref[0] = jnp.where(tied, x, jnp.int32(INT_MAX))[:, :1]

        # the choice itself, once more over the row's keys while they are
        # here: its words, the pairs the row chose and its smallest chosen key
        tau_col, tie_col = tau_ref[0], tie_ref[0]

        def put(c, carry):
            n, low = carry
            key = keys[c]
            q_pos, k_pos = _positions(i, c, block_q, block_k)
            chosen = _chosen(key, tau_col, tie_col, q_pos, k_pos)
            _put_words(mask_ref, c, i, chosen, bits)
            low = jnp.minimum(low, _lane_fold(
                jnp.where(chosen, key, jnp.int32(INT_MAX)), jnp.minimum, INT_MAX))
            return n + _lane_sum(chosen.astype(jnp.int32)), low

        n, low = jax.lax.fori_loop(
            0, last + 1, put, (jnp.zeros(shape, jnp.int32),
                               jnp.full(shape, INT_MAX, jnp.int32)))
        cnt_ref[0] = n.sum(axis=1, keepdims=True)
        kth_ref[0] = sortable_to_float(low.min(axis=1, keepdims=True))


def _mask_kernel(qi_ref, ki_ref, w_ref, tau_ref, tie_ref, mask_ref, *, block_q,
                 block_k, bits):
    """The words again from thresholds that are known: one pass of scores."""
    i, j = pl.program_id(1), pl.program_id(2)
    _clear_first(mask_ref, i, j, block_q, bits)

    @pl.when(j <= _last_key_tile(i, block_q, block_k))
    def _live():
        key = _tile_keys(qi_ref, ki_ref[0], w_ref[0])
        q_pos, k_pos = _positions(i, j, block_q, block_k)
        _put_words(mask_ref, j, i, _chosen(key, tau_ref[0], tie_ref[0], q_pos, k_pos),
                   bits)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc, m_s, l_s,
                *, scale, block_q, block_k, num_k, bits):
    i, j = pl.program_id(1), pl.program_id(2)
    kv, g, bq, d = q_ref.shape[1:]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    @pl.when(j <= _last_key_tile(i, block_q, block_k))
    def _live():
        words = _tile_words(mask_ref, i, block_q, bits)

        @pl.when(jnp.max((words != 0).astype(jnp.int32)) > 0)
        def _attend():
            chosen = _expand(words, bits) != 0
            rows = jnp.concatenate([chosen] * g, axis=0) if g > 1 else chosen
            for h in range(kv):
                q = q_ref[0, h].reshape(g * bq, d)
                k, v = k_ref[0, h], v_ref[0, h]
                s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                        preferred_element_type=jnp.float32) * scale
                s = jnp.where(rows, s, NEG_INF)
                m_prev, l_prev = m_s[h], l_s[h]
                m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                m_safe = jnp.where(m_cur <= NEG_INF, 0.0, m_cur)
                # a masked score's exp underflows to exactly 0 (m_safe is finite)
                p = jnp.exp(s - _lanes(m_safe, block_k))
                corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - m_safe))
                l_s[h] = l_prev * corr + p.sum(axis=-1, keepdims=True)
                pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)
                acc[h] = acc[h] * _lanes(corr, d) + pv
                m_s[h] = m_cur

    @pl.when(j == num_k - 1)
    def _finalize():
        for h in range(kv):
            l = l_s[h]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc[h] / _lanes(safe_l, d)).reshape(g, bq, d).astype(o_ref.dtype)
            m_safe = jnp.where(m_s[h] <= NEG_INF, 0.0, m_s[h])
            lse = jnp.where(l == 0.0, LSE_MASKED, m_safe + jnp.log(safe_l))
            # as a lane-dense ROW, the form the backward reads (a column
            # would be padded 128 times in HBM): one transpose a query tile
            lse_ref[0, h, 0] = lse.T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, dq_ref,
               dq_acc, *, scale, block_q, block_k, num_k, bits):
    """dQ of one query tile over a sweep of its causal key tiles, on
    TRANSPOSED tiles as ``_dkdv_kernel`` (what belongs to a query enters as a
    lane-dense row, where a column would be padded 128 times in HBM): dQ is
    ``ds^T`` contracted over its first dimension, one transposed tile a
    step, as the fused flash backward takes it."""
    i, j = pl.program_id(1), pl.program_id(2)
    kv, g, bq, d = q_ref.shape[1:]

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(j <= _last_key_tile(i, block_q, block_k))
    def _live():
        _tile_grads(i, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                    scale, bits, dq_acc=dq_acc)

    @pl.when(j == num_k - 1)
    def _finalize():
        for h in range(kv):
            dq_ref[0, h] = dq_acc[h].reshape(g, bq, d).astype(dq_ref.dtype)


def _tile_grads(i, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, scale,
                bits, dq_acc=None, dk_acc=None, dv_acc=None, key_tile=None):
    """One transposed tile ([BK, G * BQ] = k . q^T a KV head) of the backward
    under the choice's mask (query tile ``i``'s words, expanded and
    transposed), added to the accumulators given: dQ, dK and dV, or all
    three. dK and dV are the KV head's slot of a [KV, BK, D] accumulator, or
    slot ``key_tile`` of one head's [key tiles, BK, D] (``_bwd_kernel``).
    Nothing is multiplied where the tile holds no chosen pair."""
    kv, g, bq, d = q_ref.shape[1:]
    words = _tile_words(mask_ref, i, bq, bits)

    @pl.when(jnp.max((words != 0).astype(jnp.int32)) > 0)
    def _grads():
        chosen = _expand(words, bits).T != 0
        cols = jnp.concatenate([chosen] * g, axis=1) if g > 1 else chosen
        for h in range(kv):
            at = h if key_tile is None else key_tile
            q = q_ref[0, h].reshape(g * bq, d)
            k, v = k_ref[0, h], v_ref[0, h]
            do = do_ref[0, h].reshape(g * bq, d)
            lse = lse_ref[0, h, 0]          # [1, G * BQ]
            delta = delta_ref[0, h, 0]
            s = jax.lax.dot_general(k, q, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            p = jnp.where(cols, jnp.exp(s - lse), 0.0)
            if dv_acc is not None:
                dv_acc[at] += jax.lax.dot_general(p.astype(do.dtype), do,
                                                  (((1, ), (0, )), ((), ())),
                                                  preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, (((1, ), (1, )), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            if dk_acc is not None:
                dk_acc[at] += jax.lax.dot_general(ds, q, (((1, ), (0, )), ((), ())),
                                                  preferred_element_type=jnp.float32)
            if dq_acc is not None:
                dq_acc[h] += jax.lax.dot_general(ds, k, (((0, ), (0, )), ((), ())),
                                                 preferred_element_type=jnp.float32)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, dk_ref,
                 dv_ref, dk_acc, dv_acc, *, scale, block_q, block_k, num_q, bits):
    """dK and dV of one key tile over a sweep of the query tiles at or past
    it, on TRANSPOSED tiles ([BK, G * BQ] = k . q^T), as ``flash_dkdv``."""
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j <= _last_key_tile(i, block_q, block_k))
    def _live():
        _tile_grads(i, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                    scale, bits, dk_acc=dk_acc, dv_acc=dv_acc)

    @pl.when(i == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale, block_q,
                block_k, num_q, bits):
    """dQ, dK and dV of one KV head from ONE walk over its live (query, key)
    tiles, query-major, each visited once (``qt_ref`` / ``kt_ref``: the
    walk's tiles, a table in SMEM): a query tile's dQ accumulates over its
    sweep and leaves when it ends; the head's dK and dV of ALL keys
    accumulate in float32 in VMEM, [key tiles, BK, D] each, and the last
    query tile's sweep, in which every key tile is live, writes them out.
    The sums run in the pair's order: a tile's five products once, its words
    expanded and transposed once."""
    step = pl.program_id(2)
    i, j = qt_ref[step], kt_ref[step]
    g, bq, d = q_ref.shape[2:]

    @pl.when(step == 0)
    def _init_keys():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    _tile_grads(i, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, scale,
                bits, dq_acc=dq_acc, dk_acc=dk_acc, dv_acc=dv_acc, key_tile=j)

    @pl.when(j == _last_key_tile(i, block_q, block_k))
    def _finalize():
        dq_ref[0, 0] = dq_acc[0].reshape(g, bq, d).astype(dq_ref.dtype)

    @pl.when(i == num_q - 1)
    def _finalize_keys():
        dk_ref[0, 0] = dk_acc[j].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[j].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------


def _group(q, k, v):
    """q [B, T, H, D] -> [B, KV, G, T, D]; k, v [B, T, KV, D] -> [B, KV, T, D]."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, T, KV, H // KV, D).transpose(0, 2, 3, 1, 4)
    return qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _ungroup(x):
    """[B, KV, G, T, D] -> [B, T, H, D]."""
    B, KV, G, T, D = x.shape
    return x.transpose(0, 3, 1, 2, 4).reshape(B, T, KV * G, D)


def _tiles(T, blocks):
    block_q, block_k = (min(b, T) for b in blocks)
    assert T % block_q == 0 and T % block_k == 0, (T, blocks)
    return block_q, block_k, T // block_q, T // block_k


def _live_key_map(block_q, block_k):
    """Index-map clamp of the swept key tile to the causal range of query
    tile ``i``: a dead step names the tile already there."""
    return lambda j, i: jnp.minimum(j, _last_key_tile(i, block_q, block_k))


def _index_specs(T, HI, DI, blocks):
    """What ``dsa_index`` and ``dsa_mask`` share: the blocks of qi
    (transposed to [B, HI, T, DI]), ki and w, a [B, T, 1] column's, and the
    mask's whole row of key tiles, which stays in VMEM over the query tiles
    that share its word rows."""
    block_q, block_k, _, num_k = _tiles(T, blocks)
    live = _live_key_map(block_q, block_k)
    _, rows, block = mask_layout(T, block_q)
    return ([pl.BlockSpec((1, HI, block_q, DI), lambda b, i, j: (b, 0, i, 0)),
             pl.BlockSpec((1, block_k, DI), lambda b, i, j: (b, live(j, i), 0)),
             pl.BlockSpec((1, block_q, HI), lambda b, i, j: (b, i, 0))],
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, num_k, block, block_k),
                         lambda b, i, j: (b, 0, (i * rows) // block, 0)))


def dsa_index(qi, ki, w, topk: int, blocks, interpret: bool = False):
    """-> (tau, tie [B, T] int32: each row's threshold as a sortable key and
    the last position a key equal to it is taken at; chosen [B, T] int32: the
    pairs the row chose; kth [B, T] float32: its smallest chosen score; mask:
    the choice in words, ``mask_layout``). qi [B, T, HI, DI], ki [B, T, DI],
    w [B, T, HI] float32."""
    from .kernel_dispatch import dsa_vmem_bytes
    B, T, HI, DI = qi.shape
    block_q, block_k, num_q, num_k = _tiles(T, blocks)
    in_specs, col, mask_spec = _index_specs(T, HI, DI, blocks)
    # the scope closes before the kernel's call: one that held it would
    # rename the instruction (docs/observability.md)
    with jax.named_scope("ds.dsa.select"):
        qit = qi.transpose(0, 2, 1, 3)
    column = jax.ShapeDtypeStruct((B, T, 1), jnp.int32)
    tau, tie, cnt, kth, mask = pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, block_q=block_q, block_k=block_k,
                          pos_bits=max(1, int(T - 1).bit_length()),
                          bits=mask_layout(T, block_q)[0]),
        grid=(B, num_q, num_k),
        in_specs=in_specs,
        out_specs=[col, col, col, col, mask_spec],
        out_shape=[column, column, column, jax.ShapeDtypeStruct((B, T, 1), jnp.float32),
                   jax.ShapeDtypeStruct(_mask_shape(B, T, blocks), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((num_k, block_q, block_k), jnp.int32)],
        compiler_params=_compiler_params(dsa_vmem_bytes(
            "index", 1, 1, DI, qi.dtype.itemsize, block_q, block_k, T, HI)),
        interpret=interpret,
        name="dsa_index",
    )(qit, ki, w)
    return tau[..., 0], tie[..., 0], cnt[..., 0], kth[..., 0], mask


def dsa_mask(qi, ki, w, tau, tie, blocks, interpret: bool = False):
    """``dsa_index``'s mask again from its ``tau`` and ``tie``: the scores
    once, no selection (a backward whose layer did not keep the mask)."""
    from .kernel_dispatch import dsa_vmem_bytes
    B, T, HI, DI = qi.shape
    block_q, block_k, num_q, num_k = _tiles(T, blocks)
    in_specs, col, mask_spec = _index_specs(T, HI, DI, blocks)
    return pl.pallas_call(
        functools.partial(_mask_kernel, block_q=block_q, block_k=block_k,
                          bits=mask_layout(T, block_q)[0]),
        grid=(B, num_q, num_k),
        in_specs=in_specs + [col, col],
        out_specs=mask_spec,
        out_shape=jax.ShapeDtypeStruct(_mask_shape(B, T, blocks), jnp.int32),
        compiler_params=_compiler_params(dsa_vmem_bytes(
            "mask", 1, 1, DI, qi.dtype.itemsize, block_q, block_k, T, HI)),
        interpret=interpret,
        name="dsa_mask",
    )(qi.transpose(0, 2, 1, 3), ki, w, tau[..., None], tie[..., None])


def _mask_spec(T, blocks, q_at, k_at):
    """A (query, key) tile's words: grid step (b, *at) -> the block that
    holds query tile ``q_at(*at)``'s rows of key tile ``k_at(*at)``."""
    block_q, block_k, _, _ = _tiles(T, blocks)
    _, rows, block = mask_layout(T, block_q)
    return pl.BlockSpec((1, 1, block, block_k),
                        lambda b, *at: (b, k_at(*at), (q_at(*at) * rows) // block, 0))


def _dsa_fwd(q, k, v, mask, scale, blocks, interpret):
    """-> (o [B, T, H, D], lse [B, KV, q tiles, 1, G * BQ]: a tile's rows
    g-major as its folded queries are)."""
    from .kernel_dispatch import dsa_vmem_bytes
    B, T, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q, block_k, num_q, num_k = _tiles(T, blocks)
    live = _live_key_map(block_q, block_k)
    qg, kt, vt = _group(q, k, v)
    q_spec = pl.BlockSpec((1, KV, G, block_q, D), lambda b, i, j: (b, 0, 0, i, 0))
    kv_spec = pl.BlockSpec((1, KV, block_k, D), lambda b, i, j: (b, 0, live(j, i), 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          num_k=num_k, bits=mask_layout(T, block_q)[0]),
        grid=(B, num_q, num_k),
        in_specs=[q_spec, kv_spec, kv_spec,
                  _mask_spec(T, blocks, lambda i, j: i, lambda i, j: live(j, i))],
        out_specs=[q_spec,
                   pl.BlockSpec((1, KV, 1, 1, G * block_q),
                                lambda b, i, j: (b, 0, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, KV, num_q, 1, G * block_q), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((KV, G * block_q, D), jnp.float32),
                        pltpu.VMEM((KV, G * block_q, STAT_LANES), jnp.float32),
                        pltpu.VMEM((KV, G * block_q, STAT_LANES), jnp.float32)],
        compiler_params=_compiler_params(dsa_vmem_bytes(
            "fwd", KV, G, D, q.dtype.itemsize, block_q, block_k, T)),
        interpret=interpret,
        name="dsa_fwd",
    )(qg, kt, vt, mask)
    return _ungroup(out), lse


def _live_tiles(block_q, block_k, num_q):
    """The one walk's (query tile, key tile) pairs in its order, query-major
    and a query tile's causal key tiles ascending: two int32 tables."""
    live = [_last_key_tile(i, block_q, block_k) + 1 for i in range(num_q)]
    return (np.repeat(np.arange(num_q, dtype=np.int32), live),
            np.concatenate([np.arange(n, dtype=np.int32) for n in live]))


def _dsa_bwd(q, k, v, mask, o, lse, g_out, scale, blocks, bwd, interpret):
    """``bwd``: (kernel, its query tile) as ``kernel_dispatch.resolve_dsa_bwd``
    gives them: "fused", the one walk ``dsa_bwd``, or "pair"; the tile whole
    tiles of the forward's, with words of as many queries."""
    from .kernel_dispatch import IMPL_FUSED, dsa_vmem_bytes
    B, T, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    kernel, tile_q = bwd
    fwd_q = _tiles(T, blocks)[0]
    blocks = (tile_q, blocks[1])
    block_q, block_k, num_q, num_k = _tiles(T, blocks)
    bits = mask_layout(T, block_q)[0]
    assert bits == mask_layout(T, fwd_q)[0], (blocks, fwd_q)
    static = dict(scale=scale, block_q=block_q, block_k=block_k, bits=bits)
    qg, kt, vt = _group(q, k, v)
    dog, _, _ = _group(g_out, k, v)
    og, _, _ = _group(o, k, v)
    delta = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1)

    # the kernels walk TRANSPOSED tiles: what belongs to a query enters as
    # rows, lane-dense in HBM: [B, KV, q tiles, 1, .], g-major inside a tile
    # like the folded q rows
    def rows(x):    # [B, KV, G, T] -> [B, KV, q tiles, 1, G * BQ]
        return (x.reshape(B, KV, G, num_q, block_q).transpose(0, 1, 3, 2, 4)
                .reshape(B, KV, num_q, 1, G * block_q))

    if block_q != fwd_q:    # the forward laid its log-sum-exp out by ITS tiles
        lse = rows(lse.reshape(B, KV, T // fwd_q, G, fwd_q).transpose(0, 1, 3, 2, 4)
                   .reshape(B, KV, G, T))
    operands = (qg, kt, vt, dog, lse, rows(delta), mask)

    if kernel == IMPL_FUSED:
        # one KV head a row of the grid; its walk is a table of the live
        # tiles, so that no step is spent past the causal diagonal
        def at(index):
            return lambda b, h, s, qt, kt: index(b, h, qt[s], kt[s])

        q_spec = pl.BlockSpec((1, 1, G, block_q, D), at(lambda b, h, i, j: (b, h, 0, i, 0)))
        kv_spec = pl.BlockSpec((1, 1, block_k, D), at(lambda b, h, i, j: (b, h, j, 0)))
        r_spec = pl.BlockSpec((1, 1, 1, 1, G * block_q),
                              at(lambda b, h, i, j: (b, h, i, 0, 0)))
        m_spec = _mask_spec(T, blocks, lambda h, s, qt, kt: qt[s],
                            lambda h, s, qt, kt: kt[s])
        # a key tile's dK/dV are whole, and written, in the last query tile's
        # sweep; until then the map names tile 0, which that sweep writes first
        keys_out = pl.BlockSpec(
            (1, 1, block_k, D),
            at(lambda b, h, i, j: (b, h, jnp.where(i == num_q - 1, j, 0), 0)))
        tiles = _live_tiles(block_q, block_k, num_q)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel, num_q=num_q, **static),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, KV, len(tiles[0])),
                in_specs=[q_spec, kv_spec, kv_spec, q_spec, r_spec, r_spec, m_spec],
                out_specs=[q_spec, keys_out, keys_out],
                scratch_shapes=[pltpu.VMEM((1, G * block_q, D), jnp.float32),
                                pltpu.VMEM((num_k, block_k, D), jnp.float32),
                                pltpu.VMEM((num_k, block_k, D), jnp.float32)]),
            # dQ first: ``benchmark/dsa_cost.py`` reads a call's shape off its
            # first result
            out_shape=[jax.ShapeDtypeStruct(qg.shape, q.dtype),
                       jax.ShapeDtypeStruct(kt.shape, k.dtype),
                       jax.ShapeDtypeStruct(vt.shape, v.dtype)],
            compiler_params=_compiler_params(dsa_vmem_bytes(
                "fused", KV, G, D, q.dtype.itemsize, block_q, block_k, T)),
            interpret=interpret,
            name="dsa_bwd",
        )(*tiles, *operands)
        return _ungroup(dq), dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)

    live = _live_key_map(block_q, block_k)
    params = _compiler_params(dsa_vmem_bytes(
        "bwd", KV, G, D, q.dtype.itemsize, block_q, block_k, T))

    def specs(q_at, k_at):
        """The seven operands' blocks; ``q_at`` / ``k_at`` (x, y) -> the
        query and the key tile of grid step (b, x, y)."""
        q_spec = pl.BlockSpec((1, KV, G, block_q, D),
                              lambda b, x, y: (b, 0, 0, q_at(x, y), 0))
        kv_spec = pl.BlockSpec((1, KV, block_k, D),
                               lambda b, x, y: (b, 0, k_at(x, y), 0))
        r_spec = pl.BlockSpec((1, KV, 1, 1, G * block_q),
                              lambda b, x, y: (b, 0, q_at(x, y), 0, 0))
        return q_spec, kv_spec, [q_spec, kv_spec, kv_spec, q_spec, r_spec, r_spec,
                                 _mask_spec(T, blocks, q_at, k_at)]

    # dQ: the key sweep innermost, clamped to the causal range
    q_spec, _, in_specs = specs(lambda i, j: i, lambda i, j: live(j, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k=num_k, **static),
        grid=(B, num_q, num_k),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((KV, G * block_q, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="dsa_bwd_dq",
    )(*operands)

    # dK, dV: key-major, the query sweep innermost, clamped to the first
    # query tile that sees the key tile
    _, kv_spec, in_specs = specs(lambda j, i: jnp.maximum(i, (j * block_k) // block_q),
                                 lambda j, i: j)
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, num_q=num_q, **static),
        grid=(B, num_k, num_q),
        in_specs=in_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((KV, block_k, D), jnp.float32),
                        pltpu.VMEM((KV, block_k, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="dsa_bwd_dkdv",
    )(*operands)
    return _ungroup(dq), dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _dsa_attend(q, k, v, mask, again, scale, blocks, bwd, interpret):
    """Attention under ``mask``. ``again``: None where the backward is handed
    the forward's mask (it is a residual), else what ``dsa_mask`` makes it
    again from (qi, ki, w, tau, tie), and the mask is no residual."""
    return _dsa_fwd(q, k, v, mask, scale, blocks, interpret)[0]


def _attend_fwd(q, k, v, mask, again, scale, blocks, bwd, interpret):
    o, lse = _dsa_fwd(q, k, v, mask, scale, blocks, interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o, (q, k, v, mask if again is None else None, again, o, lse)


def _attend_bwd(scale, blocks, bwd, interpret, res, g):
    q, k, v, mask, again, o, lse = res
    if mask is None:
        mask = dsa_mask(*again, blocks, interpret)
    dq, dk, dv = _dsa_bwd(q, k, v, mask, o, lse, g, scale, blocks, bwd, interpret)
    no = lambda a: np.zeros(a.shape, jax.dtypes.float0)    # noqa: E731
    if again is not None:
        qi, ki, w, tau, tie = again
        again = (jnp.zeros_like(qi), jnp.zeros_like(ki), jnp.zeros_like(w), no(tau),
                 no(tie))
    return dq, dk, dv, no(mask), again


_dsa_attend.defvjp(_attend_fwd, _attend_bwd)


# a jit frame of its own, so that the kernels read ``%dsa_fwd.N`` /
# ``%dsa_bwd.N`` under ``jax.grad`` (ops/attention.py,
# ``_flash_attention_call``); XLA inlines the call
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _dsa_call(q, k, v, qi, ki, w, topk, scale, blocks, bwd, interpret, keep_mask):
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    tau, tie, chosen, kth, mask = dsa_index(qi, ki, w, topk, blocks, interpret)
    tau = checkpoint_name(tau, remat.DSA_CHOICE[0])
    tie = checkpoint_name(tie, remat.DSA_CHOICE[1])
    mask = checkpoint_name(mask, remat.DSA_MASK if keep_mask
                           else remat.DSA_MASK + remat.AGAIN)
    o = _dsa_attend(q, k, v, mask, None if keep_mask else (qi, ki, w, tau, tie),
                    scale, blocks, bwd, interpret)
    return o, chosen, kth


def dsa_attention(q, k, v, qi, ki, w, topk: int, scale: Optional[float] = None,
                  blocks: Optional[tuple] = None, force_pallas: Optional[bool] = None,
                  interpret: bool = False, keep_mask: bool = True,
                  bwd: Optional[str] = None):
    """Attention of each query over the ``topk`` earlier keys its indexer
    scores highest. q [B, T, H, D], k/v [B, T, KV, D] (GQA native); the
    indexer's qi [B, T, HI, DI], its one key a token ki [B, T, DI] and head
    weights w [B, T, HI] (scaled already; float32). -> (o [B, T, H, D],
    chosen [B, T] int32: the pairs each row chose, kth [B, T] float32: its
    smallest chosen score). No gradient reaches qi, ki or w.

    On a TPU (or with ``interpret=True`` anywhere) the ``dsa_*`` kernels run
    at ``kernel_dispatch.choose_dsa_blocks``' tiles, the one-walk backward
    at a wider query tile where ``resolve_dsa_bwd`` finds room, unless
    ``blocks`` pins (query, key) tiles, every kernel's; elsewhere
    ``dense_dsa``. ``keep_mask``: whether the backward is handed the
    forward's mask (named ``remat.DSA_MASK``: what a caller inside a
    recomputation asks ``remat.keeps``) or makes it again with ``dsa_mask``.
    ``bwd`` pins the backward: "fused" (``dsa_bwd``, the one walk: what every
    shape whose dK and dV fit VMEM resolves to) or "pair" (``dsa_bwd_dq`` +
    ``dsa_bwd_dkdv``), as ``impl_bwd`` pins flash's."""
    from . import kernel_dispatch as kd
    scale = float(scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]))
    w = w.astype(jnp.float32)
    if not (use_pallas(force_pallas) or interpret):
        return dense_dsa(q, k, v, qi, ki, w, topk, scale)
    sig = kd.make_sig(q.shape, k.shape[2], k.shape[1], q.dtype, True, None, None,
                      pattern=f"dsa{topk}")
    widen = blocks is None      # the rule's own tiles: the one walk may take a wider
    blocks = tuple(blocks or kd.choose_dsa_blocks(sig, qi.shape[2], qi.shape[3]))
    return _dsa_call(q, k, v, qi, ki, w, int(topk), scale, blocks,
                     kd.resolve_dsa_bwd(sig, blocks, bwd, widen), interpret,
                     bool(keep_mask))
