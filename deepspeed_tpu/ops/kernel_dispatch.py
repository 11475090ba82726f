"""Attention kernels and their blocks, from the shape of the call alone.

On a TPU ``flash_attention`` runs one forward, the per-head Pallas kernel
(``flash_fwd``), and one backward, ``flash_dkdv_dq`` ("fused": dQ, dK and
dV from one walk over the score tiles), which holds the float32 dQ of a KV
head's queries in VMEM beside its tiles: of the whole sequence where that
fits (``fused_vmem_bytes`` within ``FUSED_VMEM_CAP_BYTES``), else of one
query RANGE at a time, the fewest ranges that fit (``choose_ranges``). The
pair ``flash_dq`` + ``flash_dkdv`` ("pallas") is what no shape resolves to
and ``impl_bwd="pallas"`` pins. ``resolve`` decides; what it reads is the
``ShapeSig`` and what the caller pinned (``impl_bwd=``, ``block_q=`` /
``block_k=``, ``ranges=``: tests and the sweep tool). No environment
variable and no file is read on the way. The routes this replaced (an XLA forward under a
rule on the head size, head-folded kernels, a measured table) lost to these
two at every point of a sweep on the chip: PERF.md, PR 44.

A causal or windowed call's grid is a table of its live tiles (``walked``,
``flash_walk``: listed here at trace time, from the blocks and the mask);
an unmasked call keeps the rectangle.

Blocks are ``choose_blocks``, a pure function of the shape signature and a
VMEM estimate of the leg's tiles (1024 folded query rows a step at any GQA
group, and 512 keys or as many as the queries). The block-diffusion kernels'
tiles (``choose_block_diffusion_blocks``) and the VMEM estimates every
kernel of ``ops/`` sizes its limit by (``vmem_limit_bytes``) live here too.
"""

import math
from typing import NamedTuple, Optional

import numpy as np

# the backward's two implementations, both per-head Pallas kernels
# (ops/attention.py): the pair ``flash_dq`` + ``flash_dkdv``, and the
# one-pass ``flash_dkdv_dq``. The forward has one, named as the pair is.
IMPL_PALLAS = "pallas"
IMPL_FUSED = "fused"


class ShapeSig(NamedTuple):
    """Static trace-time facts a dispatch decision may depend on."""
    batch: int
    seq_q: int
    seq_k: int
    heads: int
    kv_heads: int
    head_dim: int
    dtype: str
    causal: bool
    windowed: bool
    softcapped: bool
    # a structured mask that is neither causal nor a window: "bd<B>" =
    # block-diffusion training over blocks of B tokens (its own kernels)
    pattern: str = ""
    # the values' width where it is not the keys' (latent attention: keys
    # 192, values 128; the ``mla_*`` calls); 0 = ``head_dim``
    v_dim: int = 0
    # the window's keys where ``windowed`` (the live tiles are counted from it)
    window: int = 0


class Decision(NamedTuple):
    """One leg's kernel and blocks, and the query ranges the fused backward
    makes its walk in (1: the whole sequence at once; every other leg).
    ``walked`` adds the leg's walk over a KV head's score tiles: ``tiles``
    of them hold an unmasked pair, the rectangle has ``grid``, and ``table``
    says that the call's grid is a list of the first and not the second."""
    impl: str
    block_q: int
    block_k: int
    ranges: int = 1
    tiles: int = 0
    grid: int = 0
    table: bool = False


def make_sig(q_shape, kv_heads: int, seq_k: int, dtype, causal: bool,
             window, softcap, pattern: str = "", v_dim: int = 0) -> ShapeSig:
    b, sq, h, d = q_shape
    return ShapeSig(batch=int(b), seq_q=int(sq), seq_k=int(seq_k),
                    heads=int(h), kv_heads=int(kv_heads), head_dim=int(d),
                    dtype=str(dtype), causal=bool(causal),
                    windowed=window is not None,
                    softcapped=softcap is not None, pattern=pattern,
                    v_dim=0 if int(v_dim) == int(d) else int(v_dim),
                    window=0 if window is None else int(window))


# What the block choice aims at (sweeps on a v5e: PR 25 at group 4, PR 32 at
# groups 1 and 2, docs/kernel_dispatch.md): 1024 folded rows a grid step
# (the G query heads of a KV group times the query block), so K/V are
# re-read once per 1024 rows at any group; 512 keys a step, so the step's
# fixed cost and the per-row softmax statistics are paid once per 512 keys.
# The masked work on the causal diagonal follows the larger of the two
# blocks, so the key block rises to a query block past it (group 1) at no
# more waste and half the steps.
KEY_BLOCK = 512
MAX_ROWS = 1024
# The fused backward walks each score tile once for all three gradients and
# carries its own VMEM limit, so its step is not held to the compiler's
# default: 2,048 folded rows, of at most 512 queries, by 512 keys was the
# fastest or within 2.5% of it at groups 1, 2, 4 and 8 and head sizes 64,
# 128 and 256 (v5e sweep, PR 34, docs/kernel_dispatch.md).
FUSED_MAX_ROWS = 2048
# Mosaic's default scoped-VMEM limit on a v5e (the core has 128 MiB behind
# it). The blocks chosen here stay under it by the estimate below; explicit
# or measured blocks past it get their own limit on the call.
VMEM_SCOPED_DEFAULT_BYTES = 16 * 2**20
# The most the fused backward's estimate may be: half the core's 128 MiB.
# The call asks for its estimate and a quarter more (``vmem_limit_bytes``),
# 80 MiB at the cap, which leaves 48 MiB for what Mosaic keeps beyond the
# estimate (its own stack: the state-space scan's refusal at 1.25x,
# docs/kernel_dispatch.md) and for what XLA holds in VMEM around the call.
# The estimate grows with ``group * seq_q`` (the float32 dQ of one KV
# head's queries): 16,384 tokens at group 4 are 55 MiB; 24,576 are 71 MiB
# and walk in two ranges of 12,288 (47 MiB).
FUSED_VMEM_CAP_BYTES = 64 * 2**20


def flash_vmem_bytes(leg: str, group: int, head_dim: int, itemsize: int,
                     block_q: int, block_k: int, seq_q: int = 0) -> int:
    """Upper estimate of the VMEM one grid step of the per-head flash
    kernels holds (``ops/attention.py``), for ``leg`` "fwd", "bwd" (the
    larger of the dq and dk/dv kernels) or "fused" (the one-pass backward,
    which also holds the float32 dQ of a KV head's ``seq_q`` queries).
    Counted: every pipelined operand and result block twice (double
    buffering), the scratch accumulators and the forward's lane-replicated
    statistics, and the score-tile temporaries: two fp32 tiles and p's cast
    in the forward, three and both casts in the backward, and ds transposed
    in the fused one. A ``[rows, 1]`` block occupies a full 128-lane row,
    and a head of 64 a 128-lane row of the accumulators. Checked against
    the v5e compiler (described-chip compiles, PR 25): every tile set it
    put under the default compiled, the first refusals stand at 22 MiB
    (forward) and 25 MiB (backward) of this estimate."""
    rows = group * block_q
    lanes = max(head_dim, 128)
    q_blk = rows * lanes * itemsize
    kv_blk = block_k * lanes * itemsize
    stat = rows * 128 * 4
    tile = rows * block_k
    if leg == "fwd":
        blocks = 2 * q_blk + 2 * kv_blk + stat        # q, o; k, v; lse
        scratch = rows * lanes * 4 + 2 * stat         # acc; m, l
        temps = tile * (2 * 4 + itemsize)
    elif leg == "fused":
        # q, do, dq; k, v, dk, dv; lse and delta as rows
        blocks = 3 * q_blk + 4 * kv_blk + 2 * 8 * rows * 4
        scratch = 2 * block_k * lanes * 4 + group * seq_q * lanes * 4
        temps = tile * (3 * 4 + 3 * itemsize)
    else:
        # dq: q, do, dq; k, v; lse, delta | dk/dv: q, do; k, v, dk, dv; rows
        blocks = max(3 * q_blk + 2 * kv_blk + 2 * stat,
                     2 * q_blk + 4 * kv_blk + 2 * 8 * rows * 4)
        scratch = max(rows * lanes * 4, 2 * block_k * lanes * 4)
        temps = tile * (3 * 4 + 2 * itemsize)
    return 2 * blocks + scratch + temps


def vmem_limit_bytes(estimate: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a kernel whose tiles are estimated at
    ``estimate``: None (the compiler's default) while it fits, else the
    estimate and a quarter more."""
    if estimate <= VMEM_SCOPED_DEFAULT_BYTES:
        return None
    return estimate * 5 // 4


def vmem_width(head_dim: int, v_dim: int = 0) -> int:
    """The head size the VMEM estimates are asked about: ``head_dim``, or
    where the values' width differs (``v_dim`` neither 0 nor ``head_dim``)
    the larger of the two in whole 128-lane tiles for every block (192 lies
    in VMEM as 256 lanes; the 128-wide v, o and dV blocks are then counted
    at 256 too: an upper estimate)."""
    if v_dim in (0, head_dim):
        return head_dim
    return -(-max(head_dim, v_dim) // 128) * 128


def fused_vmem_bytes(sig: ShapeSig, ranges: int = 1,
                     blocks: Optional[tuple] = None) -> int:
    """``flash_vmem_bytes`` of the fused backward at ``sig`` walked in
    ``ranges`` query ranges, with ``blocks`` or those the shape gives it:
    what ``choose_ranges`` holds against FUSED_VMEM_CAP_BYTES."""
    return flash_vmem_bytes("fused", max(1, sig.heads // sig.kv_heads),
                            vmem_width(sig.head_dim, sig.v_dim),
                            4 if "32" in sig.dtype else 2,
                            *(blocks or choose_blocks(sig, "fused")),
                            seq_q=sig.seq_q // ranges)


def choose_ranges(sig: ShapeSig, blocks: Optional[tuple] = None) -> int:
    """Query ranges of the fused backward's walk, from the shape alone: the
    fewest that divide the sequence into whole q blocks and put the
    estimate, one range's float32 dQ in it, within FUSED_VMEM_CAP_BYTES (1
    at every shape that fits whole). Tiles that alone pass the cap (pinned
    ones) take a q block a range; a q block that does not divide the
    sequence is the kernels' to refuse, in one range."""
    blocks = blocks or choose_blocks(sig, "fused")
    block_q = min(blocks[0], sig.seq_q)
    if sig.seq_q % block_q:
        return 1
    num_q = sig.seq_q // block_q
    for ranges in range(1, num_q):
        if (num_q % ranges == 0 and fused_vmem_bytes(sig, ranges, blocks)
                <= FUSED_VMEM_CAP_BYTES):
            return ranges
    return num_q


# Block-diffusion attention (``ops/attention.py``, ``bdattn_fwd`` /
# ``bdattn_bwd``): a grid step holds BOTH copies' query rows of one tile of
# the L data tokens, 2 * group * block_q folded rows, against one tile of
# clean keys. Both legs aim at FUSED_MAX_ROWS rows and KEY_BLOCK keys and
# carry their own VMEM limit, as the fused causal backward does (v5e sweep at
# 2 x 16,384 positions, 32 heads of 128 in groups of 8, PR 37: forward 19.5
# to 20.4 ms from 1,024 rows up and any key tile, 22.5 ms at 512 rows;
# forward + backward 55.9 to 58.8 ms over seven tile pairs, this one within
# 1% of the best). The backward holds the float32 dK
# and dV of one KV head's L clean keys in VMEM (no group factor: 8 MiB at
# L 8,192 and head 128, where the causal fused kernel's dQ would be 64 MiB at
# group 8), and its estimate is held to FUSED_VMEM_CAP_BYTES.


def bdattn_vmem_bytes(leg: str, group: int, head_dim: int, itemsize: int,
                      block_q: int, block_k: int, seq: int) -> int:
    """Upper estimate of the VMEM one grid step of the block-diffusion
    kernels holds, counted as ``flash_vmem_bytes`` counts: ``leg`` "fwd" or
    "bwd" (which adds the clean keys' float32 dK and dV over ``seq``)."""
    rows = 2 * group * block_q
    lanes = max(head_dim, 128)
    q_blk = rows * lanes * itemsize
    own_blk = block_q * lanes * itemsize
    kv_blk = block_k * lanes * itemsize
    tile = rows * block_k
    if leg == "fwd":
        blocks = 2 * q_blk + 2 * own_blk + 2 * kv_blk + rows * 128 * 4
        scratch = rows * lanes * 4 + 2 * rows * 128 * 4
        temps = tile * (2 * 4 + itemsize)
    else:
        # q, do, dq; own k, v, dk, dv; clean k, v, dk, dv; lse, delta rows
        blocks = 3 * q_blk + 4 * own_blk + 4 * kv_blk + 2 * 8 * rows * 4
        scratch = rows * lanes * 4 + 2 * seq * lanes * 4
        temps = tile * (3 * 4 + 3 * itemsize)
    return 2 * blocks + scratch + temps


def _pattern_block(seq: int, cap: int, block_length: int) -> int:
    """The largest tile of at most ``cap`` positions that divides ``seq``
    and holds whole blocks of ``block_length``; multiples of 128 first, then
    of 8, then any (a short test sequence)."""
    for lane in (128, 8, 1):
        step = math.lcm(block_length, lane)
        for b in range(min(cap, seq) // step * step, 0, -step):
            if seq % b == 0:
                return b
    return seq


def choose_block_diffusion_blocks(sig: ShapeSig, leg: str,
                                  block_length: int) -> tuple:
    """(block_q, block_k) of ``bdattn_fwd`` ("fwd") or ``bdattn_bwd``
    ("bwd") over the L = seq_q / 2 data tokens of ``sig``. Raises where the
    backward's estimate passes FUSED_VMEM_CAP_BYTES: there is no two-pass
    pair for this pattern."""
    group = max(1, sig.heads // sig.kv_heads)
    seq = sig.seq_q // 2
    itemsize = 4 if "32" in sig.dtype else 2
    bq = _pattern_block(seq, max(8, FUSED_MAX_ROWS // (2 * group)), block_length)
    bk = _pattern_block(seq, KEY_BLOCK, block_length)
    if leg == "bwd":
        need = bdattn_vmem_bytes("bwd", group, sig.head_dim, itemsize, bq, bk, seq)
        if need > FUSED_VMEM_CAP_BYTES:
            raise ValueError(
                f"block-diffusion backward at L = {seq}, head {sig.head_dim}: "
                f"the float32 dK and dV of the clean keys put its VMEM "
                f"estimate at {need >> 20} MiB, over the cap of "
                f"{FUSED_VMEM_CAP_BYTES >> 20} MiB; no two-pass backward "
                f"exists for this pattern (shorten the sequence)")
    return bq, bk


# Learned sparse attention (``ops/dsa_attention.py``, ``dsa_index`` /
# ``dsa_mask`` / ``dsa_fwd`` / ``dsa_bwd``, and the pair ``dsa_bwd_dq`` /
# ``dsa_bwd_dkdv``): a grid step of the forward and of the pair holds ALL KV
# heads of one (query, key) tile, so that the choice's words are expanded
# once for them; the query tile folds with its group to MAX_ROWS rows a KV
# head, the key tile is KEY_BLOCK. ``dsa_index`` keeps a query tile's scores
# over the whole row in VMEM (seq * block_q * 4 bytes: 16 MiB at 32,768 keys
# and 128 queries), which caps the query tile; every call carries its own
# limit. The backward is ``dsa_bwd``, one walk a KV head with that head's
# float32 dK and dV of every key in VMEM (no group factor: 32 MiB at 32,768
# keys of 128, where a range of 2,048 queries' dQ of all four heads would be
# as much and PR 57's partials 2.1 GB), wherever its estimate is within
# FUSED_VMEM_CAP_BYTES (``resolve_dsa_bwd``: 57 MiB at the Keye-VL cell's
# call, at a query tile of 256; 65,536 keys are 64 MiB of dK and dV alone),
# and past it the pair.
DSA_ROW_SCORES_CAP_BYTES = 32 * 2**20


def dsa_vmem_bytes(leg: str, kv_heads: int, group: int, head_dim: int,
                   itemsize: int, block_q: int, block_k: int, seq: int,
                   index_heads: int = 0) -> int:
    """Upper estimate of the VMEM one grid step of the ``dsa_*`` kernels
    holds, counted as ``flash_vmem_bytes`` counts: ``leg`` "index" (the
    row's scores over ``seq`` keys and its words), "mask" (the words and one
    tile's scores), "fwd", "bwd" (the larger of the pair; a tile's words
    and their expansion) or "fused" (the one walk ``dsa_bwd``: ONE KV head's
    tiles whatever ``kv_heads`` says, ds transposed, and the float32 dK and
    dV of its ``seq`` keys). The indexer's operands are counted at 128
    lanes."""
    from .dsa_attention import mask_layout
    word_rows = mask_layout(seq, block_q)[2]
    if leg in ("index", "mask"):
        scores = (2 * (index_heads * block_q + block_k) * 128 * itemsize   # qi, ki
                  + 2 * 5 * block_q * 128 * 4               # w and the columns
                  + 6 * block_q * block_k * 4               # the tile
                  + 2 * word_rows * seq * 4)                # a row of key tiles' words
        return scores + (seq * block_q * 4 if leg == "index" else 0)
    if leg == "fused":
        kv_heads = 1
    rows = kv_heads * group * block_q
    lanes = max(head_dim, 128)
    words = 2 * word_rows * block_k * 4 + 3 * block_q * block_k * 4
    q_blk = rows * lanes * itemsize
    kv_blk = kv_heads * block_k * lanes * itemsize
    stat = rows * 128 * 4
    tile = group * block_q * block_k
    if leg == "fwd":
        blocks = 2 * q_blk + 2 * kv_blk + stat          # q, o; k, v; lse
        scratch = rows * lanes * 4 + 2 * stat           # acc; m, l
        temps = tile * (2 * 4 + itemsize)
    elif leg == "fused":
        # q, do, dq; k, v, dk, dv; lse and delta as rows
        blocks = 3 * q_blk + 4 * kv_blk + 2 * 8 * rows * 4
        scratch = rows * lanes * 4 + 2 * seq * lanes * 4
        temps = tile * (3 * 4 + 3 * itemsize)
    else:
        blocks = max(3 * q_blk + 2 * kv_blk + 2 * stat,
                     2 * q_blk + 4 * kv_blk + 2 * 8 * rows * 4)
        scratch = max(rows * lanes * 4, 2 * kv_heads * block_k * lanes * 4)
        temps = tile * (3 * 4 + 2 * itemsize)
    return words + 2 * blocks + scratch + temps


DSA_BWD_PAIR = "pair"


def resolve_dsa_bwd(sig: ShapeSig, blocks: tuple, pinned: Optional[str] = None,
                    widen: bool = True) -> tuple:
    """(kernel, its query tile) of the ``dsa_*`` backward at ``sig`` under
    the call's ``blocks``, from the shape alone: IMPL_FUSED, the one walk
    ``dsa_bwd``, wherever its estimate (a KV head's float32 dK and dV of
    every key in it) is within FUSED_VMEM_CAP_BYTES, else DSA_BWD_PAIR
    (``dsa_bwd_dq`` + ``dsa_bwd_dkdv``), which needs no accumulator longer
    than a tile. ``pinned`` names either (tests, the sweep). The one walk's
    step is not held to the forward's MAX_ROWS: where the call's tiles are
    the rule's own (``widen``) and the estimate allows, its query tile is
    FUSED_MAX_ROWS folded rows of at most 512 queries, whole tiles of the
    call's and words of as many queries (half the steps: 130.4 for 141.3 ms
    at the Keye-VL cell's call, PR 59); the key tile is the call's, which
    the mask's words are laid out by."""
    from .dsa_attention import mask_layout
    if pinned not in (None, IMPL_FUSED, DSA_BWD_PAIR):
        raise ValueError(f"bwd={pinned!r}: {IMPL_FUSED!r}, {DSA_BWD_PAIR!r} or None")
    block_q, block_k = (min(b, sig.seq_q) for b in blocks)
    group = max(1, sig.heads // sig.kv_heads)

    def fits(tile_q):
        return dsa_vmem_bytes("fused", 1, group, sig.head_dim,
                              4 if "32" in sig.dtype else 2, tile_q, block_k,
                              sig.seq_k) <= FUSED_VMEM_CAP_BYTES

    if pinned == DSA_BWD_PAIR or not (pinned or fits(block_q)):
        return DSA_BWD_PAIR, block_q
    wide = _largest_block(sig.seq_q, max(block_q, min(512, FUSED_MAX_ROWS // group)))
    if (widen and wide % block_q == 0 and fits(wide)
            and mask_layout(sig.seq_q, wide)[0] == mask_layout(sig.seq_q, block_q)[0]):
        return IMPL_FUSED, wide
    return IMPL_FUSED, block_q


def choose_dsa_blocks(sig: ShapeSig, index_heads: int, index_dim: int) -> tuple:
    """(block_q, block_k) of every ``dsa_*`` kernel of one call (the mask's
    words are laid out by them, and ``dsa_mask`` makes the indexer's scores
    again at ``dsa_index``'s tile shape, so that they are bit-equal):
    MAX_ROWS folded rows a KV head, fewer while a row tile's scores pass
    DSA_ROW_SCORES_CAP_BYTES, and KEY_BLOCK keys."""
    group = max(1, sig.heads // sig.kv_heads)
    cap_q = max(128, MAX_ROWS // group)
    while cap_q > 8 and cap_q * sig.seq_k * 4 > DSA_ROW_SCORES_CAP_BYTES:
        cap_q //= 2
    if cap_q * sig.seq_k * 4 > DSA_ROW_SCORES_CAP_BYTES:
        raise ValueError(
            f"learned sparse attention at {sig.seq_k} keys: a tile of {cap_q} "
            f"rows' indexer scores pass {DSA_ROW_SCORES_CAP_BYTES >> 20} MiB of "
            f"VMEM (shorten the sequence)")
    return _largest_block(sig.seq_q, cap_q), _largest_block(sig.seq_k, KEY_BLOCK)


def _largest_block(seq: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``seq`` and is at most
    ``cap``; for a sequence with none (shorter than 128, or not a multiple
    of it), the whole sequence if it fits, else its largest power-of-two
    divisor under the cap."""
    for b in range(min(cap, seq) // 128 * 128, 0, -128):
        if seq % b == 0:
            return b
    if seq <= cap:
        return seq
    b = 1
    while b * 2 <= cap and seq % (b * 2) == 0:
        b *= 2
    return b


def choose_blocks(sig: ShapeSig, leg: str) -> tuple:
    """(block_q, block_k) of a Pallas leg from the shape alone: the query
    block that folds with its group to MAX_ROWS rows a step (at least 128)
    and KEY_BLOCK keys, or as many keys as queries, where the sequences
    allow; then the larger of rows and keys halved, the keys on a tie, while
    the leg's VMEM estimate is over the compiler's default (the backward,
    which holds more score tiles at once, reaches it at group 1 and with
    fp32 operands). ``leg`` "fused" (the one-pass backward): FUSED_MAX_ROWS
    rows a step, KEY_BLOCK queries at most, and KEY_BLOCK keys; whether its
    estimate (``fused_vmem_bytes``) fits is ``resolve``'s to ask."""
    group = max(1, sig.heads // sig.kv_heads)
    if leg == "fused":
        cap_q = min(KEY_BLOCK, max(128, FUSED_MAX_ROWS // group))
        return (_largest_block(sig.seq_q, cap_q),
                _largest_block(sig.seq_k, KEY_BLOCK))
    itemsize = 4 if "32" in sig.dtype else 2
    cap_q = max(128, MAX_ROWS // group)
    cap_k = max(KEY_BLOCK, cap_q)
    while True:
        bq = _largest_block(sig.seq_q, cap_q)
        bk = _largest_block(sig.seq_k, cap_k)
        over = flash_vmem_bytes(leg, group, vmem_width(sig.head_dim, sig.v_dim),
                                itemsize, bq, bk) > VMEM_SCOPED_DEFAULT_BYTES
        if not over or max(cap_q, cap_k) <= 128:
            return bq, bk
        if group * cap_q > cap_k and cap_q > 128:
            cap_q //= 2
        else:
            cap_k //= 2


# The experts' grouped matmuls (``ops/grouped_matmul.py``: ``moe_gmm_rows``,
# ``moe_gmm_d_rows``, ``moe_gmm_weights``): a grid step is a tile of sorted
# rows by one expert's WHOLE matrix (v5e sweeps, PRs 42 and 47,
# docs/kernel_dispatch.md: 256 rows by the whole output width was the fastest
# tile at every point, widths 768 to 2,048), so a step's weights are read
# once for the rows of their expert and the float32 sums never leave VMEM.
IMPL_XLA = "xla"
GMM_ROW_TILE = 256
# the fewest rows the sweeps measured (32 rows a group over 64 experts);
# below them a decode wave's few rows, where every step is a weight's load
GMM_MIN_ROWS = 2048


def gmm_vmem_bytes(leg: str, tile: int, k: int, n: int, itemsize: int) -> int:
    """Upper estimate of the VMEM one grid step of a ``moe_gmm_*`` kernel
    holds, for an expert of ``[k, n]`` and ``tile`` rows, counted as
    ``flash_vmem_bytes`` counts: the pipelined blocks twice, the float32
    product (and, in "weights", the float32 sums beside it), the masked
    copies and the row numbers."""
    if leg == "weights":
        blocks = (tile * (k + n) + k * n) * itemsize    # x, dy; the gradient
        return 2 * blocks + 2 * k * n * 4 + tile * (k + n) * (itemsize + 4)
    wide_in, wide_out = (n, k) if leg == "d_rows" else (k, n)
    blocks = (tile * (wide_in + wide_out) + k * n) * itemsize
    return 2 * blocks + 3 * tile * wide_out * 4


def gmm_impl(rows: int, k: int, n: int, dtype, kernel_here: bool) -> str:
    """IMPL_PALLAS (``moe_gmm``) or IMPL_XLA (``jax.lax.ragged_dot``) for
    ``[rows, k] x [experts, k, n]``, forward and backward alike, from the
    shape, the dtype and the placement alone: the kernel where a raw
    ``pallas_call`` can run (``kernel_here``: a TPU, a mesh of one device),
    the operands are bfloat16, both widths lie on the 128-lane grid and all
    three passes fit FUSED_VMEM_CAP_BYTES at GMM_ROW_TILE rows, and there are
    GMM_MIN_ROWS rows or more. At every measured point inside these edges the
    kernel took 0.26 to 0.89 of XLA's time; outside them nothing was
    measured (a LoRA delta's rank-wide matmuls, a decode wave, float32)."""
    fits = max(gmm_vmem_bytes(leg, GMM_ROW_TILE, k, n, 2)
               for leg in ("rows", "d_rows", "weights")) <= FUSED_VMEM_CAP_BYTES
    if (kernel_here and "bfloat16" in str(dtype) and k % 128 == 0
            and n % 128 == 0 and fits and rows >= GMM_MIN_ROWS):
        return IMPL_PALLAS
    return IMPL_XLA


# Kimi Delta Attention's chunk kernels (``ops/kda.py``: ``kda_chunk_fwd``,
# ``kda_chunk_bwd``): a grid step is one chunk of a BLOCK of heads, whose
# chains of small dependent matmuls (the running sum, the triangular products,
# ``(I + A)^{-1}``) share no value and are issued a stage abreast. The list is
# the sweep's on a v5e (PR 49, docs/kernel_dispatch.md: ``[1, 16384, 32 x
# 128]`` forward 18.0, 10.9, 8.0 ms a call at 1, 2, 4 heads, backward 23.0,
# 14.0, 11.0; 8 heads within 3% of 4, the MXUs' own time being the step by
# then, for up to three times the seconds to compile: not on the list).
KDA_HEAD_BLOCKS = (4, 2, 1)


def kda_vmem_bytes(block: int, d: int, chunk: int, itemsize: int,
                   arrays: int = 11) -> int:
    """Upper estimate of the VMEM one grid step of a ``kda_chunk_*`` kernel
    holds at ``block`` heads of ``d`` lanes: its ``arrays`` pipelined ``[chunk,
    block * d]`` blocks twice (6 forward: q, k, v, the two gates'
    pre-activations and the output; 11 backward: those five, the output's
    gradient and five gradients; the larger by default), the blocks of
    ``beta`` and its gradient (a head a lane, 128 lanes of float32), the
    float32 state's blocks and scratch, and some four and a half dozen
    ``[chunk, d]`` float32 temporaries a head (since PR 53 the unit rows, the
    norms and the gated output norm's are among them; the triangular solve's
    are half a dozen ``[chunk, chunk]`` tiles)."""
    width = block * d
    return (2 * arrays * chunk * width * max(itemsize, 4) + 2 * 2 * chunk * 128 * 4
            + 6 * 4 * d * width + 56 * 4 * chunk * width)


def choose_kda_heads(heads: int, d: int, chunk: int, itemsize: int) -> int:
    """Heads a grid step of the ``kda_chunk_*`` kernels, from the shape alone:
    the first of KDA_HEAD_BLOCKS that divides ``heads`` and whose estimate
    (the backward's, the larger) fits FUSED_VMEM_CAP_BYTES, else 1."""
    for block in KDA_HEAD_BLOCKS:
        if (heads % block == 0
                and kda_vmem_bytes(block, d, chunk, itemsize) <= FUSED_VMEM_CAP_BYTES):
            return block
    return 1


def resolve(sig: ShapeSig, *, impl_bwd: Optional[str] = None,
            blocks: Optional[tuple] = None, ranges: Optional[int] = None):
    """(forward Decision, backward Decision) of one ``flash_attention`` call.
    The forward is the per-head kernel. The backward is the fused kernel at
    every shape, walked in the fewest query ranges whose float32 dQ fits
    FUSED_VMEM_CAP_BYTES beside the tiles (``choose_ranges``: 1 up to 20,480
    tokens at group 4 and head 128, 2 at 24,576); ``impl_bwd`` pins it or
    the dq + dk/dv pair, ``blocks`` both legs' (block_q, block_k), else each
    leg's come from ``choose_blocks``, and ``ranges`` the fused walk's."""
    if impl_bwd is None:
        impl_bwd = IMPL_FUSED
    elif impl_bwd not in (IMPL_PALLAS, IMPL_FUSED):
        raise ValueError(f"impl_bwd={impl_bwd!r}: the backward is "
                         f"{IMPL_PALLAS!r} (the dq + dk/dv pair) or "
                         f"{IMPL_FUSED!r}")
    fused = impl_bwd == IMPL_FUSED
    bwd_blocks = blocks or choose_blocks(sig, "fused" if fused else "bwd")
    if ranges is None:
        ranges = choose_ranges(sig, bwd_blocks) if fused else 1
    elif not fused and ranges != 1:
        raise ValueError(f"ranges={ranges}: only the fused backward walks by "
                         f"query ranges")
    elif ranges != 1 and (ranges < 1 or sig.seq_q % ranges or (
            sig.seq_q // ranges) % min(bwd_blocks[0], sig.seq_q)):
        raise ValueError(
            f"ranges={ranges}: a range of the fused backward holds whole "
            f"q blocks ({sig.seq_q} queries in blocks of {bwd_blocks[0]})")
    return (Decision(IMPL_PALLAS, *map(int, blocks or choose_blocks(sig, "fwd"))),
            Decision(impl_bwd, *map(int, bwd_blocks), ranges=int(ranges)))


# A causal or windowed call's grid is a TABLE of its live tiles (PR 60): a
# tile past the diagonal or outside the window copied and multiplied nothing
# on the rectangle, but was a grid step, and a step has a fixed cost (some
# 0.2 us on a v5e: docs/kernel_dispatch.md). The tables are int32 in SMEM by
# scalar prefetch, three a forward step and four a fused backward's, and a
# v5e core's SMEM is 1 MiB. Past this many steps a KV head the call keeps the
# clamped rectangle (from the shape alone: ``walked``).
TABLE_CAP_TILES = 49152

# A step's entry in a walk's flags table: what the kernel would otherwise
# work out from the mask, a step: whether the step OPENS and CLOSES its run
# (a forward's query block, a backward's key block of one range: the sums
# that are zeroed and written there), whether its tile is LIVE and on the
# mask's EDGE (live and not interior: it needs the element mask), and, in a
# key-major walk, whether it is its query block's first (DQ_OPENS) and last
# (DQ_CLOSES) live step.
OPENS, CLOSES, LIVE, EDGE, DQ_OPENS, DQ_CLOSES = 1, 2, 4, 8, 16, 32


def live_tiles(num_q: int, num_k: int, block_q: int, block_k: int,
               causal: bool, window: Optional[int] = None) -> tuple:
    """(live, interior), each ``[num_q, num_k]`` bool: the (query tile, key
    tile) pairs in which the mask lets SOME (query, key) through, and those
    in which it lets EVERY one: ``ops/attention.py::_when_live``'s tests,
    listed. Any other structured mask is another such pair of matrices (a
    learned sparse call's causal one is this with no window; block
    diffusion's has an own-block column)."""
    q0 = np.arange(num_q)[:, None] * block_q
    k0 = np.arange(num_k)[None, :] * block_k
    live = np.ones((num_q, num_k), bool)
    interior = live.copy()
    if causal:
        live &= k0 <= q0 + block_q - 1
        interior &= k0 + block_k - 1 <= q0
    if window is not None:
        live &= k0 + block_k - 1 >= q0 - (window - 1)
        interior &= q0 + block_q - 1 - k0 <= window - 1
    return live, live & interior


def tile_walk(live: np.ndarray, major: str = "q", ranges: int = 1) -> tuple:
    """The walk over ``live`` as two int32 tables, (query tile, key tile) a
    grid step. ``major`` "q": a query tile's key tiles ascending, one query
    tile after another (a forward: a row's sums open and close once); "k": a
    key tile's query tiles ascending (a backward's dK and dV), within each of
    ``ranges`` equal runs of query tiles in turn. A major tile with no live
    tile keeps ONE step, its first, so that every one opens and closes
    exactly once and its zero result is written; that step is not LIVE."""
    per = live.shape[0] // ranges
    q_tiles, k_tiles = [], []
    for first in range(0, live.shape[0], per):
        part = live[first:first + per]
        part = (part if major == "q" else part.T).copy()
        part[~part.any(axis=1), 0] = True
        outer, inner = np.nonzero(part)
        q, k = (outer, inner) if major == "q" else (inner, outer)
        q_tiles.append(q + first)
        k_tiles.append(k)
    return (np.concatenate(q_tiles).astype(np.int32),
            np.concatenate(k_tiles).astype(np.int32))


def flash_walk(leg: str, num_q: int, num_k: int, block_q: int, block_k: int,
               causal: bool, window: Optional[int], ranges: int = 1) -> tuple:
    """The int32 tables of one KV head's walk, an entry a grid step. "fwd":
    (query tile, key tile, flags), query-major. "bwd" (the fused kernel's):
    (query tile, key tile, dQ's tile, flags), key-major in ``ranges`` query
    ranges. dQ's tile is the query tile whose LAST live step comes next, at
    the step or after it: a result that leaves once a query tile names it, so
    its block is held from the step after the one before closed until the
    step that completes it; each is written once, whole."""
    live, interior = live_tiles(num_q, num_k, block_q, block_k, causal, window)
    q_tiles, k_tiles = tile_walk(live, "q" if leg == "fwd" else "k", ranges)
    steps = np.arange(len(q_tiles))
    real = live[q_tiles, k_tiles]
    run = (q_tiles if leg == "fwd"
           else (q_tiles // (num_q // ranges)) * num_k + k_tiles)
    turns = run[1:] != run[:-1]
    flags = (OPENS * np.r_[True, turns] + CLOSES * np.r_[turns, True]
             + LIVE * real + EDGE * (real & ~interior[q_tiles, k_tiles]))
    if leg == "fwd":
        return q_tiles, k_tiles, flags.astype(np.int32)
    first, last = np.full(num_q, len(steps)), np.full(num_q, -1)
    np.minimum.at(first, q_tiles[real], steps[real])
    np.maximum.at(last, q_tiles[real], steps[real])
    assert last.min() >= 0, "a query tile with no live key tile"
    flags += DQ_OPENS * (first[q_tiles] == steps) + DQ_CLOSES * (last[q_tiles] == steps)
    closes = np.sort(last)
    upcoming = closes[np.minimum(np.searchsorted(closes, steps), num_q - 1)]
    return q_tiles, k_tiles, q_tiles[upcoming], flags.astype(np.int32)


def walked(sig: ShapeSig, dec: Decision, leg: str,
           table: Optional[bool] = None) -> Decision:
    """``dec`` (the "fwd" or "bwd" ``leg`` of ``resolve``, its blocks fitted
    to the sequences) with its walk over a KV head's score tiles. The grid is
    the table where the mask (causal, a window) leaves more than half as
    many tiles dead as live, the kernel reads one (the forward and the fused
    backward; the pair keeps its rectangles) and the table has at most
    TABLE_CAP_TILES steps. On a v5e a dead step of the rectangle costs some
    0.2 us and a table makes every live step 0.06 (forward) to 0.09 us
    (backward) dearer, every operand's block index being read from SMEM
    (docs/kernel_dispatch.md), so a call with few dead tiles (three of four
    live) and an unmasked call, which has none, keep the rectangle.
    ``table`` pins either walk of a masked call (tests, the sweep tool:
    ``False`` the clamped rectangle, ``True`` the table wherever a tile is
    dead, past the cap too)."""
    block_q, block_k = min(dec.block_q, sig.seq_q), min(dec.block_k, sig.seq_k)
    if sig.pattern or sig.seq_q % block_q or sig.seq_k % block_k:
        return dec
    num_q, num_k = sig.seq_q // block_q, sig.seq_k // block_k
    grid = tiles = num_q * num_k
    if sig.causal or sig.windowed:
        ranges = dec.ranges if num_q % dec.ranges == 0 else 1
        live, _ = live_tiles(num_q, num_k, block_q, block_k, sig.causal,
                             sig.window if sig.windowed else None)
        tiles = len(tile_walk(live, "q" if leg == "fwd" else "k", ranges)[0])
    reads_one = leg == "fwd" or dec.impl == IMPL_FUSED
    if table is None:
        table = tiles <= TABLE_CAP_TILES and 2 * (grid - tiles) > tiles
    return dec._replace(tiles=int(tiles), grid=int(grid),
                        table=bool(table and reads_one and tiles < grid))


def describe(fwd: Decision, bwd: Decision) -> str:
    """Compact per-leg note for reports and artifacts, e.g.
    ``attn[fwd=pallas@256x512,bwd=fused@512x512]``; a backward walked in
    several query ranges says how many (``bwd=fused@256x512/r8``), and legs
    that ``walked`` counted say their grid steps a KV head, live tiles of the
    rectangle's (``fwd=pallas@1024x1024 tiles=136/256, bwd=fused@512x512
    tiles=528/1024``; ``(grid)`` behind them where a masked leg keeps the
    clamped rectangle)."""
    def leg(name, dec):
        note = (f"{name}={dec.impl}@{dec.block_q}x{dec.block_k}"
                f"{f'/r{dec.ranges}' if dec.ranges > 1 else ''}")
        if dec.grid:
            note += (f" tiles={dec.tiles}/{dec.grid}"
                     f"{'' if dec.table or dec.tiles == dec.grid else '(grid)'}")
        return note

    return (f"attn[{leg('fwd', fwd)}{', ' if fwd.grid or bwd.grid else ','}"
            f"{leg('bwd', bwd)}]")


def resolved_note(batch=8, seq=1024, heads=16, kv_heads=None, head_dim=64,
                  dtype="bfloat16", causal=True, window=None) -> str:
    """The per-leg note at a given shape (default: the 0.4B preset's), so a
    saved report records which kernels a TPU runs there and how many grid
    steps each makes."""
    sig = make_sig((batch, seq, heads, head_dim),
                   kv_heads if kv_heads is not None else heads, seq, dtype,
                   causal, window, None)
    fwd, bwd = resolve(sig)
    return describe(walked(sig, fwd, "fwd"), walked(sig, bwd, "bwd"))
