"""Shape-aware attention kernel dispatch.

The round-5 chip breakdown proved the static kernel choice wrong at the
bench shape: the Pallas flash *forward* lost to XLA's fused attention
(62.9 ms vs 42.7 ms at hd64/seq1024) while the flash *backward* is the leg
the Pallas pair actually wins (no [S, S] score materialization in the
recompute).  DeepCompile (arXiv:2504.09983) argues exactly this: profile-
guided, per-shape kernel selection should replace static choices in
distributed training stacks.

This module picks the forward and backward implementations *independently*
per (shape, dtype, causal/window/softcap flags, device kind).  Precedence
per leg, strongest first:

1. explicit ``impl_fwd``/``impl_bwd`` kwargs on ``flash_attention`` (tests,
   the sweep tool);
2. ``DS_TPU_ATTN_FWD`` / ``DS_TPU_ATTN_BWD`` env (``xla|pallas|folded``, and
   ``fused`` for the backward);
3. legacy ``DS_TPU_FLASH_FOLDED``: nonzero forces the folded Pallas pair on
   BOTH legs (existing A/B scripts and tests depend on that); ``0`` pins
   the per-head variant for any leg that resolves to Pallas;
4. a *measured* entry in the persistent autotune cache
   (``autotune_cache.py``, written by ``bin/ds_kernel_tune``);
5. the built-in heuristic table below (which encodes the measured
   42.7 < 62.9 ms fwd result: XLA fused forward at hd64 / seq >= 1024
   while its float32 scores stay under 2 GiB; Pallas backward always, the
   fused kernel wherever its whole-sequence dQ accumulator fits in VMEM,
   else the dq + dk/dv pair).

Blocks follow the same idea: explicit args > ``DS_TPU_FLASH_BLOCKS`` env >
measured cache blocks > ``choose_blocks``, a pure function of the shape
signature and a VMEM estimate of the leg's tiles (1024 folded query rows a
step at any GQA group, and 512 keys or as many as the queries).
"""

import math
import os
from typing import NamedTuple, Optional

from .autotune_cache import get_cache
from ..utils.logging import logger

IMPL_XLA = "xla"
IMPL_PALLAS = "pallas"  # per-head kernels (ops/attention.py)
IMPL_FOLDED = "folded"  # head-folded kernels (ops/attention_folded.py)
# backward only: the per-head kernels' one-pass backward (dQ, dK and dV from
# one walk over the score tiles, ``flash_dkdv_dq``); "pallas" there is the
# pair ``flash_dq`` + ``flash_dkdv``
IMPL_FUSED = "fused"
_IMPLS = {"fwd": (IMPL_XLA, IMPL_PALLAS, IMPL_FOLDED),
          "bwd": (IMPL_XLA, IMPL_PALLAS, IMPL_FOLDED, IMPL_FUSED)}

# candidate (block_q, block_k) grid the offline sweep times, beyond the
# defaults — the round-5 sweep died at the window edge before reaching them
SWEEP_BLOCKS = ((256, 512), (512, 512), (512, 1024), (1024, 1024),
                (128, 128), (256, 256))


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


class Decision(NamedTuple):
    """One leg's resolved choice. ``source`` records provenance for the
    artifacts: explicit | env | legacy-env | measured | heuristic."""
    impl: str
    block_q: int
    block_k: int
    source: str


def make_sig(q_shape, kv_heads: int, seq_k: int, dtype, causal: bool,
             window, softcap, pattern: str = "", v_dim: int = 0) -> ShapeSig:
    b, sq, h, d = q_shape
    return ShapeSig(batch=int(b), seq_q=int(sq), seq_k=int(seq_k),
                    heads=int(h), kv_heads=int(kv_heads), head_dim=int(d),
                    dtype=str(dtype), causal=bool(causal),
                    windowed=window is not None,
                    softcapped=softcap is not None, pattern=pattern,
                    v_dim=0 if int(v_dim) == int(d) else int(v_dim))


def signature(leg: str, sig: ShapeSig, device_kind: str) -> str:
    """Cache key: leg + device kind + the full shape signature.  Versioned
    at the file level (autotune_cache.CACHE_VERSION), so this string only
    needs to be collision-free, not forward-compatible."""
    return (f"{leg}|{device_kind}|b{sig.batch}|sq{sig.seq_q}|sk{sig.seq_k}"
            f"|h{sig.heads}|kv{sig.kv_heads}|d{sig.head_dim}|{sig.dtype}"
            f"|c{int(sig.causal)}|w{int(sig.windowed)}"
            f"|sc{int(sig.softcapped)}" + (f"|p{sig.pattern}" if sig.pattern else "")
            + (f"|dv{sig.v_dim}" if sig.v_dim else ""))


def device_kind() -> str:
    """Device kind string for cache keys ("TPU v5e", "cpu", ...).  Interpret
    mode keys as "interpret" so CPU sweep results never masquerade as chip
    measurements."""
    import jax
    d = jax.devices()[0]
    return getattr(d, "device_kind", None) or d.platform


def _env_impl(name: str, leg: str) -> Optional[str]:
    val = os.environ.get(name, "").strip().lower()
    if not val:
        return None
    if val not in _IMPLS[leg]:
        logger.warning(f"{name}={val!r} ignored (want one of {_IMPLS[leg]})")
        return None
    return val


def _variant_preference() -> Optional[str]:
    """Which Pallas variant (per-head vs folded) a Pallas leg should use
    when nothing shape-specific decided it: the legacy env, else none."""
    env = os.environ.get("DS_TPU_FLASH_FOLDED")
    if env is not None:
        return IMPL_FOLDED if env not in ("", "0") else IMPL_PALLAS
    return None


def _env_blocks() -> Optional[tuple]:
    env = os.environ.get("DS_TPU_FLASH_BLOCKS")
    if not env:
        return None
    try:
        bq, bk = (int(x) for x in env.split(","))
        return bq, bk
    except ValueError:
        logger.warning(f"DS_TPU_FLASH_BLOCKS={env!r} ignored (want 'bq,bk')")
        return None


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
# The most the fused backward's estimate may be for the heuristic to choose
# it: half the core's 128 MiB. The call asks for its estimate and a quarter
# more (``vmem_limit_bytes``), 80 MiB at the cap, which leaves 48 MiB for
# what Mosaic keeps beyond the estimate (its own stack: the state-space
# scan's refusal at 1.25x, docs/kernel_dispatch.md) and for what XLA holds
# in VMEM around the call. The estimate grows with ``group * seq_q`` (the
# float32 dQ of one KV head's whole sequence): 16,384 tokens at group 4 are
# 55 MiB, 24,576 are 71 MiB and take the dq + dk/dv pair.
FUSED_VMEM_CAP_BYTES = 64 * 2**20
# The most the XLA forward's materialised float32 scores may take for the
# heuristic to choose it: four times the one shape the rule was measured at,
# a bound and not a crossover. A forward-only sweep at head 64, group 4 (v5e,
# PR 31, docs/kernel_dispatch.md) found no crossover to place it at: the
# Pallas forward won at every size from 0.5 to 8 GiB, by 2.3x under the
# bound and by up to 10x over it.
XLA_FWD_SCORE_BYTES = 2 * 2**30


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


def fused_vmem_bytes(sig: ShapeSig) -> int:
    """``flash_vmem_bytes`` of the fused backward at ``sig``, with the blocks
    the shape gives it: what ``_heuristic_impl`` holds against
    FUSED_VMEM_CAP_BYTES."""
    return flash_vmem_bytes("fused", max(1, sig.heads // sig.kv_heads),
                            vmem_width(sig.head_dim, sig.v_dim),
                            4 if "32" in sig.dtype else 2,
                            *choose_blocks(sig, "fused"), seq_q=sig.seq_q)


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
    estimate (``fused_vmem_bytes``) fits is ``_heuristic_impl``'s to ask."""
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


def _heuristic_impl(leg: str, sig: ShapeSig) -> str:
    """Built-in table when no measurement exists.

    Forward: XLA's fused softmax-attention beat the Pallas flash forward at
    the bench shape (42.7 vs 62.9 ms, hd64/seq1024, v5e 2026-08-01);
    the regime is "scores fit comfortably and XLA fuses the whole chain",
    which holds for hd64 at seq >= 1024 on sequences that are not
    window-limited.  Windowed shapes keep the Pallas forward: it skips
    out-of-window blocks entirely, XLA still materializes [S, S].

    Backward: Pallas flash always — the recompute never materializes
    scores, which is where the memory and time win lives (the same
    breakdown measured the pallas pair ahead on fwd+bwd). One kernel for
    dQ, dK and dV (``fused``: each score tile rebuilt once, not twice)
    where its estimate, with the blocks the shape gives, fits
    FUSED_VMEM_CAP_BYTES; else the dq + dk/dv pair.
    """
    if leg == "fwd":
        # "fit comfortably": the float32 scores of the whole call, which the
        # XLA forward writes to HBM. 0.5 GiB at the measured shape (8 x 16
        # heads x 1024^2); 32 GiB at 4 x 32 heads x 8192^2, which no chip
        # holds, so long sequences keep the Pallas forward too
        scores = 4 * sig.batch * sig.heads * sig.seq_q * sig.seq_k
        if (sig.head_dim <= 64 and sig.seq_k >= 1024 and not sig.windowed
                and scores <= XLA_FWD_SCORE_BYTES):
            return IMPL_XLA
        return IMPL_PALLAS
    return (IMPL_FUSED if fused_vmem_bytes(sig) <= FUSED_VMEM_CAP_BYTES
            else IMPL_PALLAS)


def resolve_leg(leg: str, sig: ShapeSig, kind: Optional[str] = None, *,
                explicit_impl: Optional[str] = None,
                explicit_blocks: Optional[tuple] = None,
                pallas_only: bool = False) -> Decision:
    """Resolve one leg ("fwd" | "bwd") to a Decision.  ``pallas_only``
    (force_pallas=True callers: kernel-math tests) restricts the choice to
    the Pallas variants — an XLA pick degrades to the per-head kernel."""
    kind = kind if kind is not None else device_kind()
    variant = _variant_preference()

    impl = None
    source = None
    if explicit_impl is not None:
        assert explicit_impl in _IMPLS[leg], (leg, explicit_impl)
        impl, source = explicit_impl, "explicit"
    if impl is None:
        env = _env_impl("DS_TPU_ATTN_FWD" if leg == "fwd" else "DS_TPU_ATTN_BWD",
                        leg)
        if env is not None:
            impl, source = env, "env"
    if impl is None and os.environ.get("DS_TPU_FLASH_FOLDED") not in (None, "", "0"):
        # legacy env: the folded kernels run end to end (both legs)
        impl, source = IMPL_FOLDED, "legacy-env"

    measured = None
    if impl is None:
        measured = get_cache().lookup(signature(leg, sig, kind))
        if measured and measured.get("impl") in _IMPLS[leg]:
            impl, source = measured["impl"], "measured"
        else:
            measured = None
    if impl is None:
        impl, source = _heuristic_impl(leg, sig), "heuristic"
        if impl == IMPL_PALLAS and variant == IMPL_FOLDED:
            impl = IMPL_FOLDED

    if pallas_only and impl == IMPL_XLA:
        impl = variant or IMPL_PALLAS
        source += "+pallas-forced"

    # blocks: explicit > env > measured > chosen from the shape
    blocks = explicit_blocks or _env_blocks()
    if blocks is None and measured is not None:
        try:
            blocks = (int(measured["block_q"]), int(measured["block_k"]))
        except (KeyError, TypeError, ValueError):
            blocks = None
    if blocks is None:
        blocks = choose_blocks(sig, "fused" if impl == IMPL_FUSED else leg)
    return Decision(impl=impl, block_q=int(blocks[0]), block_k=int(blocks[1]),
                    source=source)


def resolve(sig: ShapeSig, kind: Optional[str] = None, *,
            impl_fwd: Optional[str] = None, impl_bwd: Optional[str] = None,
            blocks: Optional[tuple] = None, pallas_only: bool = False):
    """(fwd Decision, bwd Decision) for one attention call site."""
    fwd = resolve_leg("fwd", sig, kind, explicit_impl=impl_fwd,
                      explicit_blocks=blocks, pallas_only=pallas_only)
    bwd = resolve_leg("bwd", sig, kind, explicit_impl=impl_bwd,
                      explicit_blocks=blocks, pallas_only=pallas_only)
    return fwd, bwd


def describe(fwd: Decision, bwd: Decision) -> str:
    """Compact per-leg note for bench unit tags / artifacts, e.g.
    ``attn[fwd=xla:heuristic,bwd=pallas@256x512:measured]``."""

    def leg(d: Decision) -> str:
        blocks = ("" if d.impl == IMPL_XLA
                  else f"@{d.block_q}x{d.block_k}")
        return f"{d.impl}{blocks}:{d.source}"

    return f"attn[fwd={leg(fwd)},bwd={leg(bwd)}]"


def table_source() -> str:
    """One line for ds_report: where dispatch decisions come from."""
    return get_cache().source_description()


def resolved_note(batch=8, seq=1024, heads=16, kv_heads=None, head_dim=64,
                  dtype="bfloat16", causal=True, window=None,
                  kind: Optional[str] = None) -> str:
    """The per-leg dispatch note at a given (default: THE bench) shape —
    reporting surfaces call this so every banked artifact records which
    kernels actually ran."""
    sig = make_sig((batch, seq, heads, head_dim),
                   kv_heads if kv_heads is not None else heads, seq, dtype,
                   causal, window, None)
    return describe(*resolve(sig, kind))
