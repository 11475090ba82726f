"""Offline attention-kernel sweep: time each (impl, block_q, block_k)
candidate SEPARATELY for the forward and backward legs and commit the
winners to the persistent autotune cache (``ops/autotune_cache.py``) that
``ops/kernel_dispatch.py`` reads on the next dispatch.

Not a pytest assertion — a measurement tool (``bin/ds_kernel_tune`` is the
CLI wrapper). Runs anywhere:

    bin/ds_kernel_tune                          # chip: real timings
    JAX_PLATFORMS=cpu bin/ds_kernel_tune --interpret --quick   # CI smoke

On CPU the kernels run in Pallas interpret mode, so the timings measure the
emulation — useless as chip numbers, which is why interpret results are
keyed under device kind "interpret" (``kernel_dispatch.device_kind`` never
lets them masquerade as chip measurements). On a real TPU the sweep covers
the {(512,512),(512,1024),(1024,1024)} grid the round-5 session never
reached, plus the current defaults.

Per shape the tool times:
  fwd:  xla fused, pallas per-head x blocks, folded x blocks
  bwd:  xla (vjp recompute), pallas per-head x blocks (the dq + dk/dv
        pair), fused x blocks (the per-head one-pass backward, on the
        per-head forward's residuals), folded x blocks;
        the pullback alone, on residuals an untimed forward left
and writes one cache entry per (leg, shape signature, device kind).
``--impls`` and ``--blocks`` narrow the candidates (a block sweep of one
impl: ``--dry-run --impls pallas --blocks 512x512,1024x512,1024x1024``).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def _time(fn, iters: int, warmup: int = 1) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def _blocks_for(impl: str, sig, leg: str, quick: bool, grid=None):
    """Candidate (block_q, block_k) grid for a Pallas impl; XLA has none.
    ``grid`` replaces ``SWEEP_BLOCKS`` (``--blocks``)."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    if impl == kd.IMPL_XLA:
        return [None]
    chosen = kd.choose_blocks(sig, "fused" if impl == kd.IMPL_FUSED else leg)
    if quick:
        return [chosen]
    return list(dict.fromkeys((chosen, ) + tuple(grid or kd.SWEEP_BLOCKS)))


def sweep_shape(batch, seq, heads, kv_heads, head_dim, dtype, causal, *,
                iters, interpret, quick, impls=None, commit=True,
                grid=None):
    """Sweep one shape; returns {leg: (winner_dict, rows)} and optionally
    commits the winners to the autotune cache. ``grid`` replaces the
    Pallas candidates' block grid."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import kernel_dispatch as kd
    from deepspeed_tpu.ops.attention import flash_attention
    from deepspeed_tpu.ops.autotune_cache import get_cache

    rng = np.random.default_rng(0)
    shp_q, shp_kv = (batch, seq, heads, head_dim), (batch, seq, kv_heads,
                                                    head_dim)
    q = jnp.asarray(rng.standard_normal(shp_q), dtype)
    k = jnp.asarray(rng.standard_normal(shp_kv), dtype)
    v = jnp.asarray(rng.standard_normal(shp_kv), dtype)

    kind = "interpret" if interpret else kd.device_kind()
    sig = kd.make_sig(shp_q, kv_heads, seq, q.dtype, causal, None, None)
    impls = impls or (kd.IMPL_XLA, kd.IMPL_PALLAS, kd.IMPL_FUSED,
                      kd.IMPL_FOLDED)

    def fwd_fn(impl, blocks):
        bq, bk = blocks or (None, None)
        f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=interpret, impl_fwd=impl,
            impl_bwd=impl if impl != kd.IMPL_XLA else kd.IMPL_XLA,
            block_q=bq, block_k=bk))
        return lambda: f(q, k, v)

    def bwd_fn(impl, blocks):
        # the pullback alone: one untimed forward of the same impl leaves
        # its residuals, and only the backward's kernels are in the timing
        # (an XLA forward's float32 scores are 4 GiB at 4 x 16 x 4096^2)
        bq, bk = blocks or (None, None)
        out, pull = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=interpret,
            impl_fwd=kd.IMPL_PALLAS if impl == kd.IMPL_FUSED else impl,
            impl_bwd=impl, block_q=bq, block_k=bk), q, k, v)
        g = jnp.ones_like(out)
        run = jax.jit(lambda pull, g: pull(g))
        return lambda: run(pull, g)

    results = {}
    for leg, make in (("fwd", fwd_fn), ("bwd", bwd_fn)):
        rows = []
        for impl in impls:
            if leg == "fwd" and impl == kd.IMPL_FUSED:
                continue    # a backward: its forward is the per-head one
            seen = set()
            for blocks in _blocks_for(impl, sig, leg, quick, grid):
                if blocks is not None:
                    # a tile can't exceed the sequence — clamp, then dedupe
                    # (several candidates can clamp to the same point)
                    blocks = (min(blocks[0], seq), min(blocks[1], seq))
                    if blocks in seen:
                        continue
                    seen.add(blocks)
                label = impl if blocks is None else (
                    f"{impl}@{blocks[0]}x{blocks[1]}")
                try:
                    ms = _time(make(impl, blocks), iters)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    print(f"  {leg} {label: <18} FAILED: "
                          f"{type(e).__name__}: {e}", flush=True)
                    continue
                rows.append((label, impl, blocks, ms))
                print(f"  {leg} {label: <18} {ms: >9.3f} ms", flush=True)
        if not rows:
            print(f"  {leg}: no candidate ran — leg left to heuristics")
            continue
        label, impl, blocks, ms = min(rows, key=lambda r: r[-1])
        bq, bk = blocks or kd.choose_blocks(sig, leg)
        entry = {"impl": impl, "block_q": bq, "block_k": bk,
                 "ms": round(ms, 4),
                 "note": f"ds_kernel_tune iters={iters}"}
        results[leg] = (entry, rows)
        if commit:
            get_cache().commit(kd.signature(leg, sig, kind), entry)
        print(f"  {leg} winner: {label} ({ms:.3f} ms)"
              f"{' -> cache' if commit else ''}", flush=True)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Sweep attention kernels per leg; commit winners to the "
                    "persistent autotune cache (see docs/kernel_dispatch.md)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="default: --heads (MHA)")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--no-causal", dest="causal", action="store_false")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode (CPU CI smoke; results key "
                         "under device kind 'interpret')")
    ap.add_argument("--quick", action="store_true",
                    help="defaults-only block grid (smoke test)")
    ap.add_argument("--dry-run", action="store_true",
                    help="time everything, commit nothing")
    ap.add_argument("--impls", default=None,
                    help="comma list of xla,pallas,fused,folded (default: all)")
    ap.add_argument("--blocks", default=None,
                    help="Pallas block grid as 'bqxbk,bqxbk,...' in place "
                         "of kernel_dispatch.SWEEP_BLOCKS")
    args = ap.parse_args(argv)

    import jax
    from deepspeed_tpu.ops import kernel_dispatch as kd
    from deepspeed_tpu.ops.autotune_cache import get_cache
    from deepspeed_tpu.ops.registry import on_tpu

    if not on_tpu() and not args.interpret:
        print("no TPU and --interpret not set: Pallas kernels can't run; "
              "pass --interpret for a CPU smoke sweep", file=sys.stderr)
        return 2

    kind = "interpret" if args.interpret else kd.device_kind()
    kv = args.kv_heads if args.kv_heads is not None else args.heads
    print(f"attn sweep: b{args.batch} s{args.seq} h{args.heads} kv{kv} "
          f"d{args.head_dim} {args.dtype} causal={args.causal} "
          f"device_kind={kind!r} cache={get_cache().path}")
    sweep_shape(args.batch, args.seq, args.heads, kv, args.head_dim,
                args.dtype, args.causal, iters=args.iters,
                interpret=args.interpret, quick=args.quick,
                impls=args.impls and tuple(args.impls.split(",")),
                commit=not args.dry_run,
                grid=args.blocks and [tuple(int(x) for x in b.split("x"))
                                      for b in args.blocks.split(",")])
    if not args.dry_run:
        print(f"table now: {get_cache().source_description()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
