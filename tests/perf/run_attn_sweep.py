"""Attention-kernel sweep: time the forward and the backward SEPARATELY at
one shape, each implementation over a grid of (block_q, block_k), and print
the table. It chose the blocks ``ops/kernel_dispatch.py:choose_blocks``
gives (PRs 25, 32, 34, 37: docs/kernel_dispatch.md) and is how a change to
them is checked.

Not a pytest assertion — a measurement tool (``bin/ds_kernel_tune`` is the
CLI wrapper). Runs anywhere:

    bin/ds_kernel_tune                          # chip: real timings
    JAX_PLATFORMS=cpu bin/ds_kernel_tune --interpret --quick   # CI smoke

On CPU the kernels run in Pallas interpret mode, so the timings measure the
emulation — useless as chip numbers.

Per shape the tool times:
  fwd:  xla (the reference, ``_xla_attention``), pallas (the per-head
        kernel) x blocks
  bwd:  xla (the reference's vjp), pallas x blocks (the dq + dk/dv pair),
        fused x blocks (the one-pass backward, in the query ranges the shape
        gives it or ``--ranges``'s); the pullback alone, on residuals an
        untimed forward left
``--impls`` and ``--blocks`` narrow the candidates (a block sweep of one
impl: ``--impls pallas --blocks 512x512,1024x512,1024x1024``); ``--ranges
8,16`` walks the fused backward in 8 and in 16 query ranges at each block
pair (``fused@256x512/r8``).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

IMPL_XLA = "xla"    # the reference beside the kernels; no route takes it
# candidate (block_q, block_k) grid beyond the blocks the shape gives
SWEEP_BLOCKS = ((256, 512), (512, 512), (512, 1024), (1024, 1024),
                (128, 128), (256, 256))


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
    """Candidate (block_q, block_k) grid for a kernel; XLA has none.
    ``grid`` replaces ``SWEEP_BLOCKS`` (``--blocks``)."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    if impl == IMPL_XLA:
        return [None]
    chosen = kd.choose_blocks(sig, "fused" if impl == kd.IMPL_FUSED else leg)
    if quick:
        return [chosen]
    return list(dict.fromkeys((chosen, ) + tuple(grid or SWEEP_BLOCKS)))


def sweep_shape(batch, seq, heads, kv_heads, head_dim, dtype, causal, *,
                iters, interpret, quick, impls=None, grid=None, ranges=None):
    """Sweep one shape; returns {leg: [(label, impl, blocks, ms), ...]}.
    ``grid`` replaces the kernels' block grid; ``ranges`` lists the query
    ranges to walk the fused backward in (default: what the shape gives)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import kernel_dispatch as kd
    from deepspeed_tpu.ops.attention import _xla_attention, flash_attention

    rng = np.random.default_rng(0)
    shp_q, shp_kv = (batch, seq, heads, head_dim), (batch, seq, kv_heads,
                                                    head_dim)
    q = jnp.asarray(rng.standard_normal(shp_q), dtype)
    k = jnp.asarray(rng.standard_normal(shp_kv), dtype)
    v = jnp.asarray(rng.standard_normal(shp_kv), dtype)

    sig = kd.make_sig(shp_q, kv_heads, seq, q.dtype, causal, None, None)
    impls = impls or (IMPL_XLA, kd.IMPL_PALLAS, kd.IMPL_FUSED)

    def attend(impl, blocks, walk=None):
        if impl == IMPL_XLA:
            scale = 1.0 / np.sqrt(head_dim)
            return lambda q, k, v: _xla_attention(q, k, v, scale, causal)
        bq, bk = blocks
        return lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=interpret, impl_bwd=impl,
            block_q=bq, block_k=bk, ranges=walk)

    def fwd_fn(impl, blocks, walk=None):
        f = jax.jit(attend(impl, blocks))
        return lambda: f(q, k, v)

    def bwd_fn(impl, blocks, walk=None):
        # the pullback alone: one untimed forward leaves its residuals, and
        # only the backward's kernels are in the timing (the reference's
        # float32 scores are 4 GiB at 4 x 16 x 4096^2)
        out, pull = jax.vjp(attend(impl, blocks, walk), q, k, v)
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
            walks = ranges if leg == "bwd" and impl == kd.IMPL_FUSED and ranges else [None]
            for blocks, walk in ((b, w) for b in _blocks_for(impl, sig, leg, quick, grid)
                                 for w in walks):
                if blocks is not None:
                    # a tile can't exceed the sequence — clamp, then dedupe
                    # (several candidates can clamp to the same point)
                    blocks = (min(blocks[0], seq), min(blocks[1], seq))
                    if (blocks, walk) in seen:
                        continue
                    seen.add((blocks, walk))
                label = impl if blocks is None else (
                    f"{impl}@{blocks[0]}x{blocks[1]}")
                try:
                    if leg == "bwd" and impl == kd.IMPL_FUSED:
                        walked = kd.resolve(sig, impl_bwd=impl, blocks=blocks,
                                            ranges=walk)[1].ranges
                        label += f"/r{walked}" if walked > 1 else ""
                    ms = _time(make(impl, blocks, walk), iters)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    print(f"  {leg} {label: <18} FAILED: "
                          f"{type(e).__name__}: {e}", flush=True)
                    continue
                rows.append((label, impl, blocks, ms))
                print(f"  {leg} {label: <18} {ms: >9.3f} ms", flush=True)
        if not rows:
            print(f"  {leg}: no candidate ran")
            continue
        results[leg] = rows
        label, _, _, ms = min(rows, key=lambda r: r[-1])
        print(f"  {leg} fastest: {label} ({ms:.3f} ms)", flush=True)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Time the attention kernels per leg over a grid of "
                    "blocks and print the table (docs/kernel_dispatch.md)")
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
                    help="Pallas interpret mode (CPU CI smoke)")
    ap.add_argument("--quick", action="store_true",
                    help="the shape's own blocks only (smoke test)")
    ap.add_argument("--impls", default=None,
                    help="comma list of xla,pallas,fused (default: all)")
    ap.add_argument("--blocks", default=None,
                    help="block grid as 'bqxbk,bqxbk,...' in place of "
                         "SWEEP_BLOCKS")
    ap.add_argument("--ranges", default=None,
                    help="comma list of query-range counts to walk the fused "
                         "backward in (default: what the shape gives)")
    args = ap.parse_args(argv)

    import jax
    from deepspeed_tpu.ops.registry import on_tpu

    if not on_tpu() and not args.interpret:
        print("no TPU and --interpret not set: Pallas kernels can't run; "
              "pass --interpret for a CPU smoke sweep", file=sys.stderr)
        return 2

    d = jax.devices()[0]
    kv = args.kv_heads if args.kv_heads is not None else args.heads
    print(f"attn sweep: b{args.batch} s{args.seq} h{args.heads} kv{kv} "
          f"d{args.head_dim} {args.dtype} causal={args.causal} "
          f"device_kind={getattr(d, 'device_kind', d.platform)!r}"
          f"{' (interpreted)' if args.interpret else ''}")
    sweep_shape(args.batch, args.seq, args.heads, kv, args.head_dim,
                args.dtype, args.causal, iters=args.iters,
                interpret=args.interpret, quick=args.quick,
                impls=args.impls and tuple(args.impls.split(",")),
                grid=args.blocks and [tuple(int(x) for x in b.split("x"))
                                      for b in args.blocks.split(",")],
                ranges=args.ranges and [int(r) for r in args.ranges.split(",")])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
