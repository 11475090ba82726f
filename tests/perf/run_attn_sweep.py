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
pair (``fused@256x512/r8``). ``--window`` and ``--v-dim`` give a windowed
call and one whose values are narrower than its keys (``mla_*``).
``--walks table,grid`` times a masked call on its table of live tiles and on
the clamped rectangle (``fused@512x512:grid``) and says whether the two
walks' results are equal bit for bit; ``--out`` appends a JSON line a row
(the shape, the leg's ``Decision`` with its live tiles and grid steps, ms).
"""

import argparse
import json
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
                iters, interpret, quick, impls=None, grid=None, ranges=None,
                window=None, v_dim=None, walks=None, out=None):
    """Sweep one shape; returns {leg: [(label, impl, blocks, ms), ...]}.
    ``grid`` replaces the kernels' block grid; ``ranges`` lists the query
    ranges to walk the fused backward in (default: what the shape gives);
    ``walks`` the walks of a masked call to time (``True`` its table of live
    tiles, ``False`` the clamped rectangle; default: what the shape gives),
    whose results are compared bit for bit; ``out``: a file that takes a JSON
    line a row."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import kernel_dispatch as kd
    from deepspeed_tpu.ops.attention import _xla_attention, flash_attention

    rng = np.random.default_rng(0)
    shp_q, shp_kv = (batch, seq, heads, head_dim), (batch, seq, kv_heads,
                                                    head_dim)
    q = jnp.asarray(rng.standard_normal(shp_q), dtype)
    k = jnp.asarray(rng.standard_normal(shp_kv), dtype)
    v = jnp.asarray(rng.standard_normal(shp_kv[:3] + (v_dim or head_dim, )), dtype)

    sig = kd.make_sig(shp_q, kv_heads, seq, q.dtype, causal, window, None,
                      v_dim=v.shape[-1])
    device = jax.devices()[0]
    device_kind = getattr(device, "device_kind", device.platform)
    impls = impls or (IMPL_XLA, kd.IMPL_PALLAS, kd.IMPL_FUSED)

    def attend(impl, blocks, walk=None, table=None):
        if impl == IMPL_XLA:
            scale = 1.0 / np.sqrt(head_dim)
            return lambda q, k, v: _xla_attention(q, k, v, scale, causal, window)
        bq, bk = blocks
        return lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window, interpret=interpret,
            impl_bwd=impl, block_q=bq, block_k=bk, ranges=walk, table=table)

    def fwd_fn(impl, blocks, walk=None, table=None):
        f = jax.jit(attend(impl, blocks, table=table))
        return lambda: f(q, k, v)

    def bwd_fn(impl, blocks, walk=None, table=None):
        # the pullback alone: one untimed forward leaves its residuals, and
        # only the backward's kernels are in the timing (the reference's
        # float32 scores are 4 GiB at 4 x 16 x 4096^2)
        res, pull = jax.vjp(attend(impl, blocks, walk, table), q, k, v)
        g = jnp.ones_like(res)
        run = jax.jit(lambda pull, g: pull(g))
        return lambda: run(pull, g)

    def same(a, b):
        return all(bool(jnp.array_equal(x, y)) for x, y in
                   zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))

    results = {}
    for leg, make in (("fwd", fwd_fn), ("bwd", bwd_fn)):
        rows = []
        for impl in impls:
            if leg == "fwd" and impl == kd.IMPL_FUSED:
                continue    # a backward: its forward is the per-head one
            in_ranges = ranges if leg == "bwd" and impl == kd.IMPL_FUSED and ranges else [None]
            tables = walks if impl != IMPL_XLA and walks else [None]
            # candidates seen, and (blocks, ranges) -> its first walk's results
            seen, first = set(), {}
            for blocks, walk, table in (
                    (b, w, t) for b in _blocks_for(impl, sig, leg, quick, grid)
                    for w in in_ranges for t in tables):
                if blocks is not None:
                    # a tile can't exceed the sequence: clamp
                    blocks = (min(blocks[0], seq), min(blocks[1], seq))
                label = impl if blocks is None else (
                    f"{impl}@{blocks[0]}x{blocks[1]}")
                line = {"shape": [batch, seq, heads, kv_heads, head_dim,
                                  v.shape[-1]],
                        "causal": causal, "window": window, "dtype": str(dtype),
                        "device": device_kind, "leg": leg, "impl": impl}
                try:
                    if impl != IMPL_XLA:
                        dec = kd.resolve(sig, impl_bwd=impl if leg == "bwd" else None,
                                         blocks=blocks, ranges=walk)[leg == "bwd"]
                        dec = kd.walked(sig, dec, leg, table)
                        # several candidates can clamp to the same point, and
                        # a pin of the walk can change nothing
                        if (blocks, walk, dec.table) in seen:
                            continue
                        seen.add((blocks, walk, dec.table))
                        label += f"/r{dec.ranges}" if dec.ranges > 1 else ""
                        # two walks side by side: the one on the rectangle
                        label += ":grid" if (
                            len(tables) > 1 and not dec.table
                            and dec.tiles < dec.grid
                            and (leg == "fwd" or impl == kd.IMPL_FUSED)) else ""
                        line.update(dec._asdict())
                    run = make(impl, blocks, walk, table)
                    ms = _time(run, iters)
                    if (blocks, walk) in first:     # the second walk of a pair
                        line["equal"] = same(first[blocks, walk], run())
                    elif len(tables) > 1:
                        first[blocks, walk] = run()
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    print(f"  {leg} {label: <18} FAILED: "
                          f"{type(e).__name__}: {e}", flush=True)
                    continue
                rows.append((label, impl, blocks, ms))
                line.update(label=label, ms=ms)
                if out is not None:
                    out.write(json.dumps(line) + "\n")
                    out.flush()
                print(f"  {leg} {label: <18} {ms: >9.3f} ms"
                      + (f"  tiles {line['tiles']}/{line['grid']}"
                         if line.get("grid") else "")
                      + (f"  equal={line['equal']}" if "equal" in line else ""),
                      flush=True)
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
    ap.add_argument("--window", type=int, default=None,
                    help="a sliding window of this many keys")
    ap.add_argument("--v-dim", type=int, default=None,
                    help="the values' width where it is not --head-dim (mla_*)")
    ap.add_argument("--walks", default=None,
                    help="comma list of table,grid: a masked call on its table "
                         "of live tiles and on the clamped rectangle, compared "
                         "bit for bit (default: what the shape gives)")
    ap.add_argument("--out", default=None,
                    help="append a JSON line a row to this file")
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
          f"d{args.head_dim}{f'|{args.v_dim}' if args.v_dim else ''} "
          f"{args.dtype} causal={args.causal} window={args.window} "
          f"device_kind={getattr(d, 'device_kind', d.platform)!r}"
          f"{' (interpreted)' if args.interpret else ''}")
    sweep_shape(args.batch, args.seq, args.heads, kv, args.head_dim,
                args.dtype, args.causal, iters=args.iters,
                interpret=args.interpret, quick=args.quick,
                impls=args.impls and tuple(args.impls.split(",")),
                grid=args.blocks and [tuple(int(x) for x in b.split("x"))
                                      for b in args.blocks.split(",")],
                ranges=args.ranges and [int(r) for r in args.ranges.split(",")],
                window=args.window, v_dim=args.v_dim,
                walks=args.walks and [w == "table" for w in args.walks.split(",")],
                out=args.out and open(args.out, "a"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
