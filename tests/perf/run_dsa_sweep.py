"""Learned sparse attention's backward alone (``ops/dsa_attention.py``: the one
walk ``dsa_bwd`` beside the pair ``dsa_bwd_dq`` + ``dsa_bwd_dkdv``) at the
Keye-VL cell's call ``[1, 32768, 32/4, 128]`` in bf16, a 16 x 64 indexer,
``topk`` 2,048: milliseconds a call, the seconds a kernel takes to lower and
to compile, the VMEM its call asks for, and whether the one walk's dQ, dK and
dV are the pair's at the same tiles bit for bit. Every variant reads ONE mask,
forward output and log-sum-exp, made once a key tile by ``dsa_index`` and
``dsa_fwd`` (timed too). It wrote ``docs/readings/dsa_bwd_sweep_pr59.jsonl``; it is how a change
to the kernels or to ``kernel_dispatch.resolve_dsa_bwd`` is checked.

Not a pytest assertion: a measurement tool, as ``run_attn_sweep.py`` is.

    python tests/perf/run_dsa_sweep.py --out chiprun_out/dsa_bwd_sweep.jsonl   # chip
    python tests/perf/run_dsa_sweep.py --variants pair@128x512,fused@256x512
    JAX_PLATFORMS=cpu python tests/perf/run_dsa_sweep.py --interpret

On a CPU the kernels run interpreted at a cut size: the comparison holds,
the timings measure the emulation.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

BATCH, SEQ, HEADS, KV, D, HI, DI, TOPK = 1, 32768, 32, 4, 128, 16, 64, 2048
# kernel@QxK: the backward's query tile by the call's key tile; the index,
# mask and forward kernels of a key tile run at (128, K)
VARIANTS = ("pair@128x512,fused@128x512,pair@256x512,fused@256x512,"
            "pair@128x1024,fused@128x1024")


def _time(fn, iters: int) -> float:
    import jax
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _operands(seed: int, seq: int, heads: int, kv: int, d: int):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    shapes = ((BATCH, seq, heads, d), (BATCH, seq, kv, d), (BATCH, seq, kv, d),
              (BATCH, seq, HI, DI), (BATCH, seq, DI), (BATCH, seq, heads, d))
    q, k, v, qi, ki, do = (jax.random.normal(key, s, jnp.float32).astype(jnp.bfloat16)
                           for key, s in zip(ks, shapes))
    w = jax.random.normal(ks[6], (BATCH, seq, HI), jnp.float32) / np.sqrt(HI)
    return q, k, v, qi, ki, w, do


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default=VARIANTS)
    ap.add_argument("--interpret", action="store_true",
                    help="CPU: interpreted kernels at 512 tokens, 8/2 heads of 32")
    args = ap.parse_args(argv)
    import jax
    from deepspeed_tpu.ops import dsa_attention as dsa
    from deepspeed_tpu.ops import kernel_dispatch as kd
    seq, heads, kv, d, topk = (512, 8, 2, 32, 64) if args.interpret else (
        SEQ, HEADS, KV, D, TOPK)
    cut = 4 if args.interpret else 1        # the tiles of a cut size
    scale = float(1.0 / np.sqrt(d))
    device = jax.devices()[0].device_kind
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps({"device": device, "shape": [BATCH, seq, heads, kv, d],
                           "topk": topk, **row})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def measure(call, given, row):
        """``row`` with the call's milliseconds and its seconds to lower and
        to compile, and what it returned (None where the compiler refuses)."""
        try:
            t0 = time.perf_counter()
            lowered = jax.jit(call).lower(*given)
            t1 = time.perf_counter()
            fn = lowered.compile()
            t2 = time.perf_counter()
            got = fn(*given)
            ms = _time(lambda: fn(*given), 1 if args.interpret else args.iters)
        except Exception as e:       # tiles the compiler refuses
            emit({**row, "error": str(e)[:400]})
            return None
        row.update(ms=ms, lower_s=t1 - t0, compile_s=t2 - t1)
        return got

    q, k, v, qi, ki, w, do = _operands(args.seed, seq, heads, kv, d)
    sig = kd.make_sig(q.shape, kv, seq, q.dtype, True, None, None, pattern=f"dsa{topk}")
    forwards, pairs, want = {}, {}, None
    for variant in args.variants.split(","):
        kernel, tiles = variant.split("@")
        tile_q, block_k = (int(t) // cut for t in tiles.split("x"))
        blocks = (128 // cut, block_k)
        if block_k not in forwards:      # the mask, o and lse of this key tile
            row = {"leg": "index", "blocks": blocks}
            made = measure(lambda *a: dsa.dsa_index(*a, topk, blocks, args.interpret),
                           (qi, ki, w), row)
            emit(row)
            mask = made[4]
            row = {"leg": "fwd", "blocks": blocks}
            o, lse = measure(lambda *a: dsa._dsa_fwd(*a, scale, blocks, args.interpret),
                             (q, k, v, mask), row)
            emit(row)
            forwards[block_k] = (mask, o, lse)
        mask, o, lse = forwards[block_k]
        leg = "fused" if kernel == kd.IMPL_FUSED else "bwd"
        need = kd.dsa_vmem_bytes(leg, kv, heads // kv, d, 2, tile_q, block_k, seq)
        row = {"leg": "bwd", "variant": variant, "blocks": blocks, "tile_q": tile_q,
               "rule": kd.resolve_dsa_bwd(sig, blocks), "vmem_estimate": need,
               "vmem_limit": kd.vmem_limit_bytes(need)}
        got = measure(lambda *a: dsa._dsa_bwd(*a, scale, blocks, (kernel, tile_q),
                                              args.interpret),
                      (q, k, v, mask, o, lse, do), row)
        if got is None:
            continue
        got = [np.asarray(a, np.float32) for a in got]
        want = want or got          # the first variant of the list: the pair
        if kernel != kd.IMPL_FUSED:
            pairs[tiles] = got
        elif tiles in pairs:        # dQ, dK, dV against the pair at the walk's tiles
            row["bit_equal_to_the_pair_at_its_tiles"] = [
                bool(np.array_equal(a, b)) for a, b in zip(got, pairs[tiles])]
        emit({**row, "finite": all(bool(np.isfinite(a).all()) for a in got),
              "bit_equal_to_first": [bool(np.array_equal(a, b))
                                     for a, b in zip(got, want)],
              # in units of the gradient's largest entry
              "max_diff_to_first": [float(np.abs(a - b).max() / np.abs(b).max())
                                    for a, b in zip(got, want)]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
