"""Kimi Delta Attention's chunk kernels alone (``ops/kda.py``: ``kda_chunk_fwd``,
``kda_chunk_bwd``) over the heads a grid step takes, at the Ling-3.0 cell's
call ``[1, 16384, 32 x 128]`` in bf16 with chunks of 64: milliseconds a call,
the seconds a kernel takes to lower (Mosaic's part) and to compile, the VMEM
its call asks for, and whether a block of heads gives what one head a step
gives, bit for bit. It wrote ``docs/readings/kda_heads_sweep_pr49.jsonl`` and,
since the kernels make the row norms, the beta products and the gated output
norm themselves (PR 53: their operands are the convolutions' raw q, k and v,
both gates' pre-activations and beta a head a lane),
``docs/readings/kda_heads_sweep_pr53.jsonl``; and, parent beside change, when
the triangular solve went to blocks (PR 55, ``ops/kda.py::_inverse``),
``docs/readings/kda_heads_sweep_pr55.jsonl``, with Gated DeltaNet's kernels
(``ops/gdn.py``, which run the same solve) at the Qwen3-Next cell's call ``[1,
32768, 16 | 32 x 128]`` under ``--gdn``. It is how a change to the kernels or
to ``kernel_dispatch.choose_kda_heads`` is checked.

Not a pytest assertion: a measurement tool, as ``run_attn_sweep.py`` is.

    python tests/perf/run_kda_sweep.py --out chiprun_out/kda_sweep.jsonl   # chip
    python tests/perf/run_kda_sweep.py --blocks 4 --gdn --side change      # PR 55's lines
    JAX_PLATFORMS=cpu python tests/perf/run_kda_sweep.py --interpret

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

BATCH, SEQ, HEADS, D, CHUNK = 1, 16384, 32, 128, 64
HBM_BYTES_S = 819e9     # one TPU v5e chip (benchmark/peaks.json has the source)


def _time(fn, iters: int) -> float:
    import jax
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _operands(seed: int, seq: int, heads: int):
    """The kernels' operands as ``kda_fused`` hands them over: q, k and v as a
    convolution and SiLU leave them (no row of unit length), a gate's
    pre-activation that decays a few per cent a token, the output gate's,
    ``beta`` in (0, 1) a head a lane, the lanes of rate, bias and the output
    norm's weight; and the output's gradient."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.kda import LANES
    from deepspeed_tpu.ops.ssd import SUBLANES
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    shape = (BATCH, seq, heads, D)
    q, k, v = (jax.nn.silu(jax.random.normal(key, shape)) for key in ks[:3])
    pre = 2.0 * jax.random.normal(ks[3], shape) - 4.0
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    dy = jax.random.normal(ks[5], shape)
    rate = jax.random.uniform(ks[6], (heads, ), minval=1.0, maxval=4.0)
    bias = 0.3 * jax.random.normal(ks[7], (heads * D, ))
    gate = jax.random.normal(ks[8], shape)
    weight = 1.0 + 0.1 * jax.random.normal(ks[9], (D, ))
    lanes = jnp.zeros((SUBLANES, heads * D), jnp.float32)
    lanes = lanes.at[0].set(jnp.repeat(rate, D)).at[1].set(bias).at[2].set(
        jnp.tile(weight, heads))
    flat = lambda a: a.astype(jnp.bfloat16).reshape(BATCH, seq, heads * D)  # noqa: E731
    beta = jnp.pad(beta, ((0, 0), (0, 0), (0, -heads % LANES)))
    return [flat(a) for a in (q, k, v, pre, gate)] + [beta, lanes], flat(dy)


GDN_SEQ, GDN_K_HEADS = 32768, 16     # the Qwen3-Next cell's call; 32 value heads


def _gdn_operands(seed: int, seq: int, k_heads: int, v_heads: int):
    """The Gated DeltaNet kernels' operands as ``gdn_fused`` hands them over:
    q and k a key head, v and the output gate a value head, the log decay (a
    few per cent a token, as the cell's mean decay of 0.83) and ``beta`` a
    head a lane, the output norm's weight a row; and the output's gradient."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.kda import LANES
    from deepspeed_tpu.ops.ssd import SUBLANES
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    flat = lambda a: a.astype(jnp.bfloat16).reshape(BATCH, seq, -1)  # noqa: E731
    by_lane = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, LANES - v_heads)))  # noqa: E731
    q, k = (flat(jax.nn.silu(jax.random.normal(key, (BATCH, seq, k_heads, D))))
            for key in ks[:2])
    v, z, dy = (flat(jax.random.normal(key, (BATCH, seq, v_heads, D))) for key in ks[2:5])
    g = -0.2 * jax.nn.softplus(jax.random.normal(ks[5], (BATCH, seq, v_heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[6], (BATCH, seq, v_heads)))
    lanes = jnp.zeros((SUBLANES, v_heads * D), jnp.float32).at[0].set(
        jnp.tile(1.0 + 0.1 * jax.random.normal(ks[7], (D, )), v_heads))
    return [q, k, jax.nn.silu(v), z, by_lane(g), by_lane(beta), lanes], dy


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", default="1,2,4,8", help="heads a grid step")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU: interpreted kernels at 256 tokens of 8 heads")
    ap.add_argument("--gdn", action="store_true",
                    help="also ops/gdn.py's kernels, at the rule's heads a step")
    ap.add_argument("--side", default="", help="a label for every line: parent, change")
    args = ap.parse_args(argv)
    import jax
    from deepspeed_tpu.ops import gdn, kda
    from deepspeed_tpu.ops import kernel_dispatch as kd
    seq, heads = (256, 8) if args.interpret else (SEQ, HEADS)
    device = jax.devices()[0].device_kind
    out = open(args.out, "a") if args.out else None

    def emit(row):
        row = {"device": device, "seq": seq, "heads": heads, "chunk": CHUNK,
               "rule": kd.choose_kda_heads(heads, D, CHUNK, 2), **row}
        line = json.dumps({**({"side": args.side} if args.side else {}), **row})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    operands, dy = _operands(args.seed, seq, heads)
    static = (heads, CHUNK, kda.GATE_FLOOR, 1e-6, args.interpret)
    # the least time of a call by the bytes of the mathematics, as
    # benchmark/kda_cost.py counts them
    values, betas = BATCH * seq * heads * D, BATCH * seq * heads
    states = 4.0 * BATCH * (seq // CHUNK) * heads * D * D
    least_ms = {"fwd": (2 * (5 * values + betas) + states) / HBM_BYTES_S * 1e3,
                "bwd": (2 * (9 * values + 2 * betas) + states) / HBM_BYTES_S * 1e3}

    def measure(call, given, row):
        """``row`` with the call's milliseconds, its seconds to lower and to
        compile, and what it returned (None where the compiler refuses it)."""
        try:
            t0 = time.perf_counter()
            lowered = jax.jit(call).lower(*given)
            t1 = time.perf_counter()
            fn = lowered.compile()
            t2 = time.perf_counter()
            got = fn(*given)
            ms = _time(lambda: fn(*given), args.iters)
        except Exception as e:       # a block the compiler refuses
            emit({**row, "error": str(e)[:300]})
            return None
        row.update(ms=ms, us_a_step=1e3 * ms / row["grid_steps"], lower_s=t1 - t0,
                   compile_s=t2 - t1,
                   finite=all(bool(np.isfinite(np.asarray(a, np.float32)).all())
                              for a in got))
        return got

    want = {}
    for block in (int(b) for b in args.blocks.split(",")):
        legs = {
            "fwd": (lambda *a: kda._fwd_call(*a, *static, block), operands),
            "bwd": (lambda *a: kda._bwd_call(*a, *static, block), None),
        }
        for leg, (call, given) in legs.items():
            if given is None:       # the backward reads the states its forward wrote
                given = operands + [want["fwd"][1], dy]
            row = {"leg": leg, "block": block, "grid_steps": BATCH * (heads // block)
                   * (seq // CHUNK), "vmem_estimate": kd.kda_vmem_bytes(
                       block, D, CHUNK, 2, 6 if leg == "fwd" else 11)}
            row["vmem_limit"] = kd.vmem_limit_bytes(row["vmem_estimate"])
            got = measure(call, given, row)
            if got is None:
                continue
            first = want.setdefault(leg, got)
            emit({**row, "us_a_chunk_and_head": 1e3 * row["ms"] / (BATCH * heads
                                                                  * (seq // CHUNK)),
                  "least_ms": least_ms[leg], "roofline_share": least_ms[leg] / row["ms"],
                  # against the first block of the list (one head a step)
                  "bit_equal_to_first": all(
                      np.array_equal(np.asarray(a), np.asarray(b))
                      for a, b in zip(got, first))})
    if args.gdn:
        seq, k_heads = (256, 4) if args.interpret else (GDN_SEQ, GDN_K_HEADS)
        block, steps = gdn.grid_of(BATCH, seq, k_heads, heads, D, CHUNK, 2)
        operands, dy = _gdn_operands(args.seed, seq, k_heads, heads)
        static = ((k_heads, heads), CHUNK, 1e-6, args.interpret, block)
        rows = {leg: {"kernel": "gdn", "leg": leg, "seq": seq, "k_heads": k_heads,
                      "block": block, "grid_steps": steps} for leg in ("fwd", "bwd")}
        fwd = measure(lambda *a: gdn._fwd_call(*a, *static), operands, rows["fwd"])
        if fwd is not None:
            emit(rows["fwd"])
            if measure(lambda *a: gdn._bwd_call(*a, *static), operands + [fwd[1], dy],
                       rows["bwd"]) is not None:
                emit(rows["bwd"])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
