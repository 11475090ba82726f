"""Grouped-matmul sweep: XLA's ``jax.lax.ragged_dot`` against the program's
``moe_gmm_*`` kernels (``ops/grouped_matmul.py``), pass by pass (``rows``,
``d_rows``, ``weights``), at the shapes the training cells call them with and
at the edges of ``kernel_dispatch.gmm_impl``'s rule, over a grid of row tiles.
It wrote ``docs/readings/moe_gmm_sweep_pr47.jsonl`` (the schema of PR 42's
file, with the seconds a pass takes to lower, Mosaic included, and to
compile) and is how a change to the kernel, its tile or the rule is checked.

Not a pytest assertion: a measurement tool, as ``run_attn_sweep.py`` is.

    python tests/perf/run_moe_gmm_sweep.py --out chiprun_out/sweep.jsonl   # chip
    JAX_PLATFORMS=cpu python tests/perf/run_moe_gmm_sweep.py --interpret --quick

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

PEAK_BF16 = 197e12      # one TPU v5e chip (benchmark/peaks.json has the source)
LEGS = ("rows", "d_rows", "weights")
# (name, sorted rows, rows held, experts, hidden, expert width, routing skew):
# the five training cells' calls (a share's rows array is twice its even
# share), then OLMoE's widths at fewer rows a group down to the rule's floor
CELLS = [
    ("kimi", 49152, 24428, 8, 2048, 1408, 0.08),
    ("sdar", 65536, 32899, 16, 2048, 768, 0.12),
    ("keye", 65536, 32768, 16, 2048, 768, 0.12),
    ("lfm2", 32768, 17104, 8, 2048, 1536, 0.08),
    ("olmoe", 131072, 131072, 64, 2048, 1024, 0.35),
]
EDGES = [(f"olmoe-{per}-rows-a-group", 64 * per, 64 * per, 64, 2048, 1024, 0.35)
         for per in (512, 128, 32)]
TILES = (128, 256, 512)


def _time(fn, iters: int) -> float:
    import jax
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _groups(rng, held: int, experts: int, skew: float):
    p = np.exp(skew * rng.standard_normal(experts))
    return rng.multinomial(held, p / p.sum()).astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", default="", help="comma-separated names")
    ap.add_argument("--quick", action="store_true", help="the rule's tile only")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU: interpreted kernels at a sixteenth of the rows")
    args = ap.parse_args(argv)
    import importlib
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import grouped_matmul as gm
    from deepspeed_tpu.ops import kernel_dispatch as kd
    if args.interpret:
        importlib.import_module("deepspeed_tpu.ops.registry").INTERPRET_KERNELS = True
    device = jax.devices()[0].device_kind
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps({**row, "device": device})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def ref(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs, preferred_element_type=x.dtype)

    xla = {
        "rows": lambda x, w, dy, gs: ref(x, w, gs),
        "d_rows": lambda x, w, dy, gs: jax.vjp(lambda x: ref(x, w, gs), x)[1](dy)[0],
        "weights": lambda x, w, dy, gs: jax.vjp(lambda w: ref(x, w, gs), w)[1](dy)[0],
    }

    def pallas(leg, tile):
        def call(x, w, dy, gs):
            a, b = {"rows": (x, w), "d_rows": (dy, w), "weights": (x, dy)}[leg]
            return gm._gmm_leg(leg, a, b, gs, tile)
        return call

    wanted = set(filter(None, args.cells.split(",")))
    for name, rows, held, experts, hidden, width, skew in CELLS + EDGES:
        if wanted and name not in wanted:
            continue
        if args.interpret:
            rows, held = rows // 16, held // 16
        for k, n in ((hidden, width), (width, hidden)):     # w1 | w3, then w2
            rng = np.random.default_rng(args.seed)
            gs = _groups(rng, held, experts, skew)
            key = jax.random.split(jax.random.PRNGKey(args.seed), 3)
            x = jax.random.normal(key[0], (rows, k), jnp.bfloat16)
            w = (jax.random.normal(key[1], (experts, k, n)) * k ** -0.5).astype(jnp.bfloat16)
            dy = jax.random.normal(key[2], (rows, n), jnp.bfloat16)
            operands = (x, w, dy, jnp.asarray(gs))
            least_ms = 2.0 * held * k * n / PEAK_BF16 * 1e3
            base = {"cell": name, "k": k, "n": n}
            want, xla_ms = {}, {}
            for leg in LEGS:
                fn = jax.jit(xla[leg])
                want[leg] = np.asarray(fn(*operands), np.float32)
                xla_ms[leg] = _time(lambda: fn(*operands), args.iters)
                emit({**base, "leg": leg, "impl": "xla", "ms": xla_ms[leg],
                      "least_ms": least_ms, "peak_share": least_ms / xla_ms[leg],
                      "rows": rows, "held": held,
                      "busiest_over_mean": float(gs.max() / gs.mean())})
            tiles = (kd.GMM_ROW_TILE, ) if args.quick or name.startswith("olmoe-") \
                else TILES
            for leg in LEGS:
                for tile in tiles:
                    try:
                        t0 = time.perf_counter()
                        lowered = jax.jit(pallas(leg, tile)).lower(*operands)
                        t1 = time.perf_counter()
                        fn = lowered.compile()
                        t2 = time.perf_counter()
                        got = np.asarray(fn(*operands), np.float32)
                        ms = _time(lambda: fn(*operands), args.iters)
                    except Exception as e:       # a tile the compiler refuses
                        emit({**base, "leg": leg, "impl": "pallas",
                              "tiles": [tile, k if leg == "d_rows" else n],
                              "error": str(e)[:300]})
                        continue
                    # past the rows held XLA's kernel leaves what the buffer
                    # held; the program's writes zeros: compare the rows held
                    dead = got[held:] if leg != "weights" else got[gs == 0]
                    live = slice(None) if leg == "weights" else slice(0, held)
                    emit({**base, "leg": leg, "impl": "pallas",
                          "tiles": [tile, k if leg == "d_rows" else n], "ms": ms,
                          "least_ms": least_ms, "peak_share": least_ms / ms,
                          "over_xla": ms / xla_ms[leg],
                          "max_abs_diff": float(
                              np.abs(got[live] - want[leg][live]).max()),
                          "ref_abs_max": float(np.abs(want[leg][live]).max()),
                          "dead_rows_all_zero": bool(not dead.any()),
                          "lower_s": t1 - t0, "compile_s": t2 - t1})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
