"""Collective micro-benchmark (reference ``bin/ds_bench`` →
DeepSpeedExamples communication benchmarks): sweeps message sizes over a
chosen collective on the live mesh and prints latency + algorithm/bus
bandwidth using the same busbw conventions as the reference CommsLogger
(allreduce busbw = 2(n-1)/n × size/t)."""

import argparse
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def _bw_factor(op: str, n: int) -> float:
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter"):
        return (n - 1) / n
    return 1.0


def run_sweep(op: str = "all_reduce", sizes: List[int] = None, trials: int = 20,
              dtype=jnp.bfloat16, group: str = "data") -> List[dict]:
    from .. import comm as dist
    if not dist.is_initialized():
        dist.init_distributed()
    ctx = dist.get_mesh_context()
    n = ctx.axis_size(group)
    sizes = sizes or [2**p for p in range(12, 27, 2)]  # 4KB..128MB elements/2
    results = []
    fns = {
        "all_reduce": lambda x: dist.all_reduce(x, group=group),
        "all_gather": lambda x: dist.all_gather(x, group=group),
        "reduce_scatter": lambda x: dist.reduce_scatter(x, group=group),
        "all_to_all": lambda x: dist.all_to_all(x, group=group),
    }
    fn = fns[op]
    for size in sizes:
        x = jnp.ones((size, ), dtype=dtype)
        out = fn(x)  # compile + warmup
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(trials):
            out = fn(x)
        jax.block_until_ready(out)
        # a host readback closes the timing region
        float(np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[0])
        dt = (time.perf_counter() - t0) / trials
        nbytes = size * jnp.dtype(dtype).itemsize
        busbw = _bw_factor(op, n) * nbytes / dt / 1e9
        results.append({"op": op, "size_bytes": nbytes, "latency_us": dt * 1e6,
                        "busbw_GBps": busbw, "world": n})
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description="deepspeed_tpu comm sweep (ds_bench)")
    ap.add_argument("--op", default="all_reduce",
                    choices=["all_reduce", "all_gather", "reduce_scatter", "all_to_all"])
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--group", default="data")
    ap.add_argument("--maxsize", type=int, default=26, help="log2 max element count")
    args = ap.parse_args(argv)
    sizes = [2**p for p in range(12, args.maxsize + 1, 2)]
    rows = run_sweep(args.op, sizes, args.trials, group=args.group)
    print(f"{'size':>12} {'latency(us)':>12} {'busbw(GB/s)':>12}")
    for r in rows:
        print(f"{r['size_bytes']:>12} {r['latency_us']:>12.1f} {r['busbw_GBps']:>12.2f}")
    return 0


if __name__ == "__main__":
    main()
