"""Optimizer micro-benchmark (reference ``tests/perf/adam_test*.py``):
fused Pallas optimizers vs optax on flat parameter buffers.

Not a pytest assertion — a measurement script (run on the real chip):

    python tests/perf/run_optimizer_bench.py [--elements 67108864]

Prints one line per (optimizer, path) with steps/s and effective GB/s
(read params+grads+2 moments, write params+2 moments ≈ 7 passes).
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp


def bench(fn, args, iters=20):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    # host readback closes the timing region
    float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, default=1 << 26)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()
    n = args.elements
    dt = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=n).astype(dt))
    g = jnp.asarray(rng.normal(size=n).astype(dt) * 1e-2)
    m = jnp.zeros(n, dt)
    v = jnp.zeros(n, dt)

    from deepspeed_tpu.ops.fused_optimizer import fused_adam_step
    import optax

    @jax.jit
    def fused(p, g, m, v):
        return fused_adam_step(p, g, m, v, lr=1e-3, step=jnp.int32(1),
                               b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0)

    opt = optax.adam(1e-3)
    state = opt.init(p)

    @jax.jit
    def ref(p, g, state):
        u, s = opt.update(g, state, p)
        return optax.apply_updates(p, u), s

    bytes_moved = 7 * n * dt.itemsize
    t_f = bench(fused, (p, g, m, v))
    t_r = bench(ref, (p, g, state))
    for name, t in (("fused_adam(pallas)", t_f), ("optax.adam(xla)", t_r)):
        print(f"{name:>20}: {1.0 / t:8.1f} steps/s  "
              f"{bytes_moved / t / 1e9:7.1f} GB/s  ({n} elems, {args.dtype})")


if __name__ == "__main__":
    main()
