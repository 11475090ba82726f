"""Seeded property sweep of the Pallas flash kernel (interpret mode) vs the
XLA oracle — randomized GQA ratios x window x softcap x ragged-ish shapes.
The fixed-shape tests missed a real Mosaic GQA-bwd bug on chip (round 4);
this sweep at least pins the MATH for every dispatchable combo so
silicon runs only have lowering left to prove."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import flash_attention, _xla_attention

CASES = []
_rng = np.random.default_rng(2024)
for _ in range(14):
    heads = int(_rng.choice([2, 4, 8]))
    group = int(_rng.choice([1, 2, 4]))
    kv = max(1, heads // group)
    CASES.append(dict(
        b=int(_rng.choice([1, 2])),
        s=int(_rng.choice([128, 256, 384])),
        h=heads, kv=kv, d=int(_rng.choice([32, 64])),
        window=(None if _rng.random() < 0.5
                else int(_rng.choice([32, 64, 128]))),
        softcap=(None if _rng.random() < 0.5 else float(_rng.choice([20.0, 50.0]))),
    ))


@pytest.mark.parametrize("case", CASES, ids=lambda c: (
    f"b{c['b']}s{c['s']}h{c['h']}kv{c['kv']}d{c['d']}"
    f"w{c['window']}c{c['softcap']}"))
def test_flash_matches_oracle(case):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(case["b"], case["s"], case["h"], case["d"])),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(case["b"], case["s"], case["kv"], case["d"])),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(case["b"], case["s"], case["kv"], case["d"])),
                    jnp.float32)
    scale = 1.0 / np.sqrt(case["d"])

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=case["window"],
                              softcap=case["softcap"], interpret=True,
                              force_pallas=True)
        return (out.astype(jnp.float32) ** 2).mean(), out

    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, scale, True, case["window"],
                             case["softcap"])
        return (out.astype(jnp.float32) ** 2).mean(), out

    (l1, o1), g1 = jax.value_and_grad(loss_flash, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    (l2, o2), g2 = jax.value_and_grad(loss_ref, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)
