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


def _case(s, h, kv, d, *, b=1, sk=None, causal=True, window=None, softcap=None,
          blocks=None):
    return dict(b=b, s=s, sk=sk, h=h, kv=kv, d=d, causal=causal, window=window,
                softcap=softcap, blocks=blocks)


# What the shape-chosen blocks bring (ops/kernel_dispatch.py:choose_blocks):
# 512-key blocks with a smaller query block, groups of 1/4/8, head sizes
# 64/128/256, and grid steps that are dead on either side of the band, whose
# index maps are clamped to a live block.
CASES += [
    # block_q != block_k, 512 keys a step, each group size and head size
    _case(1024, 4, 4, 64, blocks=(128, 512)),
    _case(1024, 4, 1, 128, blocks=(128, 512)),
    _case(1024, 8, 1, 128, blocks=(64, 512)),
    _case(512, 2, 1, 256, blocks=(128, 512), softcap=50.0),
    # the blocks the dispatcher itself picks at a Mistral-like group
    _case(1024, 8, 2, 128, window=1024),
    # a window whose edge falls inside a 512-key block
    _case(1024, 4, 1, 128, window=300, blocks=(128, 512)),
    _case(1024, 2, 2, 64, window=700, softcap=20.0, blocks=(256, 512)),
    # rows whose whole key block is dead, before the window and after the
    # diagonal: the clamped index maps must still read each live block
    _case(1024, 4, 2, 64, window=128, blocks=(128, 128)),
    _case(1024, 4, 1, 64, window=100, blocks=(128, 512)),
    _case(1024, 2, 1, 128, window=96, blocks=(512, 128)),
    # no mask at all, and more keys than queries
    _case(512, 4, 1, 128, causal=False, blocks=(128, 512)),
    _case(256, 4, 2, 64, sk=1024, causal=False, blocks=(128, 512)),
    _case(512, 2, 1, 64, sk=256, causal=False, softcap=30.0, blocks=(256, 128)),
    # a window with no causal mask: future keys all live, old ones dead
    _case(512, 2, 2, 64, causal=False, window=64, blocks=(128, 128)),
]

# every shape under both backwards, the dq + dk/dv pair and the fused
# kernel, on the per-head forward's residuals: the pair is what no shape
# resolves to and ``impl_bwd="pallas"`` pins (the fused kernel's second
# oracle), so this sweep is its guard. "ranged" is the fused kernel walked
# in a drawn number of query ranges, as a sequence past its VMEM cap is.
# Since PR 60 a masked call's forward and fused backward walk a table of
# their live tiles where enough of them are dead: "fused" and "ranged" take
# the walk the rule gives (the table at the cases of eight blocks a side),
# "pallas" pins the clamped rectangle under the forward too (what a call past
# the table's cap keeps), so the sweep guards both walks.
BWDS = ("pallas", "fused", "ranged")


def _drawn_ranges(i):
    """A divisor above 1 of case ``i``'s count of q blocks, drawn by the
    case's number (1 where it has one q block: the whole walk)."""
    case = CASES[i]
    bq = min((case.get("blocks") or (128, 128))[0], case["s"])
    num_q = case["s"] // bq
    divisors = [r for r in range(2, num_q + 1) if num_q % r == 0]
    return int(np.random.default_rng(100 + i).choice(divisors)) if divisors else 1


def _inputs(case):
    rng = np.random.default_rng(7)
    sk = case.get("sk") or case["s"]
    return tuple(
        jnp.asarray(rng.normal(size=(case["b"], n, heads, case["d"])),
                    jnp.float32)
        for n, heads in ((case["s"], case["h"]), (sk, case["kv"]),
                         (sk, case["kv"])))


_REFERENCE = {}


def _reference(i):
    """The oracle's loss, output and gradients at case ``i``, computed once
    for both backwards (one compiled program: op by op, every small op of
    the reference's backward is a compile of its own)."""
    if i not in _REFERENCE:
        case = CASES[i]
        scale = 1.0 / np.sqrt(case["d"])

        def loss_ref(q, k, v):
            out = _xla_attention(q, k, v, scale, case.get("causal", True),
                                 case["window"], case["softcap"])
            return (out.astype(jnp.float32) ** 2).mean(), out

        _REFERENCE[i] = jax.jit(jax.value_and_grad(
            loss_ref, argnums=(0, 1, 2), has_aux=True))(*_inputs(case))
    return _REFERENCE[i]


def _id(i, bwd):
    c = CASES[i]
    return (f"b{c['b']}s{c['s']}h{c['h']}kv{c['kv']}d{c['d']}"
            f"w{c['window']}c{c['softcap']}"
            + (f"k{c['sk']}" if c.get("sk") else "")
            + ("" if c.get("causal", True) else "full")
            + ("x".join(map(str, ("", ) + c["blocks"])) if c.get("blocks") else "")
            + bwd)


@pytest.mark.parametrize("i,bwd", [
    pytest.param(i, bwd, id=_id(i, bwd))
    for i in range(len(CASES)) for bwd in BWDS
    # one q block walks whole: the "fused" case already is that call
    if bwd != "ranged" or _drawn_ranges(i) > 1])
def test_flash_matches_oracle(i, bwd):
    case = CASES[i]
    bq, bk = case.get("blocks") or (None, None)
    pins = dict(impl_bwd=bwd, table=False if bwd == "pallas" else None)
    if bwd == "ranged":
        # blocks the count of ranges was drawn for: the case's, or 128 x 128
        bq, bk = case.get("blocks") or (128, 128)
        pins = dict(impl_bwd="fused", ranges=_drawn_ranges(i))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=case.get("causal", True),
                              window=case["window"], softcap=case["softcap"],
                              interpret=True, block_q=bq, block_k=bk, **pins)
        return (out.astype(jnp.float32) ** 2).mean(), out

    (l1, o1), g1 = jax.jit(jax.value_and_grad(loss_flash, argnums=(0, 1, 2),
                                              has_aux=True))(*_inputs(case))
    (l2, o2), g2 = _reference(i)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)
