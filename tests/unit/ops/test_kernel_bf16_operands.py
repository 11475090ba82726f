"""bf16-INPUT coverage for the Pallas attention kernels.

The kernels keep matmul operands in the input dtype (the MXU fast path is
bf16 x bf16 with fp32 accumulation) and cast the softmax weights P / the
score-gradient ds back to bf16 before their dots — standard flash
practice, but it means bf16 inputs exercise a genuinely different
numerical path than fp32 inputs, and the rest of the ops suite feeds
fp32 (where every astype is a no-op). These tests run the kernels in
interpret mode on bf16 inputs against the fp32 XLA oracle with
bf16-appropriate tolerances, so a precision regression on the MXU path
(ds underflow, low-mantissa P error in dv, ...) fails in CI instead of
on silicon.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import _xla_attention, flash_attention
from deepspeed_tpu.ops.paged_attention import paged_attention


def _oracle_grads(q, k, v, scale, causal):
    def L(q, k, v):
        o = _xla_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), scale, causal)
        return (o ** 2).mean()
    return jax.value_and_grad(L, argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))


@pytest.mark.parametrize("kv_heads,seq,d,blocks", [
    (8, 256, 64, None),             # MHA
    (2, 256, 64, None),             # GQA
    (2, 1024, 128, None),           # group 4, head 128: the chosen (256, 512)
    (1, 1024, 128, (64, 512)),      # group 8, 512 keys a step
    (4, 512, 256, (128, 512)),      # head size 256
    (8, 1024, 64, (256, 512)),      # head size 64, the blocks before PR 32
    (8, 1024, 128, None),           # group 1: (1024, 1024) and (1024, 512)
    (4, 1024, 128, None),           # group 2: the chosen (512, 512)
])
def test_flash_bf16_fwd_bwd_matches_fp32_oracle(kv_heads, seq, d, blocks):
    rng = np.random.default_rng(11)
    b = 2 if seq <= 256 else 1
    bq, bk = blocks or (None, None)
    q = jnp.asarray(rng.standard_normal((b, seq, 8, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, seq, kv_heads, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, seq, kv_heads, d)), jnp.bfloat16)

    def L(q, k, v):
        o = flash_attention(q, k, v, causal=True, force_pallas=True,
                            interpret=True, block_q=bq, block_k=bk)
        return (o.astype(jnp.float32) ** 2).mean()

    (lf, (dq, dk, dv)) = jax.value_and_grad(L, argnums=(0, 1, 2))(q, k, v)
    lo, (dqo, dko, dvo) = _oracle_grads(q, k, v, 1.0 / np.sqrt(d), True)

    assert abs(float(lf) - float(lo)) / abs(float(lo)) < 2e-2
    for got, want, name in ((dq, dqo, "dq"), (dk, dko, "dk"), (dv, dvo, "dv")):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        ref = float(jnp.max(jnp.abs(want))) + 1e-6
        # bf16 operands + bf16 P/ds: expect ~1e-2 relative agreement
        assert err / ref < 5e-2, (name, err, ref)


def test_paged_decode_bf16_matches_dense_fp32():
    rng = np.random.default_rng(13)
    S, N, KV, G, D, page, B = 2, 1, 2, 2, 64, 64, 3
    ctx = page * B
    kh = rng.standard_normal((S, ctx, KV, D))
    vh = rng.standard_normal((S, ctx, KV, D))
    qn = rng.standard_normal((S, N, KV, G, D))  # grouped view for the oracle
    seen = np.asarray([ctx - N, ctx // 2], np.int32)

    # paged layout [2L, slots, KV*D]: per-sequence pages laid out contiguously
    cache = np.zeros((2, page * B * S, KV * D), np.float32)
    bt = np.zeros((S, B), np.int32)
    for s in range(S):
        for b in range(B):
            pid = s * B + b
            bt[s, b] = pid
            sl = slice(b * page, min((b + 1) * page, ctx))
            n = sl.stop - sl.start
            cache[0, pid * page:pid * page + n] = kh[s, sl].reshape(n, KV * D)
            cache[1, pid * page:pid * page + n] = vh[s, sl].reshape(n, KV * D)
    # the new token's K/V live at position `seen[s]`
    out = paged_attention(
        jnp.asarray(qn.reshape(S, N, KV * G, D), jnp.bfloat16),
        jnp.asarray(cache, jnp.bfloat16), 0,
        jnp.asarray(bt), jnp.asarray(seen), jnp.asarray(seen + N),
        page_size=page, interpret=True)

    scale = 1.0 / np.sqrt(D)
    for s in range(S):
        hist = seen[s] + N
        for kvh in range(KV):
            for g in range(G):
                qv = qn[s, 0, kvh, g]
                logits = (kh[s, :hist, kvh] @ qv) * scale
                p = np.exp(logits - logits.max())
                p /= p.sum()
                want = p @ vh[s, :hist, kvh]
                got = np.asarray(out[s, 0, kvh * G + g], np.float32)
                err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
                assert err < 5e-2, (s, kvh, g, err)
