"""Kimi Delta Attention's chunk kernels (``ops/kda.py``: ``kda_chunk_fwd``,
``kda_chunk_bwd``), interpreted, through the fused entry ``kda_fused`` against
the composition it replaces (``l2norm`` of q and k, the recurrence token by
token, ``kda_reference`` under ``bounded_gate``, the gated output norm): the
forward and the gradient of every input (the raw q, k and v, the gate's
pre-activation, rate, bias, beta, the output gate, the norm's weight); a
sequence of one chunk, of several and one the chunk does not divide; a gate at
its bound ``g = -5`` over a whole chunk; ``beta = 0`` and ``beta = 1`` rows;
rows of zeros in q and k (the norm's epsilon); chunks of 16 and 64; one, two
and four heads a grid step (``kernel_dispatch.choose_kda_heads``) and a block
of heads against one head a step bit for bit; the largest ``|S|`` at the
chunks' ends, the mean decay and ``fused_rows``; what the call refuses. And
the triangular solve alone (``kda._inverse``, which ``ops/gdn.py`` runs too)
against ``solve_triangular`` in float64, with what its products cost."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda
from deepspeed_tpu.ops import kernel_dispatch as kd

H, D = 2, 128
NAMES = ("q", "k", "v", "pre", "rate", "bias", "beta", "gate", "weight")
EPS = 1e-6


def _operands(seq, seed=1, gate="random", beta="random", dtype=jnp.float32, H=H,
              zero_rows=False):
    """q and k as a convolution leaves them (no row of unit length), and what
    multiplies the output in the loss."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    q = 0.7 * jax.random.normal(ks[0], (1, seq, H, D))
    k = 1.3 * jax.random.normal(ks[1], (1, seq, H, D))
    if zero_rows:               # what SiLU leaves of a dead token: q, k, both
        q = q.at[:, 1::5].set(0.0).at[:, 2::5, 0].set(0.0)
        k = k.at[:, 2::5].set(0.0).at[:, 3::5, 1].set(0.0)
    v = jax.random.normal(ks[2], (1, seq, H, D))
    pre = 2.0 * jax.random.normal(ks[3], (1, seq, H, D)) - 2.0
    if gate == "floor":         # sigmoid(.) = 1 in float32: g = -5 at every token
        pre = jnp.full_like(pre, 40.0)
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, H)))
    if beta == "ends":          # rows that write nothing, rows that write all
        b = b.at[:, 0::3].set(0.0).at[:, 1::3].set(1.0)
    rate = jax.random.uniform(ks[5], (H, ), minval=1.0, maxval=4.0)
    bias = 0.3 * jax.random.normal(ks[6], (H * D, ))
    out_gate = jax.random.normal(ks[8], (1, seq, H, D))
    o_norm = 1.0 + 0.2 * jax.random.normal(ks[9], (D, ))
    weight = jax.random.normal(ks[7], (1, seq, H, D))
    return ([a.astype(dtype) for a in (q, k, v, pre)]
            + [rate, bias, b, out_gate.astype(dtype), o_norm], weight)


def _composition(q, k, v, pre, rate, bias, beta, gate, weight, **kw):
    """What the kernels replace, spelled out: XLA's norms around the
    recurrence. With ``with_state_absmax``: also the largest ``|S|``."""
    dtype = v.dtype
    o = kda.kda_reference(kda.l2norm(q, D ** -0.5, dtype), kda.l2norm(k, 1.0, dtype), v,
                          kda.bounded_gate(pre, rate, bias), beta, **kw)
    if kw.get("with_state_absmax"):
        return kda.gated_norm(o[0], gate, weight, EPS, dtype), o[1]
    return kda.gated_norm(o, gate, weight, EPS, dtype)


def _fused(*a, chunk, **kw):
    return kda.kda_fused(*a, chunk, eps=EPS, use_kernel=False, interpret=True, **kw)


def _both(args, weight, chunk):
    def of(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        return jax.value_and_grad(loss, argnums=tuple(range(9)), has_aux=True)(*args)
    return of(lambda *a: _fused(*a, chunk=chunk)), of(_composition)


def _pin(monkeypatch, block):
    """``block`` heads a grid step, whatever the rule would give the shape."""
    monkeypatch.setattr(kd, "choose_kda_heads", lambda *shape: block)


# heads, then the heads a grid step: the rule's own (2 of 2, 2 of 6) or pinned
@pytest.mark.parametrize("chunk,seq,gate,beta,heads,block,zero_rows", [
    (64, 64, "random", "random", 2, None, False), (64, 192, "random", "random", 2, None, False),
    (16, 48, "random", "random", 2, None, False), (64, 100, "random", "random", 2, None, False),
    (64, 128, "floor", "random", 2, None, False), (16, 32, "floor", "ends", 2, None, False),
    (64, 128, "random", "ends", 2, None, False), (64, 128, "random", "random", 4, 1, False),
    (64, 128, "random", "ends", 4, 2, False), (64, 100, "random", "random", 4, 4, False),
    (64, 128, "random", "random", 6, None, False), (64, 128, "random", "random", 2, None, True),
    (16, 40, "random", "ends", 4, 4, True)],
    ids=["one_chunk", "three_chunks", "chunk16", "padded", "gate_at_its_bound",
         "bound_chunk16_beta_ends", "beta_0_and_1", "four_heads_one_a_step",
         "four_heads_two_a_step", "four_heads_a_step_padded", "six_heads_fall_to_two",
         "rows_of_zeros", "rows_of_zeros_padded_four_a_step"])
def test_forward_and_every_gradient_match_the_recurrence(chunk, seq, gate, beta, heads,
                                                         block, zero_rows, monkeypatch):
    """The fused entry against ``l2norm`` -> ``kda_reference`` -> the gated
    norm at float32: the output and the nine gradients. A row of zeros in q
    or k leaves the norm its epsilon: the row stays zero and its gradient is
    the incoming one times ``1 / sqrt(eps)``, as autodiff gives the XLA norm."""
    if block is None:
        assert kda.grid_of(1, seq, heads, D, chunk, 4) == (2, heads // 2 * -(-seq // chunk))
    else:
        _pin(monkeypatch, block)
    args, weight = _operands(seq, gate=gate, beta=beta, H=heads, zero_rows=zero_rows)
    ((_, out), grads), ((_, want), want_grads) = _both(args, weight, chunk)
    assert out.shape == want.shape == (1, seq, heads, D)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for name, g, w in zip(NAMES, grads, want_grads):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        np.testing.assert_allclose(g, w, atol=2e-5 * scale, rtol=2e-4, err_msg=name)
    if zero_rows:
        assert float(jnp.abs(grads[0][:, 1::5]).max()) > 0.0
        assert float(jnp.abs(grads[1][:, 2::5]).max()) > 0.0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_a_block_of_heads_is_one_head_a_step_bit_for_bit(dtype, monkeypatch):
    """The mathematics of a head does not change with the heads a grid step
    takes: the outputs, the chunk states, the largest ``|S|`` and the summed
    decays a head and lane and the nine gradients at two and four heads a
    step are those of one."""
    args, weight = _operands(128, dtype=dtype, H=4, zero_rows=True)

    def loss(*a):
        out = _fused(*a, chunk=64)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    def at(block):
        _pin(monkeypatch, block)
        (_, out), grads = jax.value_and_grad(loss, argnums=tuple(range(9)),
                                             has_aux=True)(*args)
        q, k, v, pre, rate, bias, beta, gate, o_norm = args
        flat = lambda a: a.reshape(1, 128, 4 * D)       # noqa: E731
        lanes = (jnp.zeros((kda.SUBLANES, 4 * D)).at[0].set(jnp.repeat(rate, D))
                 .at[1].set(bias).at[2].set(jnp.tile(o_norm, 4)))
        kernel = kda._fwd_call(flat(q), flat(k), flat(v), flat(pre), flat(gate),
                               jnp.pad(beta, ((0, 0), (0, 0), (0, kda.LANES - 4))), lanes,
                               4, 64, kda.GATE_FLOOR, EPS, True, block)
        return dict(zip(("out", *NAMES, "y", "states", "tops", "decays"),
                        (out, *grads, *kernel)))

    one = at(1)
    assert one["states"].shape == (1, 2, D, 4 * D)
    assert one["tops"].shape == one["decays"].shape == (1, 4, 8, D)
    for block in (2, 4):
        for name, got in at(block).items():
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(one[name], np.float32), err_msg=name)


def test_bf16_operands_stay_within_bf16_of_the_recurrence():
    """The training precision: operands of a matmul in bf16 (the unit rows and
    the beta products rounded where XLA rounded them), the norms' arithmetic,
    the state, the running sum and ``(I + A)^{-1}`` float32."""
    args, weight = _operands(128, dtype=jnp.bfloat16)
    ((_, out), grads), ((_, want), want_grads) = _both(args, weight, 64)
    assert out.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)       # noqa: E731
    assert np.abs(f32(out) - f32(want)).max() <= 3e-2 * np.abs(f32(want)).max()
    for name, g, w in zip(NAMES, grads, want_grads):
        assert g.dtype == w.dtype, name
        err = np.linalg.norm(f32(g) - f32(w)) / np.linalg.norm(f32(w))
        # the gate's gradients are sums of signed differences along the
        # sequence: bias reads 0.11 here, 0.13-0.15 in the cell on the chip
        assert err <= (0.25 if name in ("pre", "rate", "bias") else 5e-2), (name, err)


def test_the_largest_state_is_read_at_the_chunks_ends_and_the_sum_is_untouched():
    args, _ = _operands(192)
    out, stats = _fused(*args, chunk=64, with_stats=True)
    want, want_top = _composition(*args, with_state_absmax=True, stat_every=64)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(stats["state_absmax"], want_top, rtol=1e-5)
    _, every_token = _composition(*args, with_state_absmax=True)
    assert float(every_token) >= float(want_top) > 0.0


@pytest.mark.parametrize("seq,heads,block", [(192, 2, None), (100, 4, 4), (100, 4, 1)],
                         ids=["three_chunks", "padded_four_a_step", "padded_one_a_step"])
def test_the_kernels_statistics_are_the_references_and_fused_rows_says_who_made_the_rows(
        seq, heads, block, monkeypatch):
    """``decay_mean`` (the mean of ``exp(g)`` over every channel and token,
    summed in the forward kernel as the largest ``|S|`` is; a padded token's
    decay of 1 is taken off) and ``state_absmax`` from the kernels equal what
    the reference path reads; ``fused_rows`` is 1.0 from the kernels and 0.0
    from the path where XLA makes the norms; none has a gradient."""
    if block is not None:
        _pin(monkeypatch, block)
    args, _ = _operands(seq, H=heads)
    _, fused = _fused(*args, chunk=64, with_stats=True)
    _, plain = kda.kda_fused(*args, 64, eps=EPS, use_kernel=False, with_stats=True)
    assert sorted(fused) == sorted(plain) == ["decay_mean", "fused_rows", "state_absmax"]
    assert float(fused["fused_rows"]) == 1.0 and float(plain["fused_rows"]) == 0.0
    decay = jnp.mean(jnp.exp(kda.bounded_gate(*args[3:6])))
    np.testing.assert_allclose(plain["decay_mean"], decay, rtol=1e-6)
    np.testing.assert_allclose(fused["decay_mean"], decay, rtol=1e-5)
    np.testing.assert_allclose(fused["state_absmax"], plain["state_absmax"], rtol=1e-5)
    grads = jax.grad(lambda *a: sum(_fused(*a, chunk=64, with_stats=True)[1].values()),
                     argnums=tuple(range(9)))(*args)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


def test_off_the_kernels_the_call_is_the_recurrence_and_bad_shapes_are_refused():
    args, _ = _operands(48)
    out = kda.kda_fused(*args, 16, eps=EPS, use_kernel=False)
    np.testing.assert_array_equal(out, _composition(*args))
    with pytest.raises(ValueError, match="multiple of 128"):
        kda.kda_fused(*(a[..., :64] if a.ndim == 4 else a for a in args[:4]),
                      args[4], args[5][:H * 64], args[6], args[7][..., :64], args[8][:64],
                      16, eps=EPS, use_kernel=False, interpret=True)
    with pytest.raises(ValueError, match="floor"):
        _fused(*args, chunk=16, floor=-8.0)
    with pytest.raises(ValueError, match="want q, k, pre, gate"):
        kda.kda_fused(args[0], args[1][:, :8], *args[2:], 16, eps=EPS, use_kernel=False)
    with pytest.raises(ValueError, match="want q, k, pre, gate"):
        kda.kda_fused(*args[:8], args[8][:64], 16, eps=EPS, use_kernel=False)
    assert kda.scan_bytes(1, 100, 2, 128, 128, 64, 2) == 2 * (128 * 128 * 2 + 2 * 128 * 128 * 4)


def _to_its_end(chain):
    """What a chain (``kda._abreast``) returns, run alone."""
    return kda._abreast([chain])[0]


def _strictly_lower(kind, Q, seed=0):
    """A chunk's ``A = strictly_lower(beta k k^T o decay)`` of three kinds."""
    rng = np.random.default_rng(seed)
    unit = lambda k: k / np.linalg.norm(k, axis=-1, keepdims=True)   # noqa: E731
    if kind == "small":
        A = 0.1 * rng.normal(size=(Q, Q))
    elif kind == "collinear":
        # one direction and a twentieth of noise a key, beta within a
        # hundredth of 1, no decay: entries near 1
        k = unit(rng.normal(size=(1, D)) + 0.05 * rng.normal(size=(Q, D)))
        A = ((1.0 - 0.01 * rng.uniform(size=(Q, 1))) * k) @ k.T
    else:
        # every seventh token forgets everything before it
        k = unit(rng.normal(size=(Q, D)))
        g = -rng.uniform(0.0, 5.0, size=Q)
        g[Q // 3::7] = -200.0
        c = np.cumsum(g)
        A = (rng.uniform(size=(Q, 1)) * k) @ k.T * np.exp(
            np.minimum(c[:, None] - c[None, :], 0.0))
    return np.tril(A, -1).astype(np.float32)


@pytest.mark.parametrize("Q", [16, 32, 64, 128])
@pytest.mark.parametrize("kind", ["small", "collinear", "decayed"])
def test_the_solve_is_the_triangular_inverse_to_float32(kind, Q):
    """``(I + A)^{-1}`` by blocks against ``solve_triangular`` in float64,
    within 1e-5 of ``|T|``'s largest entry, and ``T (I + A) = I`` to the same:
    for small entries, for entries near 1 (where the doubling product on
    blocks of 16 reads 3e-4 to 8e-4 and on the whole chunk overflows: why the
    diagonal blocks are eight rows) and for rows that a decay sent to 0."""
    A = _strictly_lower(kind, Q)
    if kind == "collinear":
        assert np.abs(A[np.tril_indices(Q, -1)]).min() > 0.95
    if kind == "decayed":
        assert (A[1:] == 0.0).all(axis=1).any()
    eye = np.eye(Q)
    with jax.enable_x64(True):
        want = np.asarray(jax.scipy.linalg.solve_triangular(
            jnp.asarray(eye + A, jnp.float64), jnp.asarray(eye), lower=True))
    assert want.dtype == np.float64
    T = _to_its_end(kda._inverse(jnp.asarray(A)))
    assert T.dtype == jnp.float32 and T.shape == (Q, Q)
    T, scale = np.asarray(T, np.float64), np.abs(want).max()
    assert np.abs(T - want).max() <= 1e-5 * scale
    assert np.abs(T @ (eye + A) - eye).max() <= 1e-5 * scale
    assert (np.triu(T, 1) == 0.0).all() and (np.diag(T) == 1.0).all()


def test_the_solves_products_are_float32_and_their_left_rows_a_third_of_the_chains():
    """What the blocked solve is for, from its jaxpr at a chunk of 64: an MXU
    product costs by its left operand's rows, and every product of the solve
    is ``contract_precision<fp32>`` (six passes). The chain ``(I - A)(I +
    A^2)...(I + A^32)`` on the whole chunk was ten products of 64 rows, 640;
    by blocks it is nine whose rows sum to 224, a stage (a ``yield``) each."""
    A = jnp.asarray(_strictly_lower("small", 64))

    def products(jaxpr):                # at any depth: ``jnp.where`` is a jit of its own
        for e in jaxpr.eqns:
            if e.primitive.name == "dot_general":
                yield e
            for inner in jax.core.jaxprs_in_params(e.params):
                yield from products(inner)

    dots = list(products(jax.make_jaxpr(lambda: _to_its_end(kda._inverse(A)))().jaxpr))
    for e in dots:
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST, ) * 2
        assert {v.aval.dtype for v in (*e.invars, *e.outvars)} == {jnp.dtype("float32")}
    rows = [e.invars[0].aval.shape[0] for e in dots]
    assert len(dots) <= 9 and sum(rows) <= 224 and max(rows) <= 32, rows
    assert sum(1 for _ in kda._inverse(A)) == len(dots)
