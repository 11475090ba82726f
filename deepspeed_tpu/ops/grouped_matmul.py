"""Grouped (megablocks-style) MoE matmul.

Reference capability: ``deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm/``
plus the ``moe_scatter``/``moe_gather`` ragged ops — tokens are routed to
experts and each expert multiplies only its own tokens, so per-token FLOPs
scale with top-k instead of the expert count E (the round-1 path computed
every expert for every token and masked: E/k× wasted FLOPs).

TPU design: sort the (token, choice) assignments by expert id (one XLA
sort, giving the permutation ``order`` and its inverse ``inv``), gather the
tokens into expert order, run the three expert MLPs as ragged grouped GEMMs
(``grouped_matmul``, FLOPs ∝ top-k, the grouped-GEMM analog of the
reference's CUTLASS kernel): on one TPU device, at bfloat16, widths on the
128-lane grid and 2,048 rows or more, the program's own Pallas kernels
``moe_gmm_rows`` / ``moe_gmm_d_rows`` / ``moe_gmm_weights`` (a tile of sorted
rows by one expert's whole matrix a grid step, stopped at the rows held;
0.26 to 0.89 of XLA's time on a v5e at every shape the training cells call,
the same bits: ``docs/kernel_dispatch.md``, "moe_gmm"), and everywhere else
``jax.lax.ragged_dot``, which on a TPU lowers to XLA's grouped-matmul kernel
(``%ragged-dot-none*``) and on the CPU to a dense masked form that only the
test harness sees (``kernel_dispatch.gmm_impl`` chooses, from the shape, the
dtype and the placement) — then gather the rows back by ``inv`` and sum each
token's k rows, weighted, in float32. Dispatch and combine are permutations, so each carries its exact
transpose as a ``jax.custom_vjp``: forward and backward move rows with
gathers only, never a scatter-add (which XLA serialises, not knowing the
indices cannot collide). The ``[T*k, ·]`` arrays between the matmuls cross
HBM in ``x.dtype``; the MXU accumulates in float32 either way. Static shapes
throughout (T*k assignments regardless of routing), no capacity factor and
no token dropping: exact token-choice semantics.

A chip that holds a share of the experts (``moe_grouped_mlp_share``: experts
``first_expert .. first_expert + w1.shape[0]`` of a wider router) computes
``Σ p_e · ffn_e(x)`` over the chosen experts it holds and adds nothing for
the others: the partial sum an expert-parallel layer's exchange would bring
home, without the exchange. The assignments are sorted with every absent
expert's last, so the rows held come first, expert by expert, and only a
prefix of the sorted rows is gathered, multiplied and combined. That prefix
has a static length, ``share_rows``: twice the even share (``SHARE_ROWS_FACTOR``; of the share
when all tokens keep the group held: ``crowding``), so a router sending this chip twice that still
costs one pass over a quarter of the ``T*k`` rows at 8 of 64 experts held. A
step that sends more takes the same function over all ``T*k`` rows under a
``lax.cond``: slower, exact, counted (``share_fallback``). Rows between the
held count and the static length are no expert's: the grouped matmul leaves
them alone, and they are masked where rows go back to tokens.

Every trip of a share back from sorted rows to tokens walks those ``R`` rows,
not the ``T*k`` token positions of which an eighth are live
(``_rows_to_tokens``: the combine's forward and the dispatch's transpose; the
weights' gradient is placed at the rows' own assignments). A second sort,
``R`` long, puts the live rows in token order, one gather of ``R`` rows
follows it, and a token's at most k rows, now adjacent, are summed in float32
by a kernel, a block of tokens a grid step (a scatter-add with sorted indices
where no kernel runs). So the share takes no inverse permutation. The whole
path above keeps its gathers: there every row is live, and walking ``T*k``
rows costs the same gather plus the sort and the sum; so does a share whose
prefix is more than half the assignments, and the exact pass over all of them
(``share_walks_rows``, with the measured crossover).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_dispatch as kd
from .registry import interpret_kernels, on_tpu, registry


def moe_sort_permutation(top_idx):
    """Sort (token, choice) assignments by expert.

    Args:
      top_idx: ``[T, k]`` int32 expert id per (token, choice).
    Returns:
      (order ``[T*k]`` int32: the flat assignment ``t*k + j`` at each sorted
       row, a stable sort so ties keep token order;
       inv ``[T*k]`` int32: the sorted row of each flat assignment,
       ``inv[order] == arange(T*k)``).

    The inverse is a second sort: on a v5e at 131,072 assignments 0.29 ms
    beside the first sort's 0.25, against 0.67 ms for an iota scatter and
    0.42 ms for a counting sort over the ``[T*k, E]`` compare.
    """
    order = jnp.argsort(top_idx.reshape(-1), stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    return order, inv


def expert_counts(top_idx, num_experts: int):
    """``[E]`` int32 (token, choice) assignments per expert. A compare and a
    column sum: on a v5e 0.33 ms for 131,072 assignments over 64 experts,
    against 1.34 ms for ``jnp.bincount``'s scatter-add."""
    return jnp.sum(top_idx.reshape(-1, 1) == jnp.arange(num_experts), axis=0,
                   dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, ))
def moe_dispatch(x, order, inv, k):
    """``x[T, H] → xs[T*k, H]``: row ``r`` of the result is token
    ``order[r] // k``. Its transpose gathers the k rows of each token back
    by ``inv`` and sums them in float32."""
    return x[order // k]


def _moe_dispatch_fwd(x, order, inv, k):
    return moe_dispatch(x, order, inv, k), inv


def _moe_dispatch_bwd(k, inv, dxs):
    dx = jnp.sum(dxs[inv].reshape(inv.size // k, k, -1), axis=1,
                 dtype=jnp.float32)
    return dx.astype(dxs.dtype), None, None


moe_dispatch.defvjp(_moe_dispatch_fwd, _moe_dispatch_bwd)


@jax.custom_vjp
def moe_combine(y, top_w, order, inv):
    """``out[t] = Σ_j top_w[t, j] · y[inv[t*k + j]]``: ``y[T*k, H]`` in
    expert order back to ``[T, H]`` tokens, the weighted sum over a token's
    k rows taken in float32 and cast once to ``y.dtype``. Backward: the row
    gradient is a gather of ``dout`` into expert order scaled by each row's
    weight, and the weights' gradient ``Σ_h y · dout`` is taken in expert
    order beside it (one pass over ``y``) and permuted as ``[T*k]`` scalars."""
    T, k = top_w.shape
    yk = y[inv].reshape(T, k, -1).astype(jnp.float32)
    out = jnp.sum(yk * top_w.astype(jnp.float32)[:, :, None], axis=1)
    return out.astype(y.dtype)


def _moe_combine_fwd(y, top_w, order, inv):
    return moe_combine(y, top_w, order, inv), (y, top_w, order, inv)


def _moe_combine_bwd(res, dout):
    y, top_w, order, inv = res
    T, k = top_w.shape
    g = dout[order // k].astype(jnp.float32)  # [T*k, H] expert order
    w_sorted = top_w.reshape(-1)[order].astype(jnp.float32)
    dy = (g * w_sorted[:, None]).astype(y.dtype)
    dw_sorted = jnp.sum(y.astype(jnp.float32) * g, axis=-1)
    dw = dw_sorted[inv].reshape(T, k).astype(top_w.dtype)
    return dy, dw, None, None


moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)


# ---------------------------------------------------------------------------
# The grouped matmul itself: XLA's ``ragged_dot``, or the program's own
# kernel where ``kernel_dispatch.gmm_impl`` says so from the shape, the dtype
# and where the call runs (docs/kernel_dispatch.md, "moe_gmm").
#
# Three passes, one ``pallas_call`` each (``moe_gmm_rows``, ``moe_gmm_d_rows``,
# ``moe_gmm_weights``): a grid step is one VISIT of a tile of ``tile`` sorted
# rows by one expert, whole widths in VMEM. A tile that straddles experts is
# visited once for each with the other's rows masked, so the static grid is
# ``tiles + E - 1`` steps; which tile and expert a step visits is a table
# made on the device from ``group_sizes`` and scalar-prefetched. An empty
# expert has no visit, tiles past the rows held (``sum(group_sizes)``) are
# written as zeros and nothing is loaded or multiplied for them, and the
# steps left over repeat the last visit's blocks and do nothing.
# ---------------------------------------------------------------------------


def _gmm_visits(group_sizes, rows: int, tile: int, weights: bool):
    """The visit table of a grid of ``tiles + E - 1`` steps, int32 ``[steps]``
    each: ``(expert, tile, out, lo, hi, flags)``. Step ``s`` multiplies rows
    ``lo[s] .. hi[s]`` (none where ``hi == lo``) of row tile ``tile[s]`` by
    ``expert[s]``. The rows' passes write row tile ``out[s]``, ``flags`` 1
    on its first step (tiles past the rows held follow the visits, a step
    each); the weights' pass gives every expert a visit, an empty one too
    (its gradient is written as zeros), ``flags`` bit 0 on an expert's first
    step and bit 1 on its last."""
    E = group_sizes.shape[0]
    tiles = -(-rows // tile)
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), rows)
    starts = jnp.concatenate([jnp.zeros((1, ), jnp.int32), ends[:-1]])
    first_tile = starts // tile
    span = jnp.where(ends > starts, (ends - 1) // tile - first_tile + 1,
                     int(weights))
    v_end = jnp.cumsum(span)
    n_visits = v_end[-1]
    s = jnp.arange(tiles + E - 1, dtype=jnp.int32)
    live = s < n_visits
    at = jnp.minimum(s, jnp.maximum(n_visits - 1, 0))   # the rest repeat the last
    e = jnp.minimum(jnp.sum(v_end[None, :] <= at[:, None], axis=1,
                            dtype=jnp.int32), E - 1)
    t = jnp.minimum(first_tile[e] + at - (v_end - span)[e], tiles - 1)
    lo = jnp.where(live, jnp.maximum(starts[e], t * tile), 0)
    hi = jnp.where(live, jnp.minimum(ends[e], (t + 1) * tile), 0)

    def changes(v, to_next=False):
        other = jnp.roll(v, -1 if to_next else 1)
        edge = s == (n_visits - 1 if to_next else 0)
        return (v != other) | edge

    if weights:
        flags = live * (changes(e) + 2 * changes(e, to_next=True))
        return e, t, t, lo, hi, flags.astype(jnp.int32)
    out = jnp.where(live, t, jnp.minimum(-(-ends[-1] // tile) + s - n_visits,
                                         tiles - 1))
    return e, t, out, lo, hi, changes(out).astype(jnp.int32)


# the rows XLA's kernel adds to an expert's float32 sums at a time (v5e, PR
# 47's sweep: the weights' pass is ``ragged_dot``'s to the bit summed so)
GMM_SUM_ROWS = 128


def _gmm_rows_kernel(e_ref, t_ref, out_ref, lo_ref, hi_ref, first_ref, x_ref,
                     w_ref, o_ref, *, tile, transposed):
    """``o[lo:hi] = x[lo:hi] @ w[e]`` (``@ w[e].T`` if ``transposed``), float32
    sums rounded once; the tile's other rows keep what an earlier visit wrote,
    zeros on its first."""
    s = pl.program_id(0)
    lo, hi, first = lo_ref[s], hi_ref[s], first_ref[s] == 1

    @pl.when(hi > lo)
    def _():
        prod = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1, ), (1 if transposed else 0, )), ((), ())),
            preferred_element_type=jnp.float32)
        row = out_ref[s] * tile + jax.lax.broadcasted_iota(
            jnp.int32, prod.shape, 0)
        kept = jnp.where(first, 0.0, o_ref[...].astype(jnp.float32))
        o_ref[...] = jnp.where((row >= lo) & (row < hi), prod,
                               kept).astype(o_ref.dtype)

    @pl.when((hi <= lo) & first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_weights_kernel(e_ref, t_ref, _, lo_ref, hi_ref, flags_ref, x_ref,
                        dy_ref, o_ref, acc_ref, *, tile):
    """``o[e] = x[rows of e].T @ dy[rows of e]``: float32 sums over the
    expert's visits, rounded once on its last. Both operands are masked: a
    row that is not the expert's, or no expert's, is never read as a number.
    The sums grow GMM_SUM_ROWS rows at a time whatever the tile, which is the
    order XLA's kernel adds them in: the same bits."""
    s, step = pl.program_id(0), min(tile, GMM_SUM_ROWS)
    lo, hi, flags = lo_ref[s], hi_ref[s], flags_ref[s]

    @pl.when(flags % 2 == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(hi > lo)
    def _():
        def add(i, _):
            at = pl.multiple_of(i * step, step)

            def own(ref):
                row = t_ref[s] * tile + at + jax.lax.broadcasted_iota(
                    jnp.int32, (step, ref.shape[1]), 0)
                return jnp.where((row >= lo) & (row < hi),
                                 ref[pl.ds(at, step), :], 0)

            acc_ref[...] += jax.lax.dot_general(
                own(x_ref), own(dy_ref), (((0, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, tile // step, add, None)

    @pl.when(flags >= 2)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gmm_call(leg: str, table, lhs, rhs, tile: int, interpret: bool):
    """One pass over the visits of ``table`` (:func:`_gmm_visits`) as one
    ``pallas_call`` with one result. ``rows``: ``lhs [R, K] x rhs [E, K, N]
    -> [R, N]``; ``d_rows``: ``lhs [R, N] x rhs [E, K, N] -> [R, K]`` (the
    contraction over ``N`` by the dot's dimension numbers: no transposed copy
    of the weights); ``weights``: ``lhs [R, K], rhs [R, N] -> [E, K, N]``."""
    R, steps = lhs.shape[0], table[0].shape[0]
    experts = steps - -(-R // tile) + 1

    def row_tile(width):
        return pl.BlockSpec((tile, width), lambda s, e, t, *_: (t[s], 0))

    def expert(k, n):
        return pl.BlockSpec((1, k, n), lambda s, e, *_: (e[s], 0, 0))

    if leg == "weights":
        k, n = lhs.shape[1], rhs.shape[1]
        kernel = functools.partial(_gmm_weights_kernel, tile=tile)
        in_specs, out_specs = [row_tile(k), row_tile(n)], expert(k, n)
        out_shape, scratch = (experts, k, n), [pltpu.VMEM((k, n), jnp.float32)]
    else:
        _, k, n = rhs.shape
        width = k if leg == "d_rows" else n
        kernel = functools.partial(_gmm_rows_kernel, tile=tile,
                                   transposed=leg == "d_rows")
        in_specs = [row_tile(lhs.shape[1]), expert(k, n)]
        out_specs = pl.BlockSpec((tile, width),
                                 lambda s, e, t, out, *_: (out[s], 0))
        out_shape, scratch = (R, width), []
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table), grid=(steps, ), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(out_shape, lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ),
            vmem_limit_bytes=kd.vmem_limit_bytes(
                kd.gmm_vmem_bytes(leg, tile, k, n, lhs.dtype.itemsize))),
        interpret=interpret,
        name=f"moe_gmm_{leg}",
    )(*table, lhs, rhs)


# One jitted body a pass, at module level, and one for the table: jit
# remembers the jaxpr it traced for a (shapes, dtype, tile), so every call
# site of a program holds the SAME ``pallas_call``, and the lowering emits it,
# through Mosaic, once a program and calls it from each site (some 120 of 6
# shapes in a share cell's step). The table is the rows' and the weights', not
# a pass's or a width's: two traces and lowerings a program, not six.
def _jitted_leg(leg: str):
    def call(table, lhs, rhs, tile, interpret):
        return _gmm_call(leg, table, lhs, rhs, tile, interpret)
    call.__name__ = call.__qualname__ = f"moe_gmm_{leg}"
    return jax.jit(call, static_argnames=("tile", "interpret"))


_GMM_LEG = {leg: _jitted_leg(leg) for leg in ("rows", "d_rows", "weights")}
_GMM_VISITS = jax.jit(_gmm_visits, static_argnames=("rows", "tile", "weights"))


def _count_traced(leg: str, impl: str):
    from ..observability import get_registry
    get_registry().counter(
        "ds_moe_gmm_traced_total",
        "Grouped matmuls of the experts traced into a program, by pass (rows, "
        "d_rows, weights) and implementation: moe_gmm, the program's kernel, "
        "or ragged_dot, XLA's, whose backward is jax's own and is not counted",
        labels={"leg": leg, "impl": impl}).inc()


def _gmm_leg(leg: str, lhs, rhs, group_sizes, tile):
    _count_traced(leg, "moe_gmm")
    tile = tile or kd.GMM_ROW_TILE
    table = _GMM_VISITS(group_sizes, rows=lhs.shape[0], tile=tile,
                        weights=leg == "weights")
    return _GMM_LEG[leg](table, lhs, rhs, tile=tile,
                         interpret=interpret_kernels())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, ))
def moe_gmm(rows, w, group_sizes, tile=None):
    """``rows [R, K] x w [E, K, N] -> [R, N]`` with the sorted rows' groups
    given by ``group_sizes [E]``, as ``jax.lax.ragged_dot(...,
    preferred_element_type=rows.dtype)`` gives it, bit for bit, rows from
    ``sum(group_sizes)`` on zeros; the Pallas kernel, forward and backward.
    ``tile``: sorted rows a grid step (tests and the sweep pin it)."""
    return _gmm_leg("rows", rows, w, group_sizes, tile)


def _moe_gmm_fwd(rows, w, group_sizes, tile):
    return (_gmm_leg("rows", rows, w, group_sizes, tile),
            (rows, w, group_sizes))


def _moe_gmm_bwd(tile, res, dy):
    rows, w, group_sizes = res
    return (_gmm_leg("d_rows", dy, w, group_sizes, tile),
            _gmm_leg("weights", rows, dy, group_sizes, tile), None)


moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


def grouped_matmul(rows, w, group_sizes):
    """The experts' matmul over sorted rows, ``[R, K] x [E, K, N] -> [R, N]``
    in ``rows.dtype``: float32 sums rounded once on write (the same bits as a
    float32 result cast afterwards, half the bytes). :func:`moe_gmm` where
    ``kernel_dispatch.gmm_impl`` says so, else ``jax.lax.ragged_dot``."""
    if kd.gmm_impl(rows.shape[0], w.shape[1], w.shape[2], rows.dtype,
                   _kernel_here()) == kd.IMPL_PALLAS:
        return moe_gmm(rows, w, group_sizes)
    _count_traced("rows", "ragged_dot")
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=rows.dtype)


def traced_counts() -> dict:
    """``ds_moe_gmm_traced_total`` as the process-wide registry has it now:
    ``{(leg, impl): count}``, every program this process traced."""
    from ..observability import get_registry
    return {(m.labels["leg"], m.labels["impl"]): int(m.value)
            for m in get_registry().series("ds_moe_gmm_traced_total")}


def traced_note(since=None) -> str:
    """What ``ds_moe_gmm_traced_total`` has counted since ``since`` (an
    earlier :func:`traced_counts`: the registry is the process's, and an
    engine's line is about its own programs; default: in this process), for
    a log line: ``grouped_matmul[rows=moe_gmm:36,d_rows=moe_gmm:24,...]``."""
    since = since or {}
    found = [f"{leg}={impl}:{n - since.get((leg, impl), 0)}"
             for (leg, impl), n in traced_counts().items()
             if n > since.get((leg, impl), 0)]
    return f"grouped_matmul[{','.join(found)}]"


def moe_grouped_mlp(x, w1, w3, w2, top_idx, top_w, *, activation=jax.nn.silu):
    """Token-choice MoE MLP via grouped GEMMs.

    ``y[t] = Σ_j top_w[t,j] · ffn_{top_idx[t,j]}(x[t])`` with
    ``ffn_e(h) = (act(h @ w1[e]) * (h @ w3[e])) @ w2[e]`` (SwiGLU).

    Args:
      x: ``[T, H]`` tokens.
      w1, w3: ``[E, H, F]``; w2: ``[E, F, H]`` expert weights.
      top_idx: ``[T, k]`` int32 chosen experts.
      top_w: ``[T, k]`` combine weights (already normalized).
    Returns:
      ``[T, H]`` in x.dtype.
    """
    k = top_idx.shape[1]
    # the gathers have a scope each (the sort is dispatch's); the grouped
    # matmuls between them keep the block's own, and their instruction names
    with jax.named_scope("ds.moe.dispatch"):
        order, inv = moe_sort_permutation(top_idx)
        group_sizes = expert_counts(top_idx, w1.shape[0])
        xs = moe_dispatch(x, order, inv, k)  # [T*k, H] expert-contiguous
    y = grouped_matmul(activation(grouped_matmul(xs, w1, group_sizes))
                       * grouped_matmul(xs, w3, group_sizes), w2, group_sizes)
    with jax.named_scope("ds.moe.combine"):
        return moe_combine(y, top_w, order, inv)


SHARE_ROWS_FACTOR = 2


def share_rows(assignments: int, held: int, num_experts: int, crowding: int = 1) -> int:
    """Static length of the sorted-rows prefix a share works on:
    ``SHARE_ROWS_FACTOR`` times the even share of the ``assignments`` (``crowding`` times it: what a
    group-limited router sends when every token keeps the group held), in 8-row tiles, at most all."""
    even = -(-assignments * held * crowding // num_experts)
    return min(assignments, -(-SHARE_ROWS_FACTOR * even // 8) * 8)


def moe_share_permutation(top_idx, first_expert: int, held: int):
    """Sort (token, choice) assignments so that those of the experts held
    (``first_expert .. first_expert + held``) come first, by expert, in token
    order; every other assignment follows. Returns ``(order, group_sizes
    [held])`` as :func:`moe_sort_permutation` and :func:`expert_counts` give
    them for all experts. Where the inverse is needed (the exact pass over
    all rows), the pass takes it itself."""
    local = top_idx.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return order, expert_counts(key, held)


# the kernel that sums token-sorted rows into their tokens: tokens a grid
# step, and sorted rows a step's matmul takes
TOKEN_BLOCK = 128
ROW_CHUNK = 128


def _sum_rows_kernel(first_ref, spans_ref, tok_ref, scale_ref, rows_ref,
                     out_ref, acc_ref, *, weighted):
    """Step ``(b, c)`` of ``(token blocks, chunks a block can span)`` adds to
    block ``b``'s float32 sums the rows of its ``c``-th chunk that are its
    tokens': ``M @ rows`` with ``M[t, i] = scale[i]`` where row ``i`` is
    token ``t``'s, 0 elsewhere. A weighted ``M`` goes to the MXU as three
    bfloat16 pieces that add up to its float32 value: every product is
    exact, the sums are float32."""
    b, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c < spans_ref[b])
    def _():
        token = b * TOKEN_BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (TOKEN_BLOCK, ROW_CHUNK), 0)
        m = jnp.where(tok_ref[0] == token, scale_ref[0], 0.0)
        rows = rows_ref[...]

        def dot(lhs, **kw):
            return jnp.dot(lhs, rows, preferred_element_type=jnp.float32, **kw)

        if rows.dtype != jnp.bfloat16:
            acc_ref[...] += dot(m, precision=jax.lax.Precision.HIGHEST)
        elif not weighted:  # zeros and ones
            acc_ref[...] += dot(m.astype(jnp.bfloat16))
        else:
            hi = m.astype(jnp.bfloat16)
            rest = m - hi.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            acc_ref[...] += dot(low) + dot(mid) + dot(hi)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _sum_sorted_rows(rows, scale, tok, num_tokens, per_token, interpret):
    """``out[t] = Σ_{i: tok[i] == t} scale[i] · rows[i]`` as ``[num_tokens,
    H]`` in ``rows.dtype``: ``tok`` ascending, ``TOKEN_BLOCK`` times the
    blocks or more for a row that is no token's (such rows zeros), at most
    ``per_token`` rows a token, ``len(tok)`` whole chunks."""
    R, H = rows.shape
    blocks, chunks = -(-num_tokens // TOKEN_BLOCK), R // ROW_CHUNK
    # rows edge[b] .. edge[b + 1] are block b's
    bounds = jnp.arange(blocks + 1, dtype=jnp.int32) * TOKEN_BLOCK
    edge = jnp.sum(tok[None, :] < bounds[:, None], axis=1, dtype=jnp.int32)
    first = jnp.minimum(edge[:-1] // ROW_CHUNK, chunks - 1)
    spans = jnp.where(edge[1:] > edge[:-1],
                      -(-edge[1:] // ROW_CHUNK) - first, 0)

    def chunk(b, c, first_ref, spans_ref):
        # a step past the block's span stays on its last chunk: no new copy
        return first_ref[b] + jnp.maximum(jnp.minimum(c, spans_ref[b] - 1), 0)

    scalars = pl.BlockSpec((1, 1, ROW_CHUNK), lambda *a: (chunk(*a), 0, 0))
    # double-buffered blocks in and out, the float32 sums and three products
    limit = kd.vmem_limit_bytes(TOKEN_BLOCK * H * (4 * rows.dtype.itemsize + 16))
    out = pl.pallas_call(
        functools.partial(_sum_rows_kernel, weighted=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks, -(-TOKEN_BLOCK * per_token // ROW_CHUNK) + 1),
            in_specs=[scalars, scalars,
                      pl.BlockSpec((ROW_CHUNK, H), lambda *a: (chunk(*a), 0))],
            out_specs=pl.BlockSpec((TOKEN_BLOCK, H), lambda b, c, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((TOKEN_BLOCK, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((blocks * TOKEN_BLOCK, H), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
        name="moe_rows_to_tokens",
    )(first, spans, tok.reshape(chunks, 1, ROW_CHUNK),
      (jnp.ones((R, ), jnp.float32) if scale is None else scale).reshape(
          chunks, 1, ROW_CHUNK), rows)
    return out[:num_tokens]


def _kernel_here() -> bool:
    """A raw ``pallas_call`` is not partitioned under GSPMD: the kernel runs
    on a TPU where the mesh is one device (as ``models/llama.py`` decides for
    the other kernels)."""
    from ..comm.mesh import get_mesh_context, mesh_is_initialized
    return on_tpu() and (not mesh_is_initialized() or all(
        n == 1 for n in get_mesh_context().mesh.shape.values()))


def _rows_to_tokens(rows, scale, tok, n_held, num_tokens: int, per_token: int,
                    use_kernel=None):
    """Sorted rows back to their tokens, by the rows held: row ``r`` of
    ``rows[R, H]`` is token ``tok[r]``'s and live while ``r < n_held``; token
    ``t`` gets ``Σ scale[r] · rows[r]`` over its live rows (``scale`` ``[R]``
    float32, or None for ones), at most ``per_token`` of them, summed in
    float32 and cast once to ``rows.dtype``: exact zeros where it has none.
    What lies from ``n_held`` on is never read as a number.

    A second sort, ``R`` long, puts the live rows in token order, the others
    last; one gather of ``R`` rows follows it, the others filled with zeros.
    A token's rows are then adjacent, and on one TPU device a kernel sums them
    a block of tokens a grid step (``moe_rows_to_tokens``: a one-hot matmul,
    so a row that is not finite spoils the tokens of its block, which a step
    with such a row has lost anyway); elsewhere a scatter-add does, whose
    indices XLA is told are sorted. docs/kernel_dispatch.md has the timings."""
    R = rows.shape[0]
    if use_kernel is None:
        use_kernel = _kernel_here()
    none = -(-num_tokens // TOKEN_BLOCK) * TOKEN_BLOCK
    key = jnp.where(jnp.arange(R) < n_held, tok, none)
    key, src = jax.lax.sort_key_val(key, jnp.arange(R, dtype=jnp.int32))
    src = jnp.where(key < none, src, R)      # the others gather the fill
    if use_kernel:                           # whole chunks of rows
        key = jnp.pad(key, (0, -R % ROW_CHUNK), constant_values=none)
        src = jnp.pad(src, (0, -R % ROW_CHUNK), constant_values=R)
    rows = rows.at[src].get(mode="fill", fill_value=0)
    if scale is not None:
        scale = scale.at[src].get(mode="fill", fill_value=0)
    if use_kernel:
        return _sum_sorted_rows(rows, scale, key, num_tokens, per_token,
                                interpret_kernels())
    terms = rows.astype(jnp.float32)
    if scale is not None:
        terms = terms * scale[:, None]
    out = jnp.zeros((num_tokens, rows.shape[1]), jnp.float32).at[key].add(
        terms, mode="drop", indices_are_sorted=True)
    return out.astype(rows.dtype)


def _rows_back(rows, pos, n_held):
    """``rows[pos]`` in float32 where ``pos`` is a row held (``< n_held``),
    zeros elsewhere: whatever lies beyond the rows held is never read as a
    number. The token-side form: a gather at every (token, choice)."""
    got = rows.at[pos].get(mode="fill", fill_value=0)
    return jnp.where((pos < n_held)[..., None], got.astype(jnp.float32), 0.0)


def share_walks_rows(rows: int, assignments: int) -> bool:
    """Whether a share working on ``rows`` of ``assignments`` sorted rows
    brings them back to their tokens by the rows (:func:`_rows_to_tokens`)
    or by the token positions (:func:`_rows_back`, which needs the inverse
    permutation). Measured on a v5e at 32,768 tokens x top-4 x 2,048 bf16
    (docs/kernel_dispatch.md): the combine by rows | by positions takes 1.2 |
    4.0 ms at an eighth of the assignments, 2.9 | 7.9 at a quarter, 5.3 | 8.1
    at half, 9.0 to 10.2 | 7.5 to 7.7 at all of them, where the sort and the
    kernel come on top of the same 131,072-row gather. The rule stays on the
    measured side of a crossover near four fifths."""
    return 2 * rows <= assignments


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, ))
def share_dispatch(x, order_r, inv, n_held, k):
    """``x[T, H] → xs[R, H]``: the first ``R`` sorted rows, zeros from row
    ``n_held`` on. Transpose: each token gets back the sum, in float32, of
    its rows that are held: by the rows where ``inv`` is None, else gathered
    at its k positions."""
    valid = jnp.arange(order_r.size) < n_held
    return jnp.where(valid[:, None], x[order_r // k], 0)


def _share_dispatch_fwd(x, order_r, inv, n_held, k):
    return share_dispatch(x, order_r, inv, n_held, k), \
        (order_r, inv, n_held, x.shape[0])


def _share_dispatch_bwd(k, res, dxs):
    order_r, inv, n_held, tokens = res
    if inv is None:
        dx = _rows_to_tokens(dxs, None, order_r // k, n_held, tokens, k)
    else:
        dx = jnp.sum(_rows_back(dxs, inv.reshape(-1, k), n_held),
                     axis=1).astype(dxs.dtype)
    return dx, None, None, None


share_dispatch.defvjp(_share_dispatch_fwd, _share_dispatch_bwd)


@jax.custom_vjp
def share_combine(y, top_w, order_r, inv, n_held):
    """``out[t] = Σ_j top_w[t, j] · y[row of (t, j)]`` over the assignments
    whose row is held; the others add nothing. As :func:`moe_combine` where
    ``inv`` is given; where it is None by the rows: each of the ``R`` takes
    its weight and goes to its token."""
    T, k = top_w.shape
    if inv is None:
        w_sorted = top_w.reshape(-1)[order_r].astype(jnp.float32)
        return _rows_to_tokens(y, w_sorted, order_r // k, n_held, T, k)
    yk = _rows_back(y, inv.reshape(T, k), n_held)
    return jnp.sum(yk * top_w.astype(jnp.float32)[:, :, None],
                   axis=1).astype(y.dtype)


def _share_combine_fwd(y, top_w, order_r, inv, n_held):
    return share_combine(y, top_w, order_r, inv, n_held), \
        (y, top_w, order_r, inv, n_held)


def _share_combine_bwd(res, dout):
    y, top_w, order_r, inv, n_held = res
    T, k = top_w.shape
    valid = jnp.arange(order_r.size) < n_held
    g = dout[order_r // k].astype(jnp.float32)          # [R, H] expert order
    w_sorted = top_w.reshape(-1)[order_r].astype(jnp.float32)
    dy = jnp.where(valid[:, None], g * w_sorted[:, None], 0.0).astype(y.dtype)
    dw_sorted = jnp.sum(y.astype(jnp.float32) * g, axis=-1)
    if inv is None:
        # a prefix of a permutation: each live row has a place of its own
        dw = jnp.zeros((T * k, ), jnp.float32).at[
            jnp.where(valid, order_r, T * k)].set(
                dw_sorted, mode="drop", unique_indices=True).reshape(T, k)
    else:
        dw = _rows_back(dw_sorted[:, None], inv.reshape(T, k), n_held)[..., 0]
    return dy, dw.astype(top_w.dtype), None, None, None


share_combine.defvjp(_share_combine_fwd, _share_combine_bwd)


def _share_rows_mlp(x, w1, w3, w2, top_w, order, group_sizes, *, rows,
                    activation, by_rows=None):
    """The share's MLP over the first ``rows`` sorted rows (``by_rows``:
    whether they go back to their tokens by the rows; by default as
    :func:`share_walks_rows` says for ``rows`` of all of ``order``)."""
    n_held = jnp.sum(group_sizes)
    if by_rows is None:
        by_rows = share_walks_rows(rows, order.size)
    with jax.named_scope("ds.moe.dispatch"):
        inv = None if by_rows else jnp.argsort(order).astype(jnp.int32)
        xs = share_dispatch(x, order[:rows], inv, n_held, top_w.shape[1])
    y = grouped_matmul(activation(grouped_matmul(xs, w1, group_sizes))
                       * grouped_matmul(xs, w3, group_sizes), w2, group_sizes)
    with jax.named_scope("ds.moe.combine"):
        return share_combine(y, top_w, order[:rows], inv, n_held)


def _share_all_rows_mlp(x, w1, w3, w2, top_w, order, group_sizes, *, window,
                        activation):
    """The exact pass where the rows held outran the static prefix: the
    sorted rows ``window`` at a time, each window the prefix's program on
    its own rows and its own part of every expert's group, the windows'
    results summed in float32. The branch is hardly ever taken, and XLA
    reserves a ``cond``'s memory for its larger branch: walked whole, all
    ``T * k`` rows at once, this one held 3.6 GB that no step used at 262,144
    assignments of 2,048 values (v5e compile, PR 37); in windows it holds
    what the prefix's branch does."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    padded = jnp.pad(order, (0, -order.size % window))  # past n_held: not read

    def one_window(y, lo):
        sizes = jnp.clip(ends, lo, lo + window) - jnp.clip(starts, lo, lo + window)
        rows = jax.lax.dynamic_slice(padded, (lo, ), (window, ))
        part = jax.checkpoint(functools.partial(
            _share_rows_mlp, rows=window, activation=activation, by_rows=True))(
                x, w1, w3, w2, top_w, rows, sizes)
        return y + part.astype(jnp.float32), None

    y, _ = jax.lax.scan(one_window, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(0, padded.size, window, dtype=jnp.int32))
    return y.astype(x.dtype)


def moe_grouped_mlp_share(x, w1, w3, w2, top_idx, top_w, *, first_expert: int,
                          num_experts: int, activation=jax.nn.silu, crowding=1):
    """The part of a token-choice MoE MLP that the experts held here give.

    ``y[t] = Σ_j [first_expert <= top_idx[t,j] < first_expert + E_held]
    · top_w[t,j] · ffn_{top_idx[t,j]}(x[t])``: routing is over
    ``num_experts``, ``w1/w3 [E_held, H, F]`` and ``w2 [E_held, F, H]`` are
    the experts held. Exact: no capacity, no row dropped (module docstring).

    Returns ``(y [T, H], rows_held, fell_back)``: the (token, choice) rows
    sent to the experts held and whether they outran ``share_rows``, both
    int32 scalars. ``crowding``: as ``share_rows`` takes it (1: no groups).
    """
    held, assignments = w1.shape[0], top_idx.size
    with jax.named_scope("ds.moe.dispatch"):
        order, group_sizes = moe_share_permutation(top_idx, first_expert,
                                                   held)
    rows_held = jnp.sum(group_sizes)
    bound = share_rows(assignments, held, num_experts, crowding)

    def over(rows):
        # nothing between the matmuls is kept for the backward: the cond
        # would keep it for both branches, the unused one's as zeros
        return jax.checkpoint(functools.partial(
            _share_rows_mlp, rows=rows, activation=activation))

    operands = (x, w1, w3, w2, top_w, order, group_sizes)
    if bound >= assignments:
        return over(assignments)(*operands), rows_held, jnp.int32(0)
    fell_back = rows_held > bound
    y = jax.lax.cond(fell_back,
                     functools.partial(_share_all_rows_mlp, window=bound,
                                       activation=activation),
                     over(bound), *operands)
    return y, rows_held, fell_back.astype(jnp.int32)


def lora_sort_slots(slots, n_slots):
    """Sort per-token adapter slot ids for the grouped LoRA delta — the
    k=1 specialization of :func:`moe_sort_permutation` (every token has exactly
    one adapter). Hoist this ONCE per forward and reuse the (order,
    group_sizes) pair across every layer/target: the sort is a function of
    the batch's slot assignment only.

    Args:
      slots: ``[T]`` int32 adapter slot per token (0 = identity).
      n_slots: static slot-pool size (bank leading dim).
    Returns:
      (order ``[T]`` sort permutation, group_sizes ``[n_slots]`` int32).
    """
    order = jnp.argsort(slots, stable=True)
    group_sizes = jnp.bincount(slots, length=n_slots).astype(jnp.int32)
    return order, group_sizes


def lora_grouped_delta(x, a, b, scale_sorted, order, group_sizes):
    """Batched multi-LoRA delta ``y[t] += B[s_t] @ (A[s_t] @ x[t]) * scale``
    via the sort-by-slot ragged idiom — ONE pair of grouped GEMMs covers a
    mixed-adapter token wave, FLOPs ∝ rank regardless of how many adapters
    are live, and slot 0's zero factors make base-only tokens an exact
    no-op (delta ≡ 0.0, so streams stay bit-identical to the base model).

    Args:
      x: ``[T, in]`` tokens (original order).
      a: ``[n_slots, in, r]`` stacked down-projection factors.
      b: ``[n_slots, r, out]`` stacked up-projection factors.
      scale_sorted: ``[T]`` fp32 per-token ``alpha / sqrt(r)`` in SORTED
        order (``scale[slots][order]`` — the caller gathers once).
      order, group_sizes: from :func:`lora_sort_slots`.
    Returns:
      ``[T, out]`` fp32 delta in original token order.
    """
    xs = x[order]
    h = jax.lax.ragged_dot(xs, a, group_sizes,
                           preferred_element_type=jnp.float32).astype(x.dtype)
    y = jax.lax.ragged_dot(h, b, group_sizes,
                           preferred_element_type=jnp.float32)
    y = y * scale_sorted[:, None]
    return jnp.zeros((x.shape[0], b.shape[-1]), jnp.float32).at[order].set(y)


def lora_dense_delta(x, a, b, slots, scale):
    """Dense-gather reference for :func:`lora_grouped_delta` — the numerics
    oracle: per-token factor gather + two plain matmuls, no sort."""
    af = a[slots].astype(jnp.float32)        # [T, in, r]
    bf = b[slots].astype(jnp.float32)        # [T, r, out]
    h = jnp.einsum("ti,tir->tr", x.astype(jnp.float32), af)
    y = jnp.einsum("tr,tro->to", h, bf)
    return y * scale[slots][:, None]


def moe_dense_mlp(x, w1, w3, w2, top_idx, top_w, *, activation=jax.nn.silu):
    """Dense-over-experts reference (every expert for every token, masked
    combine) — the numerics oracle for tests and the fallback when an
    'expert'-sharded mesh axis makes the sort/a2a layout preferable."""
    E = w1.shape[0]
    cw = jnp.sum(top_w[..., None] * jax.nn.one_hot(top_idx, E, dtype=top_w.dtype),
                 axis=-2)  # [T, E]
    a = activation(jnp.einsum("th,ehf->tef", x, w1)) * jnp.einsum("th,ehf->tef", x, w3)
    y = jnp.einsum("tef,efh->teh", a, w2)
    return jnp.einsum("te,teh->th", cw.astype(y.dtype), y).astype(x.dtype)


registry.register("moe_rows_to_tokens", "pallas", True,
                  "a share's token-sorted rows summed into their tokens")
registry.register("grouped_matmul", "pallas", True,
                  "MoE grouped GEMM, FLOPs proportional to top-k (reference "
                  "cutlass_ops moe_gemm): moe_gmm_* on one TPU device at bf16, "
                  "widths on the 128 grid and 2,048 rows or more; "
                  "jax.lax.ragged_dot (xla) elsewhere")
