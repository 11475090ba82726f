"""What a recomputed layer keeps from its forward when no policy is named.

``remat: true`` with ``remat_policy: null`` (``models/llama.py``) and
``activation_checkpointing.checkpoint()`` with no policy recompute a layer
in its backward but for the values named here. The attention kernels' output
and log-sum-exp (``ops/attention.py::RESIDUAL_NAMES``) are always kept, as are
a learned sparse attention's thresholds (``DSA_CHOICE``: two int32 a token
and layer) and what a layer hands to the layers after it (``SHARED``); the
candidates below are kept as far down the list as the chip has room for, so
that what makes them runs once a step:

    ds.dsa.mask      a learned sparse attention's choice as a bit mask
                     (``ops/dsa_attention.py``: seq x seq / 8 bytes a row of
                     the batch, 134 MB at 32,768); a layer without it makes
                     the indexer's scores once more in its backward
    ds.moe.route     the router's logits, choice and weights where the choice
                     is a top-k of biased scores (~10 MB a layer: nearly free)
    ds.mixer.out     a mixer's output projection (``o_proj``, ``out_proj``)
                     whose contraction is deeper than the hidden size
    ds.kda.scan      a Kimi Delta Attention scan's output and the float32
                     states leaving its chunks (``ops/kda.py``: 1.34 GB a
                     layer at 32,768 tokens, 32 heads of 128 and chunks of
                     64); a layer without them runs ``kda_chunk_fwd`` once
                     more in its backward
    ds.gdn.scan      a Gated DeltaNet scan's output and states, the same
                     (``ops/gdn.py``: the value heads count)
    ds.selscan.scan  a Mamba-1 selective scan's output and the float32
                     states entering its blocks (``ops/selective_scan.py``:
                     0.21 GB a layer at 16,384 tokens and 5,120 channels of
                     16 states); a layer without them runs ``selscan_fwd``
                     once more in its backward
    ds.ffn.in        a dense FFN's ``gate`` / ``up`` (``fc1``) outputs, a
                     shared expert's too
    ds.mixer.in      a mixer's input projections as they leave their matmuls
                     (q, k, v; ``in_proj``; a latent operator's ``q_proj``,
                     ``kv_a_proj_with_mqa``, ``kv_b_proj``)
    ds.mixer.out.narrow  an output projection no deeper than the hidden size
    ds.mixer.kernel  the convolution kernels' outputs

The order is milliseconds of recomputation returned a byte kept, measured on
a v5e (PERF.md §6, PR 41; the mask PR 46): a scoring pass of 15.8 ms for
0.134 GB is 118 ms a GB; a router's gather, top-k and float32 matmul for a
few megabytes; a bf16 matmul's output returns its contraction depth in FLOPs
a byte, so a 4,096-deep output projection (SDAR's attention, Mamba) 17-23 ms
a GB, gate / up and the 2,048-deep input projections 10-12; a 2,048-deep
output projection returns 9 ms a GB in the LFM2 cell and 1 in Kimi-VL's (the
residual add and the FFN's norm, which the recomputed matmul had in its
epilogue, read the kept value back); the memory-bound kernels ~6. A name is
the identity outside a recomputation. What sits inside a ``lax.cond`` (a share's gathered
rows and grouped matmuls, ``ops/grouped_matmul.py``) has no name: a name in
a branch is invisible to the enclosing layer's policy.

``choose_kept`` is the rule: one pure function of the bytes available and
the layers' price list. The rest of the module observes: the price list from
the traced shapes (``price_list``), the chip from ``memory_stats()``
(``device_memory``), and what the step needs beside the kept values
(``step_reserve_bytes``). With nothing to spend, or on a backend that
reports no memory (a CPU), the plan is ``RESIDUAL_NAMES`` alone.
"""

import contextlib
import contextvars
from typing import Optional, Sequence, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

from ..utils.logging import logger
from .attention import RESIDUAL_NAMES

# a learned sparse attention (ops/dsa_attention.py): its rows' thresholds,
# kept with the kernels' residuals whatever the plan (two int32 a token), and
# its choice as a bit mask, a candidate (T * T / 8 bytes a row of the batch)
DSA_CHOICE = ("ds.dsa.tau", "ds.dsa.tie")
DSA_MASK = "ds.dsa.mask"
# what a layer hands to the layers after it beside the residual stream (a
# differential attention layer's keys and values, a Mamba-1 layer's scan
# output: ``models/llama.py``): inputs of every recomputed reader, so alive
# from their source to its backward whatever is planned, and kept by name so
# that the source's own recomputation does not make them again
SHARED_KV = "ds.shared.kv"
SHARED_MEMORY = "ds.shared.memory"
SHARED = (SHARED_KV, SHARED_MEMORY)
ROUTE = "ds.moe.route"
MIXER_OUT = "ds.mixer.out"
KDA_SCAN = "ds.kda.scan"
GDN_SCAN = "ds.gdn.scan"
SELSCAN_SCAN = "ds.selscan.scan"
FFN_IN = "ds.ffn.in"
MIXER_IN = "ds.mixer.in"
MIXER_OUT_NARROW = "ds.mixer.out.narrow"
KERNEL_OUT = "ds.mixer.kernel"
# the walk's order: ms of recomputation returned a byte, falling
CANDIDATE_NAMES = (DSA_MASK, ROUTE, MIXER_OUT, KDA_SCAN, GDN_SCAN, SELSCAN_SCAN, FFN_IN,
                   MIXER_IN, MIXER_OUT_NARROW, KERNEL_OUT)
KEPT_NAMES = RESIDUAL_NAMES + DSA_CHOICE + SHARED + CANDIDATE_NAMES

# The step's own temporaries, in one place. Compiled for a described v5e
# (tests/unit/ops/test_tpu_aot_compile.py, ..._mla.py; PR 41) the four
# recomputing cells' steps hold, beside the values kept by name, 4.36 GB
# (LFM2), 4.56 (Granite), 5.03 (Kimi-VL) and 5.00 GB (SDAR) of temporaries:
# 26-30% of the chip's 16.9 GB, made of the compute-dtype parameters and
# their gradients, the layers' inputs, one layer's internals with their
# cotangents, a share's rows and the head's chunk. The reserve is 33% of the
# reported limit, 5.58 GB: the largest of the four and 0.55 GB for the
# program's code, the batch and fragmentation; with what the rule then keeps,
# each cell's compiled step and 12 B a parameter stay 1.0-2.9 GB under the
# limit (the AOT tests hold them to 0.8). 35% would cost the Granite cell the
# second of the two FFN layers it keeps. A program whose layers are wider
# than that (a large batch on a small model) is held to its own shapes
# instead: the layers' inputs, and twice the largest layer's priced values
# (its internals live beside their cotangents while its backward runs).
RESERVE_SHARE_OF_LIMIT = 0.33

# ONE policy for every layer, whatever each keeps: a candidate its layer does
# not keep is named with this suffix at the producer (``keeping``), which the
# policy does not know. jax keys its caches of a jitted function's partial
# evaluation, and so of its lowering, on the policy OBJECT: with a policy a
# layer, the Granite cell's nine Mamba layers traced and lowered their Pallas
# kernels nine times where they had shared one (the step's first call 88 ->
# 260 s on the chip: PERF.md §6, PR 41).
AGAIN = ".again"
KEPT_POLICY = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
_KEEPING = contextvars.ContextVar("ds_remat_keeping", default=None)

Prices = Sequence[Sequence[Tuple[str, int]]]    # a layer: ((name, bytes), ...)


@contextlib.contextmanager
def keeping(names: Optional[Tuple[str, ...]]):
    """While a layer is traced inside: the names it keeps under
    ``KEPT_POLICY`` (its row of the plan). None: every name as it is, for a
    policy that chooses by name (``activation_checkpointing.checkpoint``)
    and for ``price_list``. The names are not in any of jax's cache keys:
    what is traced inside has to be a function jax has not traced before
    (flax's lifted ``nn.remat`` makes one a call), and ``keep`` is not to be
    called under an inner ``jax.jit``."""
    token = _KEEPING.set(names)
    try:
        yield
    finally:
        _KEEPING.reset(token)


def keeps(name: str) -> bool:
    """Whether the layer being traced (``keeping``) keeps ``name``."""
    kept = _KEEPING.get()
    return kept is None or name in kept


def keep(x, name: str):
    """``x`` under ``name``, which a recomputation's policy may keep; under
    ``name + AGAIN``, which none does, where the layer being traced
    (``keeping``) does not keep it."""
    return checkpoint_name(x, name if keeps(name) else name + AGAIN)


def handed_on(x, name: str):
    """``x`` under ``name`` of ``SHARED``, which every plan keeps."""
    return checkpoint_name(x, name)


def choose_kept(available: Optional[int], prices: Prices,
                same_in_all_layers: bool = False) -> Tuple[Tuple[str, ...], ...]:
    """-> the names each layer keeps: ``RESIDUAL_NAMES`` and, walking the
    candidates in ``CANDIDATE_NAMES``' order and within a name the layers
    from the first, every (name, layer) until one does not fit in
    ``available`` bytes: the kept set is a prefix of that walk, so it grows
    with the budget and never passes it. ``prices[i]`` are layer ``i``'s
    (name, bytes); a name a layer does not offer costs nothing and is not
    listed for it. ``same_in_all_layers`` (a ``scan_layers`` body: one
    program for every layer): a name is kept in all layers or in none.
    ``available`` None or <= 0: ``RESIDUAL_NAMES`` alone."""
    kept = [list(RESIDUAL_NAMES) for _ in prices]
    left = available or 0
    cost = [{name: sum(b for n, b in layer if n == name) for name in CANDIDATE_NAMES}
            for layer in prices]
    for name in CANDIDATE_NAMES:
        if same_in_all_layers:
            steps = [(range(len(prices)), sum(c[name] for c in cost))]
        else:
            steps = [((i, ), c[name]) for i, c in enumerate(cost)]
        for layers, nbytes in steps:
            if nbytes == 0:
                continue
            if nbytes > left:
                return tuple(tuple(k) for k in kept)
            left -= nbytes
            for i in layers:
                if cost[i][name]:
                    kept[i].append(name)
    return tuple(tuple(k) for k in kept)


def kept_bytes(plan, prices: Prices) -> int:
    """Bytes of the candidates ``plan`` keeps (``RESIDUAL_NAMES`` are not
    priced: every plan keeps them)."""
    return sum(b for names, layer in zip(plan, prices) for n, b in layer if n in names)


def price_list(fn, *args, **kwargs) -> Tuple[Tuple[str, int], ...]:
    """(name, bytes) of every candidate ``fn(*args)`` names, from the shapes
    in its trace (arrays or ``jax.ShapeDtypeStruct``s; nothing runs). A
    ``lax.cond``'s branches are not walked: the policy does not see them."""
    from jax._src import core
    found = []

    def walk(jaxpr, times):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "name" and eqn.params["name"] in CANDIDATE_NAMES:
                found.append((eqn.params["name"], times * sum(
                    v.aval.size * v.aval.dtype.itemsize for v in eqn.outvars)))
            if prim == "cond":
                continue
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub, times * (eqn.params["length"] if prim == "scan" else 1))

    with keeping(None):
        walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr, 1)
    return tuple(found)


def device_memory() -> Optional[Tuple[int, int]]:
    """-> (bytes_limit, bytes_in_use) of this process's first device, None
    where the backend reports none (a CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        return None
    limit = int(stats.get("bytes_limit", 0))
    return (limit, int(stats.get("bytes_in_use", 0))) if limit else None


def batch_shards(rows: int) -> int:
    """Devices the mesh spreads ``rows`` batch rows over (``data`` x
    ``fsdp``, as ``ZeroShardingPlan.batch_sharding``): traced shapes are the
    whole batch's, the chip holds its share."""
    from ..comm.mesh import get_mesh_context, mesh_is_initialized
    if not mesh_is_initialized():
        return 1
    dp = get_mesh_context().dp_size
    return dp if dp > 1 and rows % dp == 0 else 1


def step_reserve_bytes(limit: int, prices: Prices, layer_input_bytes: int) -> int:
    """What the step needs beside the candidates (``RESERVE_SHARE_OF_LIMIT``
    says why): a share of the chip, or by the program's own shapes if that
    is more."""
    widest = max((sum(b for _, b in layer) for layer in prices), default=0)
    return max(int(RESERVE_SHARE_OF_LIMIT * limit),
               len(prices) * layer_input_bytes + 2 * widest)


# the plans made in this process, by what they were made for: a program is
# traced more than once (the step, then the trace its FLOPs and kept bytes
# are read from) and every trace has to see the same plan, whatever the
# chip holds by then. ``forget_plans`` when the resident state changes (a
# new engine).
_PLANS = {}


def forget_plans():
    _PLANS.clear()


def plan_for(key, prices_of, *, rows: int, layer_input_bytes: int,
             always_kept_bytes: int = 0, same_in_all_layers: bool = False):
    """The plan for the program ``key`` describes (hashable), made on first
    ask from what the chip reports then and remembered. ``prices_of()`` ->
    the layers' price list at traced (whole-batch) shapes, asked only where
    there is memory to spend; ``always_kept_bytes``: the ``RESIDUAL_NAMES``'
    bytes, kept whatever the plan. None: no budget, ``RESIDUAL_NAMES``
    alone in every layer."""
    if key in _PLANS:
        return _PLANS[key]
    memory = device_memory()
    if memory is None:
        return None
    limit, in_use = memory
    shards = batch_shards(rows)
    prices = tuple(tuple((n, b // shards) for n, b in layer)
                   for layer in prices_of())
    reserve = step_reserve_bytes(limit, prices, layer_input_bytes // shards)
    available = limit - in_use - reserve - always_kept_bytes // shards
    plan = choose_kept(available, prices, same_in_all_layers)
    offered = sum(b for layer in prices for _, b in layer)
    logger.info(
        f"remat: {kept_bytes(plan, prices)} of {offered} candidate bytes kept "
        f"(limit {limit}, in use {in_use}, reserve {reserve}, residuals "
        f"{always_kept_bytes // shards}, available {available}); by layer: "
        + " | ".join(",".join(n for n in names if n in CANDIDATE_NAMES) or "-"
                     for names in plan))
    _PLANS[key] = plan
    return plan
