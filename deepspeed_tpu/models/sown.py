"""What an operator sows for the host, declared once.

A flax operator that wants a statistic of its step on the host (a state's
largest value, a router's counts) sows it with :func:`sow` into its family's
collection. A :class:`Family` says, for each statistic, how it is reduced over
the layers inside the step and which gauge or counter shows it; a model class
lists its families (``LlamaForCausalLM.sown_families``) and the training
engine reads that list and names no family: what is mutable in the step, the
reductions, the publish and ``engine.sown_stats(family)`` all come from here
(docs/observability.md, "What an operator sows for the host").
"""

import contextlib
import contextvars
import functools
import operator
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


class OverLayers(NamedTuple):
    """How a statistic becomes one value of a step."""
    within: Callable    # two calls of one module: the sow's ``reduce_fn``
    across: Callable    # the sown leaves of every layer, in the tree's order
    # a mean: each call of a module that is called ``repeated`` times sows its
    # share, so that ``within`` (a sum) leaves the calls' mean
    averaged: bool = False


def _flat(leaves):
    # a leaf a layer, or one leaf with a leading axis under a layer scan
    return jnp.concatenate([leaf.reshape(-1) for leaf in leaves])


def _summed(each):
    return lambda leaves: functools.reduce(operator.add, map(each, leaves))


MAX = OverLayers(jnp.maximum, lambda leaves: jnp.max(_flat(leaves)))
MEAN = OverLayers(operator.add, lambda leaves: jnp.mean(_flat(leaves)), averaged=True)
SUM = OverLayers(operator.add, _summed(jnp.sum))
# kept a layer, in the layers' order (a sum over a deep model may pass 32 bits:
# the host adds them up)
A_LAYER = OverLayers(operator.add, _flat)
# a vector a block (a router's width): summed over the blocks, last axis kept
SUM_LAST_KEPT = OverLayers(
    operator.add, _summed(lambda leaf: leaf.reshape(-1, leaf.shape[-1]).sum(axis=0)))


# How the steps of one publish become a series' value: each takes the
# statistic's host values, a step (or a K-step dispatch, a leading axis) each.

def steps_max(values):
    return max(np.max(v) for v in values)


def steps_mean(values):
    return np.mean([np.mean(v) for v in values])


def steps_mean_kept(values):
    # a vector a step (a position a pass): the steps' mean, the positions kept
    return np.mean([np.asarray(v).reshape(-1, np.shape(v)[-1]).mean(axis=0)
                    for v in values], axis=0)


def last_step(values):
    return np.mean(values[-1])


def steps_total(values):
    # added to a counter: the only one of the four that is
    return sum(float(np.sum(v, dtype=np.float64)) for v in values)


class Gauge(NamedTuple):
    """A ``ds_*`` series of the registry and what it shows."""
    name: str
    help: str
    source: str                 # a statistic of the family, or a key of its ``derive``
    steps: Optional[Callable]   # over the steps of a publish; None: ``derive`` made it

    # a vector's positions as series of one name: the label each is told by
    label: Optional[str] = None

    @property
    def counter(self) -> bool:
        """A counter the value is added to, not a gauge set to it."""
        return self.steps is steps_total

    def publish(self, registry, value) -> None:
        """``value`` (of a publish's steps) into the registry's series."""
        labelled = ([(None, value)] if self.label is None else
                    [({self.label: str(i)}, v) for i, v in enumerate(np.ravel(value))])
        for labels, v in labelled:
            if self.counter:
                registry.counter(self.name, self.help, labels).inc(float(v))
            else:
                registry.gauge(self.name, self.help, labels).set(float(v))


class Family(NamedTuple):
    """The statistics one kind of operator sows.

    ``stats`` names each with its reduction over the layers; the step returns
    it as ``prefix + name``. ``derive``, where the family has one, makes the
    values no single statistic holds from the fetched statistics of a run of
    steps (own names, no prefix); with ``derived_view`` its result for the
    newest step alone is what ``engine.sown_stats(name)`` returns, else the
    statistics themselves are. ``aux_loss``: the family's view also carries the
    auxiliary loss the step added to the model's (all that was sown into
    ``aux_loss``, summed)."""
    name: str
    stats: Dict[str, OverLayers]
    gauges: Tuple[Gauge, ...] = ()
    derive: Optional[Callable[[Sequence[dict]], dict]] = None
    derived_view: bool = False
    aux_loss: bool = False
    prefix: Optional[str] = None    # None: ``name + "_"``

    @property
    def collection(self) -> str:
        return self.name + "_stats"

    def key(self, name: str) -> str:
        """The name a step returns the statistic ``name`` under."""
        return (self.name + "_" if self.prefix is None else self.prefix) + name

    def of(self, step: dict) -> dict:
        """This family's statistics out of a step's, under their own names."""
        prefix = self.key("")
        names = (*self.stats, "aux_loss") if self.aux_loss else self.stats
        return {name: step[prefix + name] for name in names if prefix + name in step}


def _declared(family) -> Family:
    # by name, or a model's own family as the object
    return family if isinstance(family, Family) else FAMILIES[family]


# how many times the modules being traced are called in one apply (a looped
# stack: ``models/llama.py``, ``total_ut_steps``), for ``OverLayers.averaged``
_CALLS = contextvars.ContextVar("ds_sown_calls", default=1)


@contextlib.contextmanager
def repeated(calls: int):
    """While a stack that is applied ``calls`` times over the same modules is
    traced inside."""
    token = _CALLS.set(calls)
    try:
        yield
    finally:
        _CALLS.reset(token)


def wanted(module, family) -> bool:
    """Whether this apply collects the family's statistics: ask before making
    a value that costs something."""
    return module.is_mutable_collection(_declared(family).collection)


def sow(module, family, values: dict):
    """Sow ``values`` (statistic -> this call's value) as ``family``'s, in the
    order the family declares them; nothing where the apply does not collect
    them. A statistic the family does not declare is an error."""
    fam = _declared(family)
    unknown = set(values) - set(fam.stats)
    if unknown:
        raise KeyError(f"{fam.name!r} declares no statistic {sorted(unknown)}")
    for name, how in fam.stats.items():
        if name in values:
            value = jnp.asarray(values[name])
            if how.averaged and _CALLS.get() > 1:
                value = value / _CALLS.get()
            module.sow(fam.collection, name, value, reduce_fn=how.within,
                       init_fn=functools.partial(jnp.zeros, value.shape, value.dtype))


def _moe_load(steps):
    # [E] a step, [K, E] a K-step dispatch
    counts = sum(np.asarray(s["expert_counts"], np.int64)
                 .reshape(-1, s["expert_counts"].shape[-1]).sum(axis=0)
                 for s in steps)
    load = {"load_max_over_mean": float(counts.max() / max(counts.mean(), 1.0))}
    if "rows_held" in steps[0]:
        # blocks that hold a share of the router's experts
        held = sum(float(np.sum(s["rows_held"])) for s in steps)
        load["rows_held_share"] = held / max(float(counts.sum()), 1.0)
    return load


def _dsa_view(steps):
    chosen = sum(int(np.sum(s["chosen_pairs"], dtype=np.int64)) for s in steps)
    causal = sum(float(np.sum(s["causal_pairs"], dtype=np.float64)) for s in steps)
    return {"chosen_pairs": chosen, "causal_pairs": int(causal),
            "chosen_pairs_by_layer": [int(n) for s in steps
                                      for n in np.ravel(s["chosen_pairs"])],
            "chosen_share": chosen / max(causal, 1.0),
            "kth_score_mean": float(np.mean([np.mean(s["kth_score_mean"]) for s in steps])),
            "masks_kept": sum(int(np.sum(s["masks_kept"])) for s in steps)}


def _diffusion_view(steps):
    masked, tokens, t_sum = (sum(float(np.sum(s[name])) for s in steps)
                             for name in ("masked_tokens", "tokens", "t_sum"))
    return {"masked_tokens": int(masked),
            "mask_rate": masked / max(tokens, 1.0),
            "t_mean_masked": t_sum / max(masked, 1.0)}


def _delta_rule(name, what, decay_over, kda_only=()):
    """Kimi Delta Attention's and Gated DeltaNet's: one set of statistics."""
    return Family(
        name,
        # ``fused_rows``: 1.0 from a layer whose norms and beta products rode
        # inside the kernels, 0.0 from one XLA made them for; where the chunk
        # kernels ran, ``head_block`` and ``grid_steps`` (the heads a grid
        # step took, the steps a call: the layers' calls are alike)
        stats={"state_absmax": MAX, "decay_mean": MEAN, "beta_mean": MEAN,
               "fused_rows": MEAN, "head_block": MAX, "grid_steps": MAX},
        gauges=(
            Gauge(f"ds_{name}_state_absmax",
                  f"Largest |S| the {what} scans held (the chunks' states where "
                  "the kernels run), over the layers and the steps of the last "
                  "publish", "state_absmax", steps_max),
            Gauge(f"ds_{name}_decay_mean",
                  f"Mean decay exp(g) a {decay_over} and token of the {what} "
                  "layers, over the steps of the last publish",
                  "decay_mean", steps_mean),
            *kda_only))


FAMILIES = {family.name: family for family in (
    Family(
        "moe", prefix="", aux_loss=True, derive=_moe_load,
        # ``expert_counts`` [E] a block, E the router's width; from blocks
        # that hold a share of the experts ``rows_held`` and ``share_fallback``
        stats={"expert_counts": SUM_LAST_KEPT, "group_counts": SUM_LAST_KEPT,
               "rows_held": SUM, "share_fallback": SUM},
        gauges=(
            Gauge("ds_moe_tokens_routed_total",
                  "(token, expert) assignments the router made, summed over MoE "
                  "layers and steps (top_k per token and layer: none is dropped)",
                  "expert_counts", steps_total),
            Gauge("ds_moe_expert_load_max_over_mean",
                  "Busiest expert's assignments over the mean expert's, counts "
                  "summed over MoE layers and the steps of the last publish",
                  "load_max_over_mean", None),
            Gauge("ds_moe_rows_held_total",
                  "(token, expert) assignments sent to the experts held on "
                  "this chip, summed over MoE layers and steps",
                  "rows_held", steps_total),
            Gauge("ds_moe_rows_held_share",
                  "Rows held over all assignments the router made, over the "
                  "steps of the last publish (experts held / router width "
                  "when the router is even)", "rows_held_share", None),
            Gauge("ds_moe_share_fallback_total",
                  "MoE layers of a step whose rows held outran the static "
                  "rows array and took the exact pass over all assignments",
                  "share_fallback", steps_total),
            Gauge("ds_moe_aux_loss",
                  "Router load-balancing term as added to the loss "
                  "(coefficient included, summed over layers), mean over the "
                  "steps of the last publish", "aux_loss", steps_mean))),
    Family(
        "ssm",
        stats={"state_absmax": MAX, "dt_mean": MEAN},
        gauges=(
            Gauge("ds_ssm_state_absmax",
                  "Largest |S| the state-space scans held (the chunks' states "
                  "where the kernels run), over the layers and the steps of "
                  "the last publish", "state_absmax", steps_max),
            Gauge("ds_ssm_dt_mean",
                  "Mean step size dt = softplus(dt + dt_bias) of the "
                  "state-space layers, over the steps of the last publish",
                  "dt_mean", steps_mean))),
    Family(
        "mla",
        # the rms of the latent before its norm and of the shared rope key
        stats={"latent_rms": MEAN, "k_rope_rms": MEAN},
        gauges=tuple(
            Gauge("ds_mla_" + name,
                  f"Root mean square of {what} in the latent-attention "
                  "layers, their mean over the steps of the last publish",
                  name, steps_mean)
            for name, what in (("latent_rms", "the latent before kv_a_layernorm"),
                               ("k_rope_rms", "the shared rope key")))),
    Family(
        "diffusion", derive=_diffusion_view, derived_view=True,
        # the batch's data tokens, those masked, and the sum of their ``t``
        stats={"tokens": SUM, "masked_tokens": SUM, "t_sum": SUM},
        gauges=(
            Gauge("ds_diffusion_masked_tokens_total",
                  "Data tokens the block-diffusion noising replaced by the mask "
                  "id (those that carry loss), summed over steps",
                  "masked_tokens", steps_total),
            Gauge("ds_diffusion_mask_rate",
                  "Masked tokens over data tokens, over the steps of the last "
                  "publish (the mean noise level t the batches drew)",
                  "mask_rate", None))),
    Family(
        "dsa", derive=_dsa_view, derived_view=True,
        # ``masks_kept``: the layers whose backward read the forward's mask
        stats={"chosen_pairs": A_LAYER, "causal_pairs": A_LAYER,
               "kth_score_mean": MEAN, "masks_kept": SUM},
        gauges=(
            Gauge("ds_dsa_chosen_pairs_total",
                  "(query, key) pairs the learned sparse attention's indexer "
                  "chose, summed over layers and steps",
                  "chosen_pairs", steps_total),
            Gauge("ds_dsa_chosen_share",
                  "Chosen over causal (query, key) pairs of the sparse-attention "
                  "layers, over the steps of the last publish",
                  "chosen_share", None))),
    _delta_rule("kda", "Kimi Delta Attention", "key channel", kda_only=(
        Gauge("ds_kda_fused_rows",
              "Share of the Kimi Delta Attention layers whose row norms, beta "
              "products and gated output norm rode inside the chunk kernels in "
              "the last step (0: XLA made them around the recurrence)",
              "fused_rows", last_step),
        Gauge("ds_kda_head_block",
              "Heads a grid step of the Kimi Delta Attention chunk kernels "
              "took in the last step (kernel_dispatch.choose_kda_heads)",
              "head_block", last_step),
        Gauge("ds_kda_grid_steps",
              "Grid steps a call of the Kimi Delta Attention chunk kernels "
              "made in the last step: batch x heads / ds_kda_head_block x chunks",
              "grid_steps", last_step))),
    Family(
        "selscan",
        stats={"state_absmax": MAX, "dt_mean": MEAN},
        gauges=(
            Gauge("ds_selscan_state_absmax",
                  "Largest |h| the Mamba-1 selective scans held (the blocks' "
                  "states where the kernels run), over the layers and the steps "
                  "of the last publish", "state_absmax", steps_max),
            Gauge("ds_selscan_dt_mean",
                  "Mean step size dt = softplus(dt_proj(delta)) of the Mamba-1 "
                  "layers, over the steps of the last publish",
                  "dt_mean", steps_mean))),
    Family(
        "diffattn",
        # the differential layers' ``lambda``, in their order
        stats={"lambda_mean": A_LAYER},
        gauges=(
            Gauge("ds_diffattn_lambda_mean",
                  "Mean over the differential attention layers of the weight "
                  "lambda their second softmax map is subtracted with, over the "
                  "steps of the last publish", "lambda_mean", steps_mean), )),
    _delta_rule("gdn", "Gated DeltaNet", "value head"),
    Family(
        "attn",
        # gated softmax attention: the mean of ``sigmoid(gate)``
        stats={"gate_mean": MEAN},
        gauges=(
            Gauge("ds_attn_gate_mean",
                  "Mean of sigmoid(gate) over the gated softmax attention layers' "
                  "outputs, over the steps of the last publish",
                  "gate_mean", steps_mean), )),
    Family(
        "loop",
        # a looped model's exits (``total_ut_steps`` passes over one stack,
        # an exit gate after each): ``[passes]`` the mean exit mass p_t and
        # the mean next-token CE of each pass's logits over the counted
        # positions, and the mean entropy of p; sown once a step, by the model
        stats={"exit_mass": A_LAYER, "ce": A_LAYER, "exit_entropy": MEAN},
        gauges=(
            Gauge("ds_loop_exit_mass",
                  "Mean over the counted positions of the exit distribution's "
                  "mass p_t on each pass of a looped model, over the steps of "
                  "the last publish", "exit_mass", steps_mean_kept, label="pass"),
            Gauge("ds_loop_ce",
                  "Mean next-token cross-entropy of each pass's own logits of a "
                  "looped model, over the steps of the last publish",
                  "ce", steps_mean_kept, label="pass"),
            Gauge("ds_loop_exit_entropy",
                  "Mean entropy of the exit distribution over a looped model's "
                  "passes (nats), over the steps of the last publish",
                  "exit_entropy", steps_mean))),
)}
