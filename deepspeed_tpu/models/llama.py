"""Llama-family decoder (flagship model).

The reference frames models through HF + kernel injection
(``module_inject/containers/llama.py``); here the model is native flax,
designed TPU-first:

- all matmuls batched/bfloat16-friendly (MXU), no data-dependent control flow
- GQA attention with RoPE; mask folded into one fused softmax
- optional ``scan_layers`` wraps the decoder stack in ``nn.scan`` so compile
  time and HLO size stay O(1) in depth (the 70B path)
- logical-axis metadata on every kernel via ``nn.with_partitioning`` against
  *logical* names; ``parallel/tp.py`` maps logical→mesh axes (AutoTP analog)
"""

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp
import flax.linen as nn
from flax.linen import partitioning as nn_partitioning
from . import sown
from ..ops import remat
from ..ops.registry import interpret_kernels, on_tpu

# logical axis names; mapped onto mesh axes by parallel/tp.py rules
EMBED = "embed"
HIDDEN = "mlp"
HEADS = "heads"
KV = "kv"
VOCAB = "vocab"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What ONE decoder layer is built from, for models whose layers are not
    all alike (LFM2: gated short convolutions with an attention layer among
    every few, a dense FFN in the leading layers and experts after)."""
    # "attention" | "conv" (ops/short_conv.py) | "mamba" (Mamba2Mixer) |
    # "latent" (LatentAttention: DeepSeek-V2/V3's MLA) | "kda" (KimiDeltaMixer)
    # | "gdn" (GatedDeltaNetMixer) | "mamba1" (SelectiveScanMixer) | "gmu"
    # (GatedMemoryUnit)
    operator: str = "attention"
    ffn: str = "dense"              # "dense" (LlamaMLP) | "moe" (LlamaMoEBlock)
    ffn_width: int = 0              # the dense FFN's, or ONE expert's, width
    # an "attention" layer's own sliding window (0: what the config's
    # ``sliding_window`` / ``sliding_window_layers`` say of it)
    window: int = 0
    # "attention" in the differential form (``LlamaAttention._differential``)
    differential: bool = False
    # what the layer reads of an EARLIER layer of the stack (-1: nothing): a
    # differential "attention" layer the keys and values of layer ``kv_from``
    # (it then has no k or v projection of its own), a "gmu" layer the scan
    # output of the "mamba1" layer ``memory_from``
    kv_from: int = -1
    memory_from: int = -1


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Decoder-family config. Defaults are Llama; the variant knobs below
    cover the reference's other injection containers (OPT/Falcon/Phi —
    ``module_inject/containers/``, ``inference/v2/model_implementations/``):
    learned positions + LayerNorm + ReLU fc MLP (OPT), parallel
    attention/MLP residual + MQA (Falcon), partial rotary + fused parallel
    block with biases (Phi)."""
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # qwen2-style qkv biases
    attention_out_bias: bool = False  # OPT/Phi: bias on the output projection
    # ---- architecture variant knobs ----
    # "rmsnorm" | "layernorm" (scale+bias) | "layernorm_nobias" (Cohere:
    # scale only) | "layernorm_np" (OLMo: non-parametric, no scale/bias)
    norm_type: str = "rmsnorm"
    # "rope" | "learned" (OPT) | "alibi" (BLOOM) | "none" (Granite 4.0-H: NoPE)
    pos_embedding: str = "rope"
    embed_layernorm: bool = False     # BLOOM word_embeddings_layernorm
    pos_offset: int = 0               # OPT stores positions at index pos+2
    rotary_dim: Optional[int] = None  # Phi partial rotary; None = full head_dim
    rope_interleaved: bool = False    # GPT-J adjacent-pair rotary layout
    # Mistral/GPT-Neo local attention: keys older than sliding_window are
    # masked. sliding_window_layers = indices using the window (None = all
    # layers when sliding_window is set; GPT-Neo alternates local/global)
    sliding_window: Optional[int] = None
    sliding_window_layers: Optional[Tuple[int, ...]] = None
    attn_scale: Optional[float] = None  # None = 1/sqrt(head_dim); GPT-Neo = 1.0
    clip_qkv: Optional[float] = None  # OLMo: clamp q/k/v projections to ±clip
    logit_scale: Optional[float] = None  # Cohere: logits *= logit_scale
    # True / "flat" (OLMo2, OLMoE): RMSNorm on the FLAT q/k projections
    # (q_norm over nq*hd, k_norm over nkv*hd) before the head reshape + rope.
    # "head" (LFM2): RMSNorm over each head's hd, one weight [hd] shared by
    # the heads, after the reshape and before rope
    qk_norm: "bool | str" = False
    # OLMo2: post-norm residual — x + norm(attn(x)), then x + norm(mlp(x));
    # layer norms are post_attention_layernorm / post_feedforward_layernorm
    post_norm: bool = False
    # Gemma-2: x + post_norm(attn(pre_norm(x))) for BOTH sublayers (norms:
    # input/post_attention + pre_feedforward/post_feedforward)
    sandwich_norm: bool = False
    # Gemma: RMSNorm scales stored as (weight - 1); apply (1 + w) * x_hat
    norm_plus_one: bool = False
    # Gemma: embeddings scaled by sqrt(hidden_size) after lookup
    embed_scale: Optional[float] = None
    # Gemma-2 softcaps: x -> cap * tanh(x / cap)
    attn_logit_softcapping: Optional[float] = None
    final_logit_softcapping: Optional[float] = None
    # "swiglu" | "gelu_fc" (exact erf, Falcon) | "gelu_tanh_fc" (HF
    # "gelu_new", Phi) | "relu_fc" (OPT)
    mlp_type: str = "swiglu"
    mlp_bias: bool = False            # fc1/fc2 biases (OPT/Phi)
    parallel_residual: bool = False   # Falcon/Phi: x + attn(ln(x)) + mlp(ln(x))
    # GPT-NeoX: the parallel MLP branch reads its OWN norm of x
    # (x + attn(ln1(x)) + mlp(ln2(x))); 1 = Falcon/Phi shared-norm form
    parallel_residual_norms: int = 1
    lm_head_bias: bool = False        # Phi
    # >0 = sparse MoE MLP: the ROUTER's width, every expert of the model
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    moe_renormalize: bool = True  # Mixtral renormalizes top-k; Qwen2-MoE not
    # the experts whose matrices live here, when that is a share of the
    # router's (expert parallelism as seen from one chip): share
    # ``moe_share_index`` of ``num_local_experts / moe_experts_held``, experts
    # ``index * held .. (index + 1) * held``. None = all of them
    moe_experts_held: Optional[int] = None
    moe_share_index: int = 0
    # "softmax" (Mixtral, OLMoE, Qwen2-MoE) | "sigmoid" (LFM2; DeepSeek-V3's
    # ``noaux_tc`` with one group, as Kimi-VL's language model has it):
    # independent scores; with ``moe_selection_bias`` the top-k is taken of
    # score + bias (a buffer: no gradient, its update rule is the training
    # recipe's and not implemented) and weighted by the unbiased scores
    moe_scoring: str = "softmax"
    moe_selection_bias: bool = False
    moe_renorm_eps: float = 0.0          # p / (sum p + eps) where renormalized
    routed_scaling_factor: float = 1.0
    # >0: sow the Switch/Mixtral load-balancing loss (reference
    # sharded_moe.py l_aux); the engine adds sown "aux_loss" scalars to the
    # training loss
    router_aux_loss_coef: float = 0.0
    # A dense "shared expert" this wide added to the sparse output (None =
    # none): scaled by a sigmoid gate of the token (Qwen2-MoE), or with
    # ``shared_expert_gated`` False added as it is (DeepSeek-V2/V3:
    # ``n_shared_experts * moe_intermediate_size`` wide, weight 1)
    shared_expert_intermediate_size: Optional[int] = None
    shared_expert_gated: bool = True
    moe_grouped: bool = True      # grouped GEMM (FLOPs ∝ top-k) vs dense-over-experts
    # One LayerSpec a layer, for models whose layers differ; None = every
    # layer attention + the one global FFN the fields above describe
    layer_specs: Optional[Tuple[LayerSpec, ...]] = None
    conv_L_cache: int = 3         # taps of the "conv" operator
    # the "latent" operator (MLA as it trains; nothing is absorbed): q is
    # ``heads x head_dim`` straight from the stream (no q_lora_rank), its last
    # ``rotary_dim`` values the rope part (HF ``qk_rope_head_dim``) and the
    # others the part without (``qk_nope_head_dim``); k and v come from a
    # latent of ``kv_lora_rank`` under its own RMSNorm, the rotary part of k
    # is ONE ``rotary_dim`` key a token shared by the heads, v is
    # ``v_head_dim`` wide
    kv_lora_rank: int = 0
    v_head_dim: int = 0
    # learned sparse attention on the "attention" operator (DeepSeek Sparse
    # Attention as it trains, ``ops/dsa_attention.py``): > 0 = an indexer of
    # ``dsa_index_heads`` heads ``dsa_index_head_dim`` wide with ONE key a
    # token scores every earlier token, and each query attends the
    # ``dsa_topk`` of largest score (all its heads alike). The choice passes
    # no gradient, so the indexer's parameters get none
    dsa_topk: int = 0
    dsa_index_heads: int = 0
    dsa_index_head_dim: int = 0
    # the "mamba" operator (Mamba-2): heads of mamba_d_head values, a state
    # of mamba_d_state a value, B and C shared by the heads of a group, the
    # scan in chunks of mamba_chunk_size, mamba_d_conv taps before it
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    # the "kda" operator (Kimi Delta Attention, ``ops/kda.py``):
    # ``num_attention_heads`` heads of ``kda_head_dim`` keys and values,
    # ``kda_d_conv`` causal taps with SiLU on q, k and v, a decay for every key
    # channel bounded below by ``kda_gate_floor`` (``g = floor * sigmoid(...)``),
    # the scan in chunks of ``kda_chunk_size``
    kda_head_dim: int = 128
    kda_d_conv: int = 4
    kda_gate_floor: float = -5.0
    kda_chunk_size: int = 64
    # the "gdn" operator (Gated DeltaNet, ``ops/gdn.py``): ``gdn_k_heads`` key
    # heads of ``gdn_k_head_dim`` under ``gdn_v_heads`` value heads of
    # ``gdn_v_head_dim`` (a multiple: consecutive value heads share a key head's
    # q and k), ``gdn_d_conv`` causal taps with SiLU over q | k | v together, one
    # log decay a value head and token with no floor (``-exp(A_log) *
    # softplus(.)``), the scan in chunks of ``gdn_chunk_size``
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0
    gdn_k_head_dim: int = 128
    gdn_v_head_dim: int = 128
    gdn_d_conv: int = 4
    gdn_chunk_size: int = 64
    # the "mamba1" operator (Mamba-1, ``ops/selective_scan.py``):
    # ``mamba1_d_inner`` channels, each with ``mamba_d_state`` states of a decay
    # rate of their own, ``mamba_d_conv`` taps (``mamba_conv_bias``) before the
    # scan, the step size through ``mamba1_dt_rank``; a "gmu" layer gates such a
    # layer's scan output, ``mamba1_d_inner`` wide
    mamba1_d_inner: int = 0
    mamba1_dt_rank: int = 0
    # the published index of this stack's layer 0, for a stack cut out of a
    # deeper model: differential attention's ``lambda_init`` follows the
    # published depth
    layer_index_offset: int = 0
    # "head": the "latent" operator's output times a sigmoid gate of the layer's
    # normed input, one value a head and token, before ``o_proj`` (Ling-3.0's
    # ``gated_attention_proj_granularity_type: head_wise``); "elementwise": the
    # "attention" operator's ``q_proj`` is twice as wide, its second half a gate
    # of every output value, ``o_proj(attn * sigmoid(gate))`` (Qwen3-Next);
    # None = no gate
    attn_output_gate: Optional[str] = None
    # group-limited choice of a sigmoid router with a selection bias (DeepSeek-V3's
    # ``noaux_tc``): the router's experts in ``moe_n_group`` groups of
    # consecutive experts, a group's score the sum of its two largest biased
    # scores, the top-k taken inside the ``moe_topk_group`` best groups. 1, 1 =
    # no groups
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Granite: x + residual_multiplier * sublayer(norm(x)) on both branches
    # of a layer_specs layer (None = 1)
    residual_multiplier: Optional[float] = None
    # The training objective. "causal_lm": next-token CE under the causal
    # mask. "block_diffusion" (BD3-LM's efficient form, SDAR's training):
    # the model runs ONCE on ``xt ⊕ x0``, a noised copy of each document in
    # positions [0, L) and the clean copy in [L, 2L), under the
    # block-diffusion mask over blocks of ``diffusion_block_length`` tokens
    # (``ops.attention.block_diffusion_mask``); only the noisy half reaches
    # the head, whose logits at position i predict ``x0[i]`` itself, each
    # masked token's CE weighted by 1/t of its block
    # (``runtime/data_pipeline/block_diffusion.py`` makes the batch).
    # ``diffusion_mask_id`` None = the vocabulary's last row
    objective: str = "causal_lm"
    diffusion_block_length: int = 4
    diffusion_mask_id: Optional[int] = None
    diffusion_t_min: float = 1e-3
    # A looped stack (Ouro's LoopLM, ``total_ut_steps``): the layers are
    # applied this many times over the SAME parameters; the one final norm
    # runs after every pass and its output is what the next pass reads, and
    # the head reads every pass's normed stream. 1 = every layer once.
    total_ut_steps: int = 1
    # a ``hidden -> 1`` exit gate (``early_exit_gate``, with a bias) reads the
    # normed stream of every pass but the last: ``lambda_t = sigmoid(g_t)``, a
    # token's exit distribution ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``,
    # the last pass taking what is left
    exit_gate: bool = False
    # the weight beta of the exit distribution's entropy in the training loss
    # ``mean_tokens [sum_t p_t CE_t - beta H(p)]`` (the LoopLM paper's Stage I
    # objective). None: the loss is the last pass's CE alone, which is what
    # the published ``early_exit_threshold`` 1 makes of HF's ``labels`` path
    exit_entropy_weight: Optional[float] = None
    attn_impl: str = "auto"       # "auto" | "flash" (Pallas) | "xla"
    dtype: Any = jnp.bfloat16
    scan_layers: bool = False
    # ZeRO-3 live-parameter governor (runtime/zero_governor.py): scan over
    # chunks of this many layers — one chunk's params is the hard ceiling on
    # gathered-live elements (reference stage3_max_live_parameters). 1 =
    # tightest ceiling; larger chunks trade memory for fewer scan steps.
    scan_chunk_size: int = 1
    remat: bool = False
    # jax.checkpoint_policies name for selective remat (e.g. "dots_saveable":
    # save matmul outputs, recompute elementwise/norms — most of the memory
    # saving at a fraction of full remat's recompute). None = the layer is
    # recomputed but for what its attention kernel gave (ops/attention.py::
    # RESIDUAL_NAMES) and, as far as the chip reports room beside the step,
    # its router's, projections' and FFN's named outputs in ops/remat.py's
    # order (none on a backend that reports no memory);
    # "nothing_saveable" = nothing kept.
    remat_policy: "Optional[str]" = None
    # chunked unembed+CE (ops/chunked_ce.py): a bound on the transient
    # logits, which hold at most tokens x ce_chunk_size elements and are
    # never saved for the backward. The op sweeps chunks of sequence
    # positions with the whole vocabulary in each, sc = the largest power of
    # two <= seq * ce_chunk_size / vocab positions a chunk, and forms loss
    # and gradient in that one sweep. None/0 = dense CE. The big win is
    # large-vocab training (32k: ~2 GB of saved activation at bs16 x 1k;
    # Gemma 256k: ~8 GB). Under a mesh each device sweeps its own sequences
    # against the whole head (gathered once, dw reduced once). Not tuned for
    # a mesh with seq > 1 (the scan slices the sequence axis).
    ce_chunk_size: "Optional[int]" = None

    @property
    def head_dim_(self):
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def looped_(self) -> bool:
        """Whether the model makes a stream a pass and not one."""
        return self.total_ut_steps > 1 or self.exit_gate

    @property
    def exit_loss_(self) -> bool:
        """Whether the training loss is the exit distribution's (a gate to
        read and an entropy weight), not the last pass's CE alone."""
        return (self.exit_gate and self.total_ut_steps > 1
                and self.exit_entropy_weight is not None)

    @property
    def experts_held_(self) -> int:
        return self.moe_experts_held or self.num_local_experts

    @property
    def block_diffusion_(self) -> bool:
        if self.objective not in ("causal_lm", "block_diffusion"):
            raise ValueError(f"unknown objective {self.objective!r}")
        return self.objective == "block_diffusion"

    @property
    def diffusion_mask_id_(self) -> int:
        return (self.vocab_size - 1 if self.diffusion_mask_id is None
                else self.diffusion_mask_id)

    def shared_sources(self) -> Tuple[Optional[int], ...]:
        """For every layer, the earlier layer whose keys and values (a
        differential "attention" layer with ``kv_from``) or scan output (a
        "gmu" layer) it reads, None for a layer that reads nothing. A reader
        whose source is not an earlier layer of the right kind IN THIS STACK
        is refused by name: a stack cut out of a deeper model has to keep the
        sources of the readers it keeps."""
        out = []
        for i, spec in enumerate(self.layer_specs or ()):
            source, wanted = None, None
            if spec.operator == "gmu":
                source, wanted = spec.memory_from, "mamba1"
            elif spec.operator == "attention" and spec.kv_from >= 0:
                source, wanted = spec.kv_from, "attention"
                if not spec.differential:
                    raise ValueError(f"layer {i}: keys and values of another layer "
                                     "(kv_from) are read by the differential form alone")
            elif spec.kv_from >= 0 or spec.memory_from >= 0:
                raise ValueError(f"layer {i} ({spec.operator!r}) reads nothing of "
                                 f"another layer: kv_from {spec.kv_from}, memory_from "
                                 f"{spec.memory_from}")
            if wanted is not None:
                what = "memory" if wanted == "mamba1" else "keys and values"
                if not 0 <= source < i:
                    raise ValueError(
                        f"layer {i} ({spec.operator!r}) reads the {what} of layer "
                        f"{source}, which is not an earlier layer of this stack of "
                        f"{len(self.layer_specs)}: keep the source with its readers")
                have = self.layer_specs[source]
                if have.operator != wanted or have.kv_from >= 0 or (
                        wanted == "attention" and not have.differential):
                    raise ValueError(
                        f"layer {i} ({spec.operator!r}) reads the {what} of layer "
                        f"{source}, a {have.operator!r} layer that makes none")
            out.append(source)
        return tuple(out)

    def per_layer_elements(self) -> int:
        """Analytic element count of one decoder layer (operator + MLP/MoE
        + norms) — the unit of the ZeRO-3 live-parameter budget; with
        ``layer_specs``, of the largest layer."""
        h, hd = self.hidden_size, self.head_dim_
        attn = h * (self.num_attention_heads * hd) * 2 \
            + h * (self.num_key_value_heads * hd) * 2
        if self.dsa_topk:   # the indexer: q, k and its LayerNorm, head weights
            attn += (h * (self.dsa_index_heads + 1) * self.dsa_index_head_dim
                     + 2 * self.dsa_index_head_dim + h * self.dsa_index_heads)
        proj = 3 if self.mlp_type in ("swiglu", "geglu_tanh") else 2

        def ffn(kind, width):
            if kind == "moe":
                return proj * h * width * self.experts_held_ \
                    + h * self.num_local_experts
            return proj * h * width

        if self.layer_specs is None:
            kind = "moe" if self.num_local_experts > 0 else "dense"
            return attn + ffn(kind, self.intermediate_size) + 2 * h
        conv = 4 * h * h + self.conv_L_cache * h    # in_proj, out_proj, taps
        inner = self.mamba_n_heads * self.mamba_d_head
        xbc = inner + 2 * self.mamba_n_groups * self.mamba_d_state
        mamba = (h * (inner + xbc + self.mamba_n_heads) + inner * h
                 + (self.mamba_d_conv + 1) * xbc + 3 * self.mamba_n_heads + inner)
        rope = self.rotary_dim or 0
        latent = (h * self.num_attention_heads * hd
                  + h * (self.kv_lora_rank + rope)
                  + self.kv_lora_rank * (1 + self.num_attention_heads
                                         * (hd - rope + self.v_head_dim))
                  + self.num_attention_heads * self.v_head_dim * h)
        if self.attn_output_gate:
            latent += h * self.num_attention_heads
        kda_inner = self.num_attention_heads * self.kda_head_dim
        kda = (6 * h * kda_inner + h * self.num_attention_heads
               + 3 * self.kda_d_conv * kda_inner
               + self.num_attention_heads + kda_inner + self.kda_head_dim)
        gdn_k = self.gdn_k_heads * self.gdn_k_head_dim
        gdn_v = self.gdn_v_heads * self.gdn_v_head_dim
        gdn = (h * (2 * gdn_k + 2 * gdn_v) + h * 2 * self.gdn_v_heads
               + self.gdn_d_conv * (2 * gdn_k + gdn_v) + 2 * self.gdn_v_heads
               + self.gdn_v_head_dim + gdn_v * h)
        if self.attn_output_gate == "elementwise":
            attn += h * self.num_attention_heads * hd
        wide, rank = self.mamba1_d_inner, self.mamba1_dt_rank
        mamba1 = (3 * h * wide + (self.mamba_d_conv + 1) * wide
                  + wide * (rank + 2 * self.mamba_d_state) + (rank + 1) * wide
                  + wide * self.mamba_d_state + wide)
        operator = {"conv": conv, "attention": attn, "mamba": mamba,
                    "latent": latent, "kda": kda, "gdn": gdn, "mamba1": mamba1,
                    "gmu": 2 * h * wide}
        return max(operator[spec.operator] + ffn(spec.ffn, spec.ffn_width) + 2 * h
                   for spec in self.layer_specs)

    def with_live_param_budget(self, max_live_parameters: int) -> "LlamaConfig":
        """Return a config whose layer scan chunk honors the ZeRO-3
        ``stage3_max_live_parameters`` budget (runtime/zero_governor.py):
        one chunk's params is the gathered-live ceiling."""
        from ..runtime.zero_governor import chunk_size_for
        chunk = chunk_size_for(self.num_hidden_layers, self.per_layer_elements(),
                               max_live_parameters)
        return dataclasses.replace(self, scan_layers=True, scan_chunk_size=chunk)

    # ---- presets ----
    @staticmethod
    def tiny(**over):
        return LlamaConfig(**{**dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                                     num_hidden_layers=2, num_attention_heads=4,
                                     num_key_value_heads=2, max_position_embeddings=128,
                                     rope_theta=10000.0), **over})

    @staticmethod
    def llama3_8b(**over):
        return LlamaConfig(**{**dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                                     num_hidden_layers=32, num_attention_heads=32,
                                     num_key_value_heads=8), **over})

    @staticmethod
    def llama3_70b(**over):
        return LlamaConfig(**{**dict(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
                                     num_hidden_layers=80, num_attention_heads=64,
                                     num_key_value_heads=8, scan_layers=True), **over})


def precompute_rope(head_dim: int, max_len: int, theta: float, dtype=jnp.float32):
    inv_freq = 1.0 / (theta**(jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin, positions, rotary_dim: Optional[int] = None,
               interleaved: bool = False):
    """x: [b, s, h, d]; rotate-half formulation (reference
    csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu, rebuilt in jnp —
    XLA fuses this into the surrounding matmuls). ``rotary_dim < d`` rotates
    only the leading slice (Phi-style partial rotary). ``interleaved``
    rotates adjacent pairs (x[2i], x[2i+1]) — GPT-J's layout — instead of the
    half-split (x[i], x[i+d/2]) NeoX/Llama layout."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
        return jnp.concatenate([apply_rope(xr, cos, sin, positions,
                                           interleaved=interleaved), xp],
                               axis=-1).astype(x.dtype)
    c = cos[positions][:, :, None, :]  # [b, s, 1, d/2]
    s = sin[positions][:, :, None, :]
    if interleaved:
        x1, x2 = x[..., ::2], x[..., 1::2]
        r1 = x1 * c - x2 * s
        r2 = x2 * c + x1 * s
        return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def rope_at(x, positions, theta: float):
    """Rotate-half rotary over ALL of x's last axis, the angles made from
    ``positions`` and not looked up (a second width beside the model's own
    table: the sparse-attention indexer's). x [b, s, h, d], positions [b, s]."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta**(jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    plus_one: bool = False  # Gemma stores scales as (weight - 1)

    @nn.compact
    def __call__(self, x):
        scale = self.param("weight",
                           nn.initializers.zeros if self.plus_one
                           else nn.initializers.ones,
                           (x.shape[-1], ), jnp.float32)
        if self.plus_one:
            scale = 1.0 + scale
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + self.eps)
        return (out * scale).astype(self.dtype)


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (HF ``build_alibi_tensor`` formula, including the
    non-power-of-2 interpolation). Press et al., "Train Short, Test Long"."""
    import math
    closest = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = base ** np.arange(1, closest + 1)
    if closest != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        extra = extra_base ** np.arange(1, 2 * (n_heads - closest), 2)
        slopes = np.concatenate([slopes, extra])
    return slopes.astype(np.float32)


def _use_cast(w, dtype):
    """Use-site weight cast, hoist-proof (engine ``param_cast="model"``).

    fp32 masters arrive stacked ``[L, ...]`` under ``nn.scan``; each scan
    step must down-convert only ITS slice, or peak HBM grows by a whole
    bf16 copy of the model. XLA undoes a naive in-body ``astype`` —
    ``convert(slice(W))`` commutes to ``slice(convert(W))`` and LICM hoists
    the now loop-invariant whole-tree convert right back out of the scan
    loop (the round-4 OOM pattern).
    The ``optimization_barrier`` between the slice and the cast makes that
    reorder illegal, pinning the convert to chunk granularity. When params
    already arrive at compute dtype (engine-side casting), this is a no-op.
    """
    if w.dtype == dtype:
        return w
    return jax.lax.optimization_barrier(w).astype(dtype)


class _BarrierDense(nn.Module):
    """nn.Dense with a hoist-proof use-site kernel cast (see _use_cast).
    Same param names/shapes/partitioning as nn.Dense."""
    features: int
    dtype: Any
    kernel_init: Any
    bias_init: Any
    use_bias: bool = False
    # the name a recomputed layer may keep the output under (ops/remat.py)
    keep: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (x.shape[-1], self.features))
        y = jax.lax.dot_general(
            x.astype(self.dtype), _use_cast(kernel, self.dtype),
            (((x.ndim - 1, ), (0, )), ((), ())))
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features, ))
            y = y + _use_cast(bias, self.dtype)
        return remat.keep(y, self.keep) if self.keep else y


def _dense(features, name, axes, dtype, use_bias=False, keep=None):
    return _BarrierDense(features, use_bias=use_bias, dtype=dtype, name=name, keep=keep,
                         kernel_init=nn.with_partitioning(nn.initializers.lecun_normal(), axes),
                         bias_init=nn.with_partitioning(nn.initializers.zeros, (axes[-1], )))


def _keep_out(cfg, inner: int):
    """The name a mixer's output projection is kept under, by its contraction
    ``inner``: deeper than the hidden size (SDAR's attention, Mamba: 4,096
    for 2,048) the matmul a kept output saves is worth two of what reading
    it back costs the residual add and the FFN's norm; no deeper, about one
    (``ops/remat.py`` has both prices), so it is taken after the FFN's and
    the input projections' outputs."""
    return remat.MIXER_OUT if inner > cfg.hidden_size else remat.MIXER_OUT_NARROW


def _make_norm(cfg, name):
    if cfg.norm_type == "layernorm":
        return nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype, name=name)
    if cfg.norm_type == "layernorm_nobias":  # Cohere: mean-subtracted, scale only
        return nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                            use_bias=False, name=name)
    if cfg.norm_type == "layernorm_np":  # OLMo: no learnable params at all
        return nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                            use_bias=False, use_scale=False, name=name)
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, plus_one=cfg.norm_plus_one,
                   name=name)


def _layer_window(cfg, layer_idx: int):
    """Sliding window for this layer (None = global attention): its own
    (``LayerSpec.window``), else the config's."""
    if cfg.layer_specs is not None and cfg.layer_specs[layer_idx].window:
        return cfg.layer_specs[layer_idx].window
    if cfg.sliding_window is None:
        return None
    if (cfg.sliding_window_layers is not None
            and layer_idx not in cfg.sliding_window_layers):
        return None
    return cfg.sliding_window


def _mesh_shape() -> dict:
    from ..comm.mesh import mesh_is_initialized, get_mesh_context
    return (dict(get_mesh_context().mesh.shape)
            if mesh_is_initialized() else {})


def _causal_attention_at_two_widths(cfg, q, k, v, scale, window=None):
    """Causal attention whose values are not as wide as its keys (latent
    attention: 192 | 128; differential attention's stacked call: 64 | 128),
    under ``window`` where there is one: the ``mla_*`` kernels on one TPU
    device where the sequence tiles, XLA's attention with the scores by hand
    anywhere else (a CPU, a mesh of more than one device: correct and
    unmeasured)."""
    from ..ops.attention import _xla_attention, flash_attention
    s = q.shape[1]
    one_device = all(n == 1 for n in _mesh_shape().values())
    if (cfg.attn_impl != "xla" and (cfg.attn_impl == "flash" or on_tpu())
            and one_device and (s <= 128 or s % 128 == 0)):
        return flash_attention(q, k, v, causal=True, scale=scale, window=window,
                               interpret=interpret_kernels())
    return _xla_attention(q, k, v, scale, True, window)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, cos, sin, positions, attn_mask=None, shared=None,
                 hand_on=False):
        cfg = self.config
        window = _layer_window(cfg, self.layer_idx)
        if cfg.layer_specs is not None and cfg.layer_specs[self.layer_idx].differential:
            return self._differential(x, attn_mask, window, shared, hand_on)
        b, s, _ = x.shape
        hd = cfg.head_dim_
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads

        gated = cfg.attn_output_gate == "elementwise"
        if cfg.attn_output_gate and not gated:
            raise ValueError(f"attn_output_gate {cfg.attn_output_gate!r}: the attention "
                             "operator knows the elementwise gate alone")
        q = _dense(nq * hd * (2 if gated else 1), "q_proj", (EMBED, HEADS), cfg.dtype,
                   cfg.attention_bias, remat.MIXER_IN)(x)
        if gated:   # the queries of every head, then the gates of every head
            q, gate = q[..., :nq * hd], q[..., nq * hd:]
        k = _dense(nkv * hd, "k_proj", (EMBED, KV), cfg.dtype, cfg.attention_bias,
                   remat.MIXER_IN)(x)
        v = _dense(nkv * hd, "v_proj", (EMBED, KV), cfg.dtype, cfg.attention_bias,
                   remat.MIXER_IN)(x)
        if cfg.clip_qkv is not None:  # OLMo stability clamp
            q = jnp.clip(q, -cfg.clip_qkv, cfg.clip_qkv)
            k = jnp.clip(k, -cfg.clip_qkv, cfg.clip_qkv)
            v = jnp.clip(v, -cfg.clip_qkv, cfg.clip_qkv)
        per_head = cfg.qk_norm == "head"
        if cfg.qk_norm and not per_head:
            # OLMo2: normalize the flat projections pre-reshape
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)

        q = q.reshape(b, s, nq, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if per_head:  # LFM2: each head's hd normalized, one weight for all
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_plus_one, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_plus_one, name="k_norm")(k)
        if cfg.pos_embedding == "rope":
            # closed before the kernel's call below: a scope that held it
            # would rename the instruction (docs/observability.md)
            with jax.named_scope("ds.rope"):
                q = apply_rope(q, cos, sin, positions, cfg.rotary_dim, cfg.rope_interleaved)
                k = apply_rope(k, cos, sin, positions, cfg.rotary_dim, cfg.rope_interleaved)

        # GQA handled natively by both paths (no materialized K/V head
        # repeat — 4x K/V bandwidth saving at 8B scale). The Pallas kernels
        # (fwd AND bwd, ops/attention.py) run on TPU when the shape tiles
        # cleanly and the mask is one they know by its structure: causal,
        # with or without a window, or the block-diffusion pattern. A mask
        # given as an array (padding) takes XLA's fused
        # dot_product_attention, as does every other case.
        from ..ops.attention import flash_attention

        mesh_shape = _mesh_shape()
        sp_sz = mesh_shape.get("seq", 1)
        one_device = all(n == 1 for n in mesh_shape.values())

        # shared kernel eligibility (shape/mask/positions); the sharded and
        # unsharded dispatch conditions below both build on it
        flash_shape_ok = (cfg.attn_impl != "xla" and attn_mask is None
                          and cfg.pos_embedding != "alibi"
                          and (s <= 128 or s % 128 == 0))
        on_flash_backend = cfg.attn_impl == "flash" or on_tpu()
        # a raw pallas_call doesn't auto-partition under GSPMD: on any mesh
        # of more than one device (data parallel and ZeRO included) the
        # sharded dispatch below owns the kernel path
        use_flash = flash_shape_ok and on_flash_backend and one_device
        if cfg.dsa_topk:
            attn = self._sparse(x, q, k, v, positions, attn_mask, window, sp_sz,
                                use_flash)
        elif cfg.block_diffusion_:
            attn = self._block_diffusion(q, k, v, attn_mask, window, sp_sz,
                                         use_flash)
        elif use_flash:
            # the Pallas kernel handles local (sliding-window) attention
            # natively, skipping out-of-window blocks
            attn = flash_attention(q, k, v, causal=True, scale=cfg.attn_scale,
                                   window=window,
                                   softcap=cfg.attn_logit_softcapping,
                                   interpret=interpret_kernels())
        else:
            mask = None
            if attn_mask is not None:
                # [b, s] key padding mask -> [b, 1, 1, s]
                mask = attn_mask[:, None, None, :].astype(bool)
            if window is not None:
                # Mistral/GPT-Neo local attention: drop keys older than the
                # window (the causal side is handled by is_causal)
                keep = (positions[:, None, :, None] - positions[:, None, None, :]
                        < window)
                mask = keep if mask is None else (mask & keep)
            bias = None
            if cfg.pos_embedding == "alibi":
                # BLOOM: logits += slope_h * (key_pos - query_pos); future
                # positions are cut by the causal mask
                slopes = jnp.asarray(alibi_slopes(nq))
                dist = (positions[:, None, None, :]
                        - positions[:, None, :, None]).astype(jnp.float32)
                bias = slopes[None, :, None, None] * dist

            def _core_attn(q, k, v):
                if cfg.attn_logit_softcapping is not None:
                    # Gemma-2: scores -> cap*tanh(scores/cap) BEFORE masking;
                    # tanh is not expressible as an additive bias, so this
                    # path computes dense attention by hand — grouped over
                    # KV heads (no materialized GQA repeat)
                    cap = jnp.float32(cfg.attn_logit_softcapping)
                    kvh = k.shape[2]
                    g = q.shape[2] // kvh
                    scl = (cfg.attn_scale if cfg.attn_scale is not None
                           else 1.0 / float(np.sqrt(hd)))
                    qg = q.reshape(b, s, kvh, g, hd).astype(jnp.float32)
                    from ..ops.attention import softcap_scores
                    scores = jnp.einsum("bqkgd,blkd->bkgql", qg,
                                        k.astype(jnp.float32)) * jnp.float32(scl)
                    scores = softcap_scores(scores, cap)
                    causal = (positions[:, :, None]
                              >= positions[:, None, :])[:, None, None]
                    keep_all = causal if mask is None \
                        else (causal & mask[:, :, None])
                    scores = jnp.where(keep_all, scores, -1e30)
                    probs = jax.nn.softmax(scores, axis=-1)
                    out = jnp.einsum("bkgql,blkd->bqkgd", probs,
                                     v.astype(jnp.float32))
                    return out.reshape(b, s, q.shape[2], hd).astype(q.dtype)
                return jax.nn.dot_product_attention(q, k, v, bias=bias, mask=mask,
                                                    is_causal=True,
                                                    scale=cfg.attn_scale)

            attn = None
            if not one_device and flash_shape_ok and on_flash_backend:
                # flash-inside-shard_map: data axes = each device's own
                # rows, seq axis = Ulysses all-to-alls (the 32k-seq
                # memory-safe path), model axis = per-head-block kernel
                from ..sequence.layer import ulysses_flash
                attn = ulysses_flash(
                    q, k, v, window=window, scale=cfg.attn_scale,
                    softcap=cfg.attn_logit_softcapping,
                    interpret=interpret_kernels())
            if attn is None and sp_sz > 1:
                # GSPMD Ulysses: sharding constraints make XLA emit the
                # all-to-all pair around full-sequence attention
                from ..sequence.layer import ulysses_spmd
                attn = ulysses_spmd(_core_attn, q, k, v)
            if attn is None:
                attn = _core_attn(q, k, v)
        out = attn.reshape(b, s, nq * hd)
        if gated:
            with jax.named_scope("ds.attn.gate"):
                open_ = jax.nn.sigmoid(gate.astype(jnp.float32))
                out = (out.astype(jnp.float32) * open_).astype(cfg.dtype)
            if sown.wanted(self, "attn"):
                sown.sow(self, "attn", {"gate_mean": jax.lax.stop_gradient(jnp.mean(open_))})
        return _dense(cfg.hidden_size, "o_proj", (HEADS, EMBED), cfg.dtype,
                      cfg.attention_out_bias, _keep_out(cfg, nq * hd))(out)


    def _differential(self, x, attn_mask, window, shared, hand_on):
        """Differential attention (Ye et al., arXiv:2410.05258, as
        Phi-4-mini-flash has it): the heads in adjacent pairs, query pair
        ``p`` reading key/value pair ``g = p // group``:

            A1 = softmax(q_{2p} k_{2g}^T * scale + m),  A2 = softmax(q_{2p+1} k_{2g+1}^T * scale + m)
            o_p = (1 - l_init) * RMSNorm((A1 - l * A2) [v_{2g} | v_{2g+1}]) * subln
            l = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2) + l_init
            l_init = 0.8 - 0.6 * exp(-0.3 * published layer index)

        ``m`` the causal mask and the layer's window, no position embedding.
        ``(A1 - l A2) V = A1 V - l A2 V``: with the query heads ordered ``[even
        | odd]``, the key heads likewise and the paired values once for each
        half, both products are ONE call of the two-width attention (q and k
        ``head_dim``, v ``2 * head_dim``; the ``mla_*`` kernels on one TPU
        device, XLA's attention anywhere else: correct and unmeasured), the
        first half of its heads ``A1 V`` and the second ``A2 V``. ``shared``:
        the ``(k, v)`` of an earlier layer, ``[b, s, kv heads, head_dim]``
        as its projections gave them; the layer then has ``q_proj`` and
        ``o_proj`` alone. ``hand_on``: also return this layer's ``(k, v)``.
        Sows ``diffattn_stats`` (only when mutable): ``lambda_mean``, the
        layer's ``l``."""
        cfg = self.config
        b, s, _ = x.shape
        hd, nq, nkv = cfg.head_dim_, cfg.num_attention_heads, cfg.num_key_value_heads
        if (attn_mask is not None or cfg.pos_embedding != "none" or cfg.qk_norm
                or cfg.dsa_topk or cfg.block_diffusion_ or cfg.clip_qkv is not None
                or cfg.attn_logit_softcapping is not None or nq % 2 or nkv % 2
                or (nq // 2) % (nkv // 2)):
            raise ValueError(
                "differential attention is causal attention over pairs of heads "
                "without a position embedding: no padding mask, q/k norm, clamp, "
                "softcapping, learned sparsity or other objective, and an even "
                "number of query and of key heads")
        q = _dense(nq * hd, "q_proj", (EMBED, HEADS), cfg.dtype, cfg.attention_bias,
                   remat.MIXER_IN)(x).reshape(b, s, nq, hd)
        if shared is None:
            k, v = (_dense(nkv * hd, name, (EMBED, KV), cfg.dtype, cfg.attention_bias,
                           remat.MIXER_IN)(x).reshape(b, s, nkv, hd)
                    for name in ("k_proj", "v_proj"))
            if hand_on:     # the layers after read them: kept once, whatever the plan
                k, v = (remat.handed_on(a, remat.SHARED_KV) for a in (k, v))
        else:
            k, v = shared

        def lam(name):
            return self.param(name, nn.with_partitioning(
                nn.initializers.normal(0.1), (None, )), (hd, ), jnp.float32)

        lq1, lk1, lq2, lk2 = (lam("lambda_" + n) for n in ("q1", "k1", "q2", "k2"))
        subln = self.param("subln", nn.with_partitioning(nn.initializers.ones, (None, )),
                           (2 * hd, ), jnp.float32)
        l_init = 0.8 - 0.6 * float(np.exp(-0.3 * (self.layer_idx + cfg.layer_index_offset)))
        # every scope closes before the kernel's call below: one that held it
        # would rename the instruction (docs/observability.md)
        with jax.named_scope("ds.diffattn.combine"):
            by_parity = lambda a, n: (a.reshape(b, s, n // 2, 2, hd)      # noqa: E731
                                      .transpose(0, 1, 3, 2, 4).reshape(b, s, n, hd))
            paired = v.reshape(b, s, nkv // 2, 2 * hd)
            qs, ks = by_parity(q, nq), by_parity(k, nkv)
            vs = jnp.concatenate([paired, paired], axis=2)
        scale = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / float(np.sqrt(hd))
        attn = _causal_attention_at_two_widths(cfg, qs, ks, vs, scale, window)
        with jax.named_scope("ds.diffattn.combine"):
            f32 = jnp.float32       # the parameters may arrive in the compute dtype
            lam_full = (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
                        - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32))) + l_init)
            o = (attn[:, :, :nq // 2].astype(f32)
                 - lam_full * attn[:, :, nq // 2:].astype(f32))
            var = jnp.mean(o * o, axis=-1, keepdims=True)
            o = (o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * subln.astype(f32)
                 * (1.0 - l_init))
            o = o.astype(cfg.dtype).reshape(b, s, nq * hd)
        if sown.wanted(self, "diffattn"):
            sown.sow(self, "diffattn", {"lambda_mean": jax.lax.stop_gradient(lam_full)})
        out = _dense(cfg.hidden_size, "o_proj", (HEADS, EMBED), cfg.dtype,
                     cfg.attention_out_bias, _keep_out(cfg, nq * hd))(o)
        return (out, (k, v)) if hand_on else out

    def _sparse(self, x, q, k, v, positions, attn_mask, window, sp_sz, use_kernel):
        """Learned sparse attention: the indexer (``indexer_q_proj``,
        ``indexer_k_proj`` under ``indexer_k_norm``, ``indexer_weights_proj``,
        all from the layer's normed input under ``stop_gradient``; rotary
        over the indexer's whole width) and ``ops/dsa_attention.py``'s
        call: the ``dsa_*`` kernels on one TPU device, else the dense form
        in blocks of queries. Sows ``dsa_stats`` (only when mutable):
        ``chosen_pairs``, ``causal_pairs``, ``kth_score_mean`` (the mean
        of each row's smallest chosen score) and ``masks_kept`` (1 where the
        layer's backward reads the forward's mask, ``ops/remat.py::DSA_MASK``,
        0 where it makes it again), and ``dsa_choice`` (the same):
        the indexer's operands and each row's smallest chosen score."""
        from ..ops.dsa_attention import dsa_attention
        cfg = self.config
        b, s, _ = x.shape
        hi, di = cfg.dsa_index_heads, cfg.dsa_index_head_dim
        if not (hi and di):
            raise ValueError("dsa_topk needs dsa_index_heads and dsa_index_head_dim")
        if (attn_mask is not None or window is not None or sp_sz > 1
                or cfg.pos_embedding != "rope" or cfg.block_diffusion_
                or cfg.attn_logit_softcapping is not None):
            raise ValueError(
                "learned sparse attention is causal attention with rotary "
                "positions: no padding mask, window, softcapping, other "
                "objective or position form, and no 'seq' mesh axis")
        # every scope closes before the kernels' call below: one that held
        # it would rename the instruction (docs/observability.md)
        with jax.named_scope("ds.dsa.index"):
            xi = jax.lax.stop_gradient(x)
            qi = _dense(hi * di, "indexer_q_proj", (EMBED, None), cfg.dtype)(xi)
            ki = _dense(di, "indexer_k_proj", (EMBED, None), cfg.dtype)(xi)
            ki = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                              name="indexer_k_norm")(ki)
            w = _dense(hi, "indexer_weights_proj", (EMBED, None), cfg.dtype)(xi)
            w = w.astype(jnp.float32) * float(hi**-0.5 * di**-0.5)
            qi = rope_at(qi.reshape(b, s, hi, di), positions, cfg.rope_theta)
            ki = rope_at(ki[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        # the kernels' mask across this layer's recomputation: handed to the
        # backward where the plan keeps it, else made again there
        keep_mask = remat.keeps(remat.DSA_MASK)
        attn, chosen, kth = dsa_attention(
            q, k, v, qi, ki, w, cfg.dsa_topk, scale=cfg.attn_scale,
            force_pallas=use_kernel, interpret=use_kernel and interpret_kernels(),
            keep_mask=keep_mask)
        if sown.wanted(self, "dsa"):
            sown.sow(self, "dsa", {
                "chosen_pairs": chosen.sum(dtype=jnp.int32),
                "causal_pairs": jnp.float32(b * s * (s + 1) / 2),
                "kth_score_mean": jax.lax.stop_gradient(kth).mean(),
                # the dense form has no mask to keep
                "masks_kept": jnp.int32(bool(use_kernel) and keep_mask)})
        if self.is_mutable_collection("dsa_choice"):
            # what ``ops.dsa_attention.chosen_keys`` rebuilds the choice of any
            # query from (a check's, not a step's)
            for name, value in (("qi", qi), ("ki", ki), ("w", w), ("kth", kth)):
                self.sow("dsa_choice", name, value)
        return attn

    def _block_diffusion(self, q, k, v, attn_mask, window, sp_sz, use_kernel):
        """Attention over ``xt ⊕ x0`` under the block-diffusion mask: the
        ``bdattn_*`` kernels on one TPU device, else XLA's attention under
        the same mask built whole ([2L, 2L]: sizes a test or a data-parallel
        mesh of short sequences holds)."""
        from ..ops.attention import (block_diffusion_attention,
                                     block_diffusion_mask)
        cfg = self.config
        s, blk = q.shape[1], cfg.diffusion_block_length
        if (attn_mask is not None or window is not None
                or cfg.pos_embedding == "alibi"
                or cfg.attn_logit_softcapping is not None):
            raise ValueError(
                "the block-diffusion objective takes no padding mask, sliding "
                "window, ALiBi or score softcapping: its mask is the pattern")
        if s % 2 or (s // 2) % blk:
            raise ValueError(
                f"block diffusion runs on xt ⊕ x0, 2 * L positions with L a "
                f"multiple of the block length {blk}: got {s}")
        if sp_sz > 1:
            raise ValueError(
                "block-diffusion attention is not built for a mesh with a "
                "'seq' axis: the Ulysses exchange splits the sequence the "
                "pattern is defined on")
        if use_kernel:
            return block_diffusion_attention(q, k, v, blk, scale=cfg.attn_scale,
                                             interpret=interpret_kernels())
        mask = jnp.asarray(block_diffusion_mask(s // 2, blk))[None, None]
        return jax.nn.dot_product_attention(q, k, v, mask=mask, is_causal=False,
                                            scale=cfg.attn_scale)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3, HF ``modeling_deepseek.
    py``), the operator of a ``"latent"`` layer, in its training form:

        q = W_q x                      [heads, nope + rope]; rope part rotated
        [c | k_r] = W_kva x            c: kv_lora_rank; k_r: ONE rope key a token
        [k_nope | v] = W_kvb rms(c)    [heads, nope + v]
        k = [k_nope | rope(k_r)]       k_r broadcast over the heads
        out = W_o softmax_causal(q k^T / sqrt(nope + rope)) v

    q and k are ``nope + rope`` = ``head_dim`` wide (``rope`` is the config's
    ``rotary_dim``) and v ``v_head_dim``: on one TPU device
    the kernels are ``ops/attention.py``'s at two widths (``mla_fwd`` /
    ``mla_bwd``), with no operand padded; anywhere else (a CPU, a mesh of
    more than one device) XLA's attention with the scores by hand, correct
    and unmeasured. Nothing is absorbed into the projections: that is
    decode's form. The gradient of ``k_r`` is the sum over the heads (the
    broadcast's transpose), that of ``c`` goes through ``kv_a_layernorm``.
    Sows ``mla_stats`` (only when mutable): the rms of ``c`` before its norm
    and of ``k_r`` before the rotary embedding."""
    config: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, cos, sin, positions, attn_mask=None):
        cfg = self.config
        b, s, _ = x.shape
        nh, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        d_rope, d_v = cfg.rotary_dim or 0, cfg.v_head_dim
        d_nope = cfg.head_dim_ - d_rope
        if not (rank and d_v and 0 < d_rope < cfg.head_dim_):
            raise ValueError("the latent operator needs kv_lora_rank, v_head_dim "
                             "and a rotary_dim short of head_dim")
        if (attn_mask is not None or _layer_window(cfg, self.layer_idx) is not None
                or cfg.pos_embedding != "rope" or cfg.block_diffusion_
                or cfg.attn_logit_softcapping is not None):
            raise ValueError(
                "the latent operator is causal attention with a rotary key: "
                "no padding mask, window, softcapping or other position form")
        q = _dense(nh * (d_nope + d_rope), "q_proj", (EMBED, HEADS), cfg.dtype,
                   keep=remat.MIXER_IN)(x)
        kva = _dense(rank + d_rope, "kv_a_proj_with_mqa", (EMBED, None), cfg.dtype,
                     keep=remat.MIXER_IN)(x)
        # every scope closes before the kernel's call below: one that held
        # it would rename the instruction (docs/observability.md)
        with jax.named_scope("ds.mla.assemble"):
            q = q.reshape(b, s, nh, d_nope + d_rope)
            q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
            c, k_r = kva[..., :rank], kva[..., rank:].reshape(b, s, 1, d_rope)
        if sown.wanted(self, "mla"):
            def rms(a):
                a = jax.lax.stop_gradient(a).astype(jnp.float32)
                return jnp.sqrt(jnp.mean(a * a))
            sown.sow(self, "mla", {"latent_rms": rms(c), "k_rope_rms": rms(k_r)})
        c = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="kv_a_layernorm")(c)
        kvb = _dense(nh * (d_nope + d_v), "kv_b_proj", (None, HEADS), cfg.dtype,
                     keep=remat.MIXER_IN)(c)
        with jax.named_scope("ds.rope"):
            q_rope = apply_rope(q_rope, cos, sin, positions,
                                interleaved=cfg.rope_interleaved)
            k_r = apply_rope(k_r, cos, sin, positions,
                             interleaved=cfg.rope_interleaved)
        with jax.named_scope("ds.mla.assemble"):
            kvb = kvb.reshape(b, s, nh, d_nope + d_v)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [kvb[..., :d_nope], jnp.broadcast_to(k_r, (b, s, nh, d_rope))],
                axis=-1)
            v = kvb[..., d_nope:]

        scale = (cfg.attn_scale if cfg.attn_scale is not None
                 else 1.0 / float(np.sqrt(d_nope + d_rope)))
        attn = _causal_attention_at_two_widths(cfg, q, k, v, scale)
        if cfg.attn_output_gate == "head":
            gate = _dense(nh, "gate_proj", (EMBED, None), cfg.dtype)(x)
            with jax.named_scope("ds.mla.gate"):
                attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))[..., None]).astype(cfg.dtype)
        elif cfg.attn_output_gate:
            raise ValueError(f"unknown attn_output_gate {cfg.attn_output_gate!r}")
        return _dense(cfg.hidden_size, "o_proj", (HEADS, EMBED), cfg.dtype,
                      keep=_keep_out(cfg, nh * d_v))(attn.reshape(b, s, nh * d_v))


class ShortConvOperator(nn.Module):
    """LFM2's gated short convolution, the operator of a ``"conv"`` layer:
    ``in_proj`` to ``B | C | u``, ``y = C * conv(B * u)`` with
    ``conv_L_cache`` causal depthwise taps (``ops/short_conv.py``: one Pallas
    kernel forward, one backward), ``out_proj``. No activation, no bias."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.short_conv import short_conv
        cfg = self.config
        H = cfg.hidden_size
        bcx = _dense(3 * H, "in_proj", (EMBED, HIDDEN), cfg.dtype,
                     keep=remat.MIXER_IN)(x)
        taps = self.param(
            "conv_weight",
            nn.with_partitioning(nn.initializers.lecun_normal(in_axis=0,
                                                              out_axis=1),
                                 (None, HIDDEN)),
            (cfg.conv_L_cache, H), jnp.float32)
        # a raw pallas_call is not partitioned under GSPMD: as for flash,
        # the kernel runs where the mesh is one device
        one_device = all(n == 1 for n in _mesh_shape().values())
        y = remat.keep(short_conv(bcx, taps, use_kernel=on_tpu() and one_device,
                                  interpret=interpret_kernels()), remat.KERNEL_OUT)
        return _dense(H, "out_proj", (HIDDEN, EMBED), cfg.dtype,
                      keep=_keep_out(cfg, H))(y)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba-2's: ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1]."""
    lo, hi = np.log(1e-3), np.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _mamba_conv_params(module, cfg, width: int):
    """-> (taps ``[mamba_d_conv, width]``, bias ``[width]``) of a Mamba mixer's
    causal depthwise convolution, ``conv_weight`` and (under
    ``mamba_conv_bias``; zeros without) ``conv_bias`` of ``module``: the taps
    lecun-normal over the taps, the bias as torch's Conv1d draws it."""
    f32 = jnp.float32
    taps = module.param(
        "conv_weight",
        nn.with_partitioning(nn.initializers.lecun_normal(in_axis=0, out_axis=1),
                             (None, HIDDEN)),
        (cfg.mamba_d_conv, width), f32)
    if not cfg.mamba_conv_bias:
        return taps, jnp.zeros((width, ), f32)
    bound = cfg.mamba_d_conv ** -0.5     # torch's Conv1d: U(+-1/sqrt(fan in))
    return taps, module.param(
        "conv_bias",
        nn.with_partitioning(lambda key, shape, dtype=f32: jax.random.uniform(
            key, shape, dtype, -bound, bound), (HIDDEN, )),
        (width, ), f32)


class Mamba2Mixer(nn.Module):
    """Mamba-2, the operator of a ``"mamba"`` layer (HF
    ``GraniteMoeHybridMambaLayer``): ``z | xBC | dt = in_proj(u)``; ``xBC =
    silu(conv(xBC) + bias)`` (``mamba_d_conv`` causal depthwise taps,
    ``ops/short_conv.py::causal_conv``); ``x | B | C = xBC``, ``x`` as
    ``mamba_n_heads`` heads of ``mamba_d_head``; ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; the state-space scan with a state of
    ``mamba_d_head x mamba_d_state`` a head (``ops/ssd.py::ssd_scan``, in
    chunks of ``mamba_chunk_size``); ``RMSNorm(y * silu(z)) * w`` over all
    of ``y``; ``out_proj``. One group: ``B`` and ``C`` are shared by the
    heads. Sows ``ssm_stats``: the largest ``|S|`` and the mean ``dt``."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, u):
        from ..ops.short_conv import causal_conv
        from ..ops.ssd import ssd_scan
        cfg = self.config
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        if cfg.mamba_n_groups != 1:
            raise ValueError(f"mamba_n_groups={cfg.mamba_n_groups}: one group "
                             "(B and C shared by the heads) is what is built")
        inner, xbc_width = H * P, H * P + 2 * N
        b, s, _ = u.shape
        f32 = jnp.float32
        zxbcdt = _dense(inner + xbc_width + H, "in_proj", (EMBED, HIDDEN), cfg.dtype,
                        keep=remat.MIXER_IN)(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + xbc_width], axis=-1)
        taps, conv_bias = _mamba_conv_params(self, cfg, xbc_width)
        # raw pallas_calls are not partitioned under GSPMD: as for flash, the
        # kernels run where the mesh is one device
        kernels = on_tpu() and all(n == 1 for n in _mesh_shape().values())
        xbc = remat.keep(causal_conv(xbc, taps, conv_bias, use_kernel=kernels,
                                     interpret=interpret_kernels()), remat.KERNEL_OUT)
        x, B, C = jnp.split(xbc, [inner, inner + N], axis=-1)
        def per_head(name, init):
            return self.param(name, nn.with_partitioning(init, (HEADS, )), (H, ), f32)

        dt_bias = per_head("dt_bias", _dt_bias_init)
        a_log = per_head("A_log", lambda key, shape, dtype=f32: jnp.log(
            jnp.arange(1, shape[0] + 1, dtype=dtype)))
        d_skip = per_head("D", nn.initializers.ones)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        want_stats = sown.wanted(self, "ssm")
        y = ssd_scan(x.reshape(b, s, H, P), dt, -jnp.exp(a_log), B, C, d_skip,
                     cfg.mamba_chunk_size, use_kernel=kernels,
                     interpret=interpret_kernels(), with_state_absmax=want_stats)
        if want_stats:
            y, top = y
            sown.sow(self, "ssm", {"state_absmax": top,
                                   "dt_mean": jax.lax.stop_gradient(jnp.mean(dt))})
        gated = y.reshape(b, s, inner).astype(f32) * jax.nn.silu(z.astype(f32))
        weight = self.param("norm_weight",
                            nn.with_partitioning(nn.initializers.ones, (HIDDEN, )),
                            (inner, ), f32)
        var = jnp.mean(gated * gated, axis=-1, keepdims=True)
        y = (gated * jax.lax.rsqrt(var + cfg.rms_norm_eps) * weight).astype(cfg.dtype)
        return _dense(cfg.hidden_size, "out_proj", (HIDDEN, EMBED), cfg.dtype,
                      keep=_keep_out(cfg, inner))(y)


class KimiDeltaMixer(nn.Module):
    """Kimi Delta Attention, the operator of a ``"kda"`` layer (Kimi Linear,
    arXiv:2510.26692; flash-linear-attention's ``KimiDeltaAttention`` with the
    bounded gate): ``q_proj``, ``k_proj``, ``v_proj`` to ``num_attention_heads`` heads
    of ``kda_head_dim``, each through its own ``kda_d_conv`` causal depthwise
    taps without bias and SiLU (``ops/short_conv.py::causal_conv``); per head
    ``q = l2norm(q) / sqrt(d)``, ``k = l2norm(k)``; the decay of every key
    channel ``g = kda_gate_floor * sigmoid(exp(A_log_h) * (f_proj(x) +
    dt_bias))``, ``beta = sigmoid(b_proj(x))`` one a head; the delta-rule scan
    with a state of ``d x d`` a head, in chunks of ``kda_chunk_size``;
    ``RMSNorm_d(o) * o_norm * sigmoid(g_proj(x))`` head by head; ``o_proj``.
    Everything between the convolutions and ``o_proj`` is one call,
    ``ops/kda.py::kda_fused``: where the chunk kernels run (a TPU, a mesh of
    one device, heads of a multiple of 128) they make the norms, ``beta k``,
    ``beta v``, the gate and the gated output norm on the tiles they hold, and
    no pass over ``[tokens, heads * d]`` is XLA's; elsewhere XLA makes the
    norms around the recurrence (``ds.kda.norm``). Named ``self_attn`` by its
    layer, as the other sequence mixers' projections are read
    (``benchmark/scope_time.py``). Sows ``kda_stats`` (only when mutable): the
    largest ``|S|``, the mean decay ``exp(g)``, the mean ``beta`` and
    ``fused_rows`` (1.0 where the kernels made the rows' norms and products,
    0.0 where XLA did).

    ``GatedDeltaNetMixer`` is the same recurrence at a decay that is constant
    over a head's key channels, and shares with this mixer the chunk algebra's
    code (the triangular solve, the state passing, the unit rows, the matmul
    stages abreast: ``ops/kda.py``), the convolution kernel and the shape of
    its statistics. It does not share: the gate (here one a key channel under
    a floor, made in the kernels from ``f_proj``; there one a value head with
    no floor, made by XLA at ``[tokens, heads]``), the heads (here as many key
    heads as value heads), the output gate (here a sigmoid), the projections
    (here six, and three convolutions)."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.kda import GATE_FLOOR, grid_of, kda_fused
        from ..ops.short_conv import causal_conv
        cfg = self.config
        H, d = cfg.num_attention_heads, cfg.kda_head_dim
        if not GATE_FLOOR <= cfg.kda_gate_floor < 0:
            raise ValueError(f"kda_gate_floor={cfg.kda_gate_floor}: the chunked scan "
                             f"holds a gate in [{GATE_FLOOR}, 0)")
        inner = H * d
        b, s, _ = x.shape
        f32 = jnp.float32
        # raw pallas_calls are not partitioned under GSPMD: as for flash, the
        # kernels run where the mesh is one device
        kernels = on_tpu() and all(n == 1 for n in _mesh_shape().values())
        no_bias = jnp.zeros((inner, ), f32)

        def projected(name, keep=remat.MIXER_IN):
            return _dense(inner, name, (EMBED, HEADS), cfg.dtype, keep=keep)(x)

        def conv_silu(name):
            taps = self.param(
                name + "_conv_weight",
                nn.with_partitioning(nn.initializers.lecun_normal(in_axis=0, out_axis=1),
                                     (None, HEADS)),
                (cfg.kda_d_conv, inner), f32)
            y = causal_conv(projected(name + "_proj"), taps, no_bias, use_kernel=kernels,
                            interpret=interpret_kernels())
            return remat.keep(y, remat.KERNEL_OUT).reshape(b, s, H, d)

        q, k, v = conv_silu("q"), conv_silu("k"), conv_silu("v")
        decay_in = projected("f_proj")
        beta_in = _dense(H, "b_proj", (EMBED, None), cfg.dtype)(x)
        gate_in = projected("g_proj")
        a_log = self.param("A_log", nn.with_partitioning(
            lambda key, shape, dtype=f32: jnp.log(jax.random.uniform(
                key, shape, dtype, 1.0, 16.0)), (HEADS, )), (H, ), f32)
        dt_bias = self.param("dt_bias", nn.with_partitioning(_dt_bias_init, (HEADS, )),
                             (inner, ), f32)
        o_norm = self.param("o_norm", nn.with_partitioning(nn.initializers.ones, (None, )),
                            (d, ), f32)

        with jax.named_scope("ds.kda.gates"):
            rate = jnp.exp(a_log)
            beta = jax.nn.sigmoid(beta_in.astype(f32))
        want_stats = sown.wanted(self, "kda")
        use_kernel, interpret = kernels and d % 128 == 0, interpret_kernels() and d % 128 == 0
        # where the kernels run they make everything between the convolutions
        # and o_proj on the tiles they hold: the norms, beta k and beta v, the
        # gate g = floor * sigmoid(rate * (f_proj + dt_bias)) and its running
        # sum, the gated output norm (ops/kda.py); elsewhere XLA makes the
        # norms around the recurrence. Every scope closes before the kernels'
        # call: one that held it would rename the instruction
        # (docs/observability.md)
        y = kda_fused(q, k, v, decay_in.reshape(b, s, H, d), rate, dt_bias, beta,
                      gate_in.reshape(b, s, H, d), o_norm, cfg.kda_chunk_size,
                      eps=cfg.rms_norm_eps, floor=cfg.kda_gate_floor,
                      use_kernel=use_kernel, interpret=interpret,
                      with_stats=want_stats, keep=remat.keeps(remat.KDA_SCAN))
        if want_stats:
            y, stats = y
            stats["beta_mean"] = jax.lax.stop_gradient(jnp.mean(beta))
            if use_kernel or interpret:
                # how the kernels' grid was laid over this call: the heads a
                # grid step took and the steps a call (kernel_dispatch.choose_kda_heads)
                grid = grid_of(b, s, H, d, cfg.kda_chunk_size, jnp.dtype(v.dtype).itemsize)
                stats["head_block"], stats["grid_steps"] = (jnp.asarray(n, f32) for n in grid)
            sown.sow(self, "kda", stats)
        y = y.reshape(b, s, inner)
        return _dense(cfg.hidden_size, "o_proj", (HEADS, EMBED), cfg.dtype,
                      keep=_keep_out(cfg, inner))(y)


class GatedDeltaNetMixer(nn.Module):
    """Gated DeltaNet, the operator of a ``"gdn"`` layer (Yang et al.,
    arXiv:2412.06464; HF ``Qwen3NextGatedDeltaNet``): ONE projection
    ``in_proj_qkvz`` to ``q | k`` (``gdn_k_heads`` heads of ``gdn_k_head_dim``
    each) ``| v | z`` (``gdn_v_heads`` heads of ``gdn_v_head_dim`` each) and one
    ``in_proj_ba`` to ``b | a`` (a value head each); ``q | k | v`` through ONE
    ``gdn_d_conv``-tap causal depthwise convolution without bias and SiLU
    (``ops/short_conv.py::causal_conv``); ``beta = sigmoid(b)``, ``g =
    -exp(A_log_h) * softplus(a + dt_bias_h)``, one float32 a value head and
    token, made by XLA at ``[tokens, heads]`` and never wider; value head ``h``
    reads key head ``h // (gdn_v_heads / gdn_k_heads)`` (HF repeats q and k;
    nothing is repeated here); per head ``q = l2norm(q) / sqrt(d)``, ``k =
    l2norm(k)``; the delta-rule scan with a state of ``d_k x d_v`` a value
    head, in chunks of ``gdn_chunk_size``; ``RMSNorm_d(o) * norm_weight *
    silu(z)`` head by head (the weight as it is, not ``1 + w``); ``out_proj``.
    Everything between the convolution and ``out_proj`` is one call,
    ``ops/gdn.py::gdn_fused`` (``KimiDeltaMixer`` says what the two share).
    The columns of ``in_proj_qkvz`` and ``in_proj_ba`` are laid out kind by
    kind (all of q, then k, v, z; all of b, then a): the HF checkpoint's
    key-head-by-key-head order is ``Qwen3NextPolicy``'s to permute. Named
    ``self_attn`` by its layer. Sows ``gdn_stats`` (only when mutable): the
    largest ``|S|``, the mean decay ``exp(g)``, the mean ``beta`` and
    ``fused_rows``."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.gdn import gdn_fused, grid_of, log_decay
        from ..ops.short_conv import causal_conv
        cfg = self.config
        Hk, Hv, dk, dv = (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_head_dim,
                          cfg.gdn_v_head_dim)
        if not (Hk and Hv) or Hv % Hk:
            raise ValueError(f"the gdn operator needs gdn_k_heads and gdn_v_heads, the "
                             f"second a multiple of the first: got {Hk}, {Hv}")
        keys, values = Hk * dk, Hv * dv
        b, s, _ = x.shape
        f32 = jnp.float32
        # raw pallas_calls are not partitioned under GSPMD: as for flash, the
        # kernels run where the mesh is one device
        kernels = on_tpu() and all(n == 1 for n in _mesh_shape().values())
        qkvz = _dense(2 * keys + 2 * values, "in_proj_qkvz", (EMBED, HEADS), cfg.dtype,
                      keep=remat.MIXER_IN)(x)
        ba = _dense(2 * Hv, "in_proj_ba", (EMBED, None), cfg.dtype)(x)
        taps = self.param(
            "conv_weight",
            nn.with_partitioning(nn.initializers.lecun_normal(in_axis=0, out_axis=1),
                                 (None, HEADS)),
            (cfg.gdn_d_conv, 2 * keys + values), f32)
        with jax.named_scope("ds.gdn.split"):
            mixed, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
        qkv = remat.keep(
            causal_conv(mixed, taps, jnp.zeros((2 * keys + values, ), f32),
                        use_kernel=kernels, interpret=interpret_kernels()),
            remat.KERNEL_OUT)
        with jax.named_scope("ds.gdn.split"):
            q = qkv[..., :keys].reshape(b, s, Hk, dk)
            k = qkv[..., keys:2 * keys].reshape(b, s, Hk, dk)
            v = qkv[..., 2 * keys:].reshape(b, s, Hv, dv)
        a_log = self.param("A_log", nn.with_partitioning(
            lambda key, shape, dtype=f32: jnp.log(jax.random.uniform(
                key, shape, dtype, 1.0, 16.0)), (HEADS, )), (Hv, ), f32)
        dt_bias = self.param("dt_bias", nn.with_partitioning(_dt_bias_init, (HEADS, )),
                             (Hv, ), f32)
        norm_weight = self.param("norm_weight", nn.with_partitioning(
            nn.initializers.ones, (None, )), (dv, ), f32)
        with jax.named_scope("ds.gdn.gates"):
            beta = jax.nn.sigmoid(ba[..., :Hv].astype(f32))
            g = log_decay(ba[..., Hv:], a_log, dt_bias)
        want_stats = sown.wanted(self, "gdn")
        fits = dk == dv and dk % 128 == 0 and Hv <= 128
        use_kernel, interpret = kernels and fits, interpret_kernels() and fits
        # every scope closes before the kernels' call: one that held it would
        # rename the instruction (docs/observability.md)
        y = gdn_fused(q, k, v, g, beta, z.reshape(b, s, Hv, dv), norm_weight,
                      cfg.gdn_chunk_size, eps=cfg.rms_norm_eps, use_kernel=use_kernel,
                      interpret=interpret, with_stats=want_stats,
                      keep=remat.keeps(remat.GDN_SCAN))
        if want_stats:
            y, stats = y
            stats["beta_mean"] = jax.lax.stop_gradient(jnp.mean(beta))
            if use_kernel or interpret:
                grid = grid_of(b, s, Hk, Hv, dk, cfg.gdn_chunk_size,
                               jnp.dtype(v.dtype).itemsize)
                stats["head_block"], stats["grid_steps"] = (jnp.asarray(n, f32) for n in grid)
            sown.sow(self, "gdn", stats)
        return _dense(cfg.hidden_size, "out_proj", (HEADS, EMBED), cfg.dtype,
                      keep=_keep_out(cfg, values))(y.reshape(b, s, values))


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.mlp_type in ("swiglu", "geglu_tanh"):
            # gated MLP: silu gate (llama) or tanh-gelu gate (gemma)
            gate = _dense(cfg.intermediate_size, "gate_proj", (EMBED, HIDDEN), cfg.dtype,
                          keep=remat.FFN_IN)(x)
            up = _dense(cfg.intermediate_size, "up_proj", (EMBED, HIDDEN), cfg.dtype,
                        keep=remat.FFN_IN)(x)
            g = (nn.silu(gate) if cfg.mlp_type == "swiglu"
                 else nn.gelu(gate, approximate=True))
            return _dense(cfg.hidden_size, "down_proj", (HIDDEN, EMBED),
                          cfg.dtype)(g * up)
        # fc1/fc2 form: Falcon uses exact (erf) GELU, Phi HF "gelu_new" is
        # the tanh approximation, OPT is ReLU
        act = {"gelu_fc": lambda y: nn.gelu(y, approximate=False),
               "gelu_tanh_fc": lambda y: nn.gelu(y, approximate=True),
               "relu_fc": nn.relu}[cfg.mlp_type]
        h = _dense(cfg.intermediate_size, "fc1", (EMBED, HIDDEN), cfg.dtype,
                   cfg.mlp_bias, remat.FFN_IN)(x)
        return _dense(cfg.hidden_size, "fc2", (HIDDEN, EMBED), cfg.dtype,
                      cfg.mlp_bias)(act(h))


class LlamaMoEBlock(nn.Module):
    """Sparse MoE MLP (reference moe/sharded_moe.py gating +
    module_inject/containers mixtral): a router ``num_local_experts`` wide,
    top-k, SwiGLU experts ``intermediate_size`` wide. Scoring is a softmax
    over the experts (Mixtral, renormalized; OLMoE and Qwen2-MoE, not) or
    independent sigmoids (LFM2, DeepSeek-V3), there with a selection bias
    added for the top-k only, the chosen experts weighted by their unbiased
    scores, ``p / (sum p + moe_renorm_eps)`` and ``routed_scaling_factor``.
    A shared expert (``shared_expert_intermediate_size``) is one dense
    SwiGLU every token passes, added to the routed sum: under a sigmoid gate
    of the token (Qwen2-MoE) or as it is (DeepSeek-V2/V3,
    ``shared_expert_gated`` False); a share of the experts computes it whole,
    as every chip of an expert-parallel layer does.

    The experts whose matrices live in this block are all of the router's,
    or share ``moe_share_index`` of them (``moe_experts_held``): the block
    then routes over all, computes ``Σ p_e · expert_e(x)`` over the chosen
    experts it holds and adds nothing for the others: the partial result of
    one chip of an expert-parallel layer, without the exchange. No matrix
    of an absent expert exists. ``expert_counts`` (sown, ``moe_stats``) are
    over the router's width either way; a share also sows ``rows_held`` and
    ``share_fallback`` (``ops/grouped_matmul.py``).

    Compute is a megablocks-style grouped GEMM (``ops/grouped_matmul.py``:
    sort-by-expert → gather into expert order → ragged_dot → gather back by
    the inverse permutation and a weighted sum over each token's k rows) so
    per-token FLOPs ∝ top-k; ``moe_grouped=False`` keeps the
    dense-over-experts oracle (also the better layout when the 'expert'
    logical axis is sharded over a real mesh axis — EP uses moe/layer.py's
    all-to-all dispatch instead). Expert weights carry the 'expert' logical
    axis so EP sharding is a mesh rule like everything else."""
    config: LlamaConfig

    def _route(self, logits):
        """-> (scores the balance loss reads ``[..., E]``, combine weights
        ``[..., k]`` float32, chosen experts ``[..., k]``)."""
        cfg = self.config
        E, k = cfg.num_local_experts, cfg.num_experts_per_tok
        if cfg.moe_scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            w, idx = jax.lax.top_k(scores, k)
        elif cfg.moe_scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            if cfg.moe_selection_bias:
                bias = self.param("expert_bias", nn.with_partitioning(
                    nn.initializers.zeros, ("expert", )), (E, ), jnp.float32)
                # under the router's name (ops/remat.py). A top-k that also
                # gives the weights, as below, reads its OWN indices in its
                # backward, which no name reaches: it is made again whatever
                # is kept, and keeping its logits alone cost the SDAR cell
                # 4 ms a step (PERF.md §6, PR 41)
                biased = scores + jax.lax.stop_gradient(bias)
                if cfg.moe_n_group > 1:
                    biased = self._in_best_groups(biased)
                _, idx = jax.lax.top_k(biased, k)
                idx = remat.keep(idx, remat.ROUTE)
                w = remat.keep(jnp.take_along_axis(scores, idx, axis=-1), remat.ROUTE)
            else:
                w, idx = jax.lax.top_k(scores, k)
        else:
            raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
        if cfg.moe_n_group > 1 and not (cfg.moe_scoring == "sigmoid"
                                        and cfg.moe_selection_bias):
            raise ValueError("expert groups (moe_n_group > 1) belong to the sigmoid "
                             "router with a selection bias")
        if cfg.moe_renormalize:  # Mixtral; Qwen2-MoE keeps raw softmax mass
            total = jnp.sum(w, -1, keepdims=True)
            w = w / (total + cfg.moe_renorm_eps if cfg.moe_renorm_eps else total)
        if cfg.routed_scaling_factor != 1.0:
            w = w * cfg.routed_scaling_factor
        return scores, w, idx

    def _in_best_groups(self, biased):
        """The biased scores ``[..., E]`` with every expert outside the
        ``moe_topk_group`` best of ``moe_n_group`` groups at -inf: a group is
        ``E / moe_n_group`` consecutive experts, its score the sum of its two
        largest biased scores. Sows ``group_counts`` (``moe_stats``): the
        tokens that kept each group."""
        cfg = self.config
        groups, best = cfg.moe_n_group, cfg.moe_topk_group
        if biased.shape[-1] % groups or not 0 < best <= groups:
            raise ValueError(f"{biased.shape[-1]} experts in {groups} groups, "
                             f"{best} of them kept")
        grouped = biased.reshape(*biased.shape[:-1], groups, -1)
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(score, best)
        is_kept = jnp.sum(jax.nn.one_hot(kept, groups, dtype=jnp.int32), axis=-2) > 0
        sown.sow(self, "moe", {"group_counts": jnp.sum(is_kept.reshape(-1, groups), axis=0,
                                                       dtype=jnp.int32)})
        return jnp.where(is_kept[..., None], grouped, -jnp.inf).reshape(biased.shape)

    @nn.compact
    def __call__(self, x):
        from ..ops.grouped_matmul import (expert_counts, moe_grouped_mlp,
                                          moe_grouped_mlp_share, moe_dense_mlp)
        cfg = self.config
        E, k = cfg.num_local_experts, cfg.num_experts_per_tok
        held = cfg.experts_held_
        first = cfg.moe_share_index * held
        if not 0 < held <= E or first + held > E:
            raise ValueError(f"experts {first}..{first + held} held of {E}")
        H, F = cfg.hidden_size, cfg.intermediate_size
        # the router's logits, choice and weights under its name where the
        # choice is a top-k of biased scores (``_route``): there a recomputed
        # layer that holds them runs no float32 matmul, top-k or gather
        biased = cfg.moe_scoring == "sigmoid" and cfg.moe_selection_bias
        logits = _dense(E, "gate", (EMBED, "expert"), jnp.float32,
                        keep=remat.ROUTE if biased else None)(x.astype(jnp.float32))
        with jax.named_scope("ds.moe.route"):
            probs, w, idx = self._route(logits)
            # (token, choice) assignments per expert: what the engine's fused
            # step returns beside the loss (the "moe" family, read only when mutable)
            counts = expert_counts(idx, E)
        sown.sow(self, "moe", {"expert_counts": counts})
        if cfg.router_aux_loss_coef > 0:
            # Switch/Mixtral load balance: E * sum_e(frac_routed_e * mean_prob_e)
            pe = probs.reshape(-1, E).mean(axis=0)
            fe = counts.astype(jnp.float32) / idx.size
            self.sow("aux_loss", "moe_load_balance",
                     cfg.router_aux_loss_coef * E * jnp.sum(fe * pe),
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: jnp.float32(0.0))
        w = w.astype(cfg.dtype)

        # each expert is a matrix of its own: the expert axis is a batch
        # axis of the initializer, not part of the fan-in (counted in, it
        # made the seeded block's output E times too small to see)
        lecun = nn.initializers.lecun_normal(batch_axis=(0, ))
        init = nn.with_partitioning(lecun, ("expert", EMBED, HIDDEN))
        w1 = _use_cast(self.param("w1", init, (held, H, F), jnp.float32), cfg.dtype)
        w3 = _use_cast(self.param("w3", init, (held, H, F), jnp.float32), cfg.dtype)
        w2 = _use_cast(self.param("w2",
                                  nn.with_partitioning(lecun,
                                                       ("expert", HIDDEN, EMBED)),
                                  (held, F, H), jnp.float32), cfg.dtype)

        lead = x.shape[:-1]
        xt = x.reshape(-1, H)
        idx, w = idx.reshape(-1, k), w.reshape(-1, k)
        if held < E:
            if not cfg.moe_grouped:
                raise ValueError("a share of the experts needs moe_grouped")
            # a share inside one routing group gets n_group / topk_group times
            # its even share of the tokens that keep its group, and every
            # token may: the static rows are reckoned from that (1 without
            # groups: the program as it was)
            inside = held * cfg.moe_n_group <= E
            out, rows_held, fell_back = moe_grouped_mlp_share(
                xt, w1, w3, w2, idx, w, first_expert=first, num_experts=E,
                crowding=cfg.moe_n_group // cfg.moe_topk_group if inside else 1)
            sown.sow(self, "moe", {"rows_held": rows_held, "share_fallback": fell_back})
        else:
            fn = moe_grouped_mlp if cfg.moe_grouped else moe_dense_mlp
            out = fn(xt, w1, w3, w2, idx, w)
        out = out.reshape(*lead, H)
        if cfg.shared_expert_intermediate_size:
            se_cfg = dataclasses.replace(
                cfg, intermediate_size=cfg.shared_expert_intermediate_size,
                num_local_experts=0)
            with jax.named_scope("ds.moe.shared"):
                shared = LlamaMLP(se_cfg, name="shared_expert")(x)
                if cfg.shared_expert_gated:  # Qwen2-MoE
                    g = _dense(1, "shared_expert_gate", (EMBED, HIDDEN), jnp.float32)(
                        x.astype(jnp.float32))
                    shared = jax.nn.sigmoid(g).astype(cfg.dtype) * shared
            out = out + shared
        return out


class LlamaDecoderLayer(nn.Module):
    """One decoder layer. ``shared``: what a layer that reads an earlier
    layer's keys and values or scan output is handed (``LlamaConfig.
    shared_sources``); a layer that later layers read returns ``(its output,
    what it hands on)``, every other layer its output alone."""
    config: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, cos, sin, positions, attn_mask=None, shared=None):
        cfg = self.config
        if cfg.layer_specs is not None:
            # this layer's own operator and FFN (LFM2's names and residual
            # form): r = x + op(operator_norm(x)); r + ffn(ffn_norm(r)),
            # each branch times residual_multiplier where there is one
            spec = cfg.layer_specs[self.layer_idx]

            def branch(out):
                if cfg.residual_multiplier is None:
                    return out
                # the product in float32, rounded once, as torch multiplies
                # a bf16 tensor by a Python scalar
                return (out.astype(jnp.float32)
                        * cfg.residual_multiplier).astype(out.dtype)

            normed = _make_norm(cfg, "operator_norm")(x)
            handed = None
            if spec.operator in ("mamba1", "gmu") or spec.differential:
                mixed, handed = self._sharing_mixer(spec, normed, attn_mask, shared)
                h = x + branch(mixed)
            elif spec.operator == "conv":
                h = x + branch(ShortConvOperator(cfg, name="conv")(normed))
            elif spec.operator == "mamba":
                h = x + branch(Mamba2Mixer(cfg, name="mamba")(normed))
            elif spec.operator == "kda":
                h = x + branch(KimiDeltaMixer(cfg, name="self_attn")(normed))
            elif spec.operator == "gdn":
                h = x + branch(GatedDeltaNetMixer(cfg, name="self_attn")(normed))
            elif spec.operator in ("attention", "latent"):
                op_cls = (LatentAttention if spec.operator == "latent"
                          else LlamaAttention)
                h = x + branch(op_cls(cfg, self.layer_idx, name="self_attn")(
                    normed, cos, sin, positions, attn_mask))
            else:
                raise ValueError(f"unknown operator {spec.operator!r}")
            ffn_cfg = dataclasses.replace(cfg, intermediate_size=spec.ffn_width)
            normed2 = _make_norm(cfg, "ffn_norm")(h)
            if spec.ffn == "moe":
                out = h + branch(LlamaMoEBlock(ffn_cfg, name="block_sparse_moe")(normed2))
            else:
                out = h + branch(LlamaMLP(ffn_cfg, name="mlp")(normed2))
            return out if handed is None else (out, handed)
        if cfg.sandwich_norm:
            # Gemma-2: pre AND post norms around both sublayers
            attn_out = LlamaAttention(cfg, self.layer_idx, name="self_attn")(
                _make_norm(cfg, "input_layernorm")(x), cos, sin, positions,
                attn_mask)
            h = x + _make_norm(cfg, "post_attention_layernorm")(attn_out)
            mlp_out = LlamaMLP(cfg, name="mlp")(
                _make_norm(cfg, "pre_feedforward_layernorm")(h))
            return h + _make_norm(cfg, "post_feedforward_layernorm")(mlp_out)
        if cfg.post_norm:
            # OLMo2: no input norms — the SUBLAYER OUTPUT is normalized
            attn_out = LlamaAttention(cfg, self.layer_idx, name="self_attn")(
                x, cos, sin, positions, attn_mask)
            h = x + _make_norm(cfg, "post_attention_layernorm")(attn_out)
            if cfg.num_local_experts > 0:
                mlp_out = LlamaMoEBlock(cfg, name="block_sparse_moe")(h)
            else:
                mlp_out = LlamaMLP(cfg, name="mlp")(h)
            return h + _make_norm(cfg, "post_feedforward_layernorm")(mlp_out)
        normed = _make_norm(cfg, "input_layernorm")(x)
        attn_out = LlamaAttention(cfg, self.layer_idx, name="self_attn")(
            normed, cos, sin, positions, attn_mask)
        if cfg.parallel_residual:
            # Falcon/Phi: one shared input norm feeds BOTH branches;
            # GPT-NeoX (norms=2): the MLP branch norms x independently
            if cfg.parallel_residual_norms == 2:
                normed = _make_norm(cfg, "post_attention_layernorm")(x)
            return x + attn_out + LlamaMLP(cfg, name="mlp")(normed)
        h = x + attn_out
        normed2 = _make_norm(cfg, "post_attention_layernorm")(h)
        if cfg.num_local_experts > 0:
            h = h + LlamaMoEBlock(cfg, name="block_sparse_moe")(normed2)
        else:
            h = h + LlamaMLP(cfg, name="mlp")(normed2)
        return h

    def _sharing_mixer(self, spec, normed, attn_mask, shared):
        """-> (the mixer's output, what the layer hands on or None) of the
        kinds that pass something between layers beside the residual stream:
        a differential "attention" layer (its own keys and values, or
        ``shared``'s), a "mamba1" layer, a "gmu" layer (``shared`` the scan
        output it gates)."""
        cfg = self.config
        sources = cfg.shared_sources()
        hands_on = self.layer_idx in sources
        if (sources[self.layer_idx] is None) != (shared is None):
            raise ValueError(f"layer {self.layer_idx} ({spec.operator!r}) reads layer "
                             f"{sources[self.layer_idx]} and was handed "
                             f"{'nothing' if shared is None else 'something'}")
        if spec.operator == "mamba1":
            out = SelectiveScanMixer(cfg, name="mamba")(normed, hands_on)
        elif spec.operator == "gmu":
            out = GatedMemoryUnit(cfg, name="mamba")(normed, shared)
        else:
            out = LlamaAttention(cfg, self.layer_idx, name="self_attn")(
                normed, None, None, None, attn_mask, shared, hands_on)
        return out if hands_on else (out, None)


class LMHead(nn.Module):
    """Unembed with bf16 MXU inputs but fp32 accumulation *and* output.

    Keeps the ``lm_head/kernel`` param path (HF conversion + AutoTP policies
    address it) while controlling the matmul output dtype, which ``nn.Dense``
    can't (its output dtype == compute dtype).
    """
    features: int
    dtype: Any
    use_bias: bool = False

    @nn.compact
    def __call__(self, x, return_params=False):
        kernel = self.param(
            "kernel",
            nn.with_partitioning(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
            (x.shape[-1], self.features))
        bias = self.param(
            "bias", nn.with_partitioning(nn.initializers.zeros, (VOCAB, )),
            (self.features, ), jnp.float32) if self.use_bias else None
        if return_params:  # chunked-CE path: same param tree, no matmul here
            return kernel, bias
        out = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if self.use_bias:
            out = out + bias
        return out


def _remat_layer_cls(cfg):
    """nn.remat with the configured jax.checkpoint_policies policy (selective
    remat — reference activation_checkpointing config's TPU analog). With no
    policy named the layer is recomputed but for the values it names among
    ``ops/remat.py::KEPT_NAMES``: what its attention kernel gave, so that the
    kernel's forward runs once a step, and the candidates of its row of
    ``_kept_plan`` (``remat.keeping`` around its call: the others carry
    another name); ``"nothing_saveable"`` keeps nothing."""
    if cfg.remat_policy:
        pol = getattr(jax.checkpoint_policies, cfg.remat_policy, None)
        if pol is None:
            raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")
        return nn.remat(LlamaDecoderLayer, policy=pol)
    return nn.remat(LlamaDecoderLayer, policy=remat.KEPT_POLICY)


def _kept_plan(cfg, x, cos, sin, positions, attn_mask):
    """-> the names each layer keeps under ``remat`` with no policy named
    (``ops/remat.py``: ``RESIDUAL_NAMES`` and as many of the candidates, in
    their order, as the chip has room for beside the step; ``RESIDUAL_NAMES``
    alone on a backend that reports no memory), or None where no name
    chooses: no recomputation, or a named policy. The prices are the layers'
    own named values, read off a trace of each kind of layer at ``x``'s
    shape. A looped stack (``total_ut_steps`` passes) is planned an
    APPLICATION of a layer at a time, pass by pass: row ``t * N + i`` is layer
    ``i`` in pass ``t``, since each holds its own input and kept values until
    its own backward."""
    if not cfg.remat or cfg.remat_policy:
        return None
    specs = cfg.layer_specs or (None, ) * cfg.num_hidden_layers
    passes = cfg.total_ut_steps

    def prices_of():
        abstract = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        by_kind = {}
        for i, spec in enumerate(specs):
            if spec not in by_kind:
                by_kind[spec] = remat.price_list(
                    LlamaDecoderLayer(cfg, i, parent=None).init, jax.random.PRNGKey(0),
                    abstract(x), cos, sin, abstract(positions), attn_mask,
                    *_shared_like(cfg, spec, x))
        return [by_kind[spec] + scan_price(spec) for spec in specs] * passes

    def scan_price(spec):
        # a scan kernel's output and states are named inside its forward
        # rule, which no trace of the layer's forward shows
        if spec is not None and spec.operator == "mamba1":
            from ..ops.selective_scan import scan_bytes
            return ((remat.SELSCAN_SCAN, scan_bytes(
                x.shape[0], x.shape[1], cfg.mamba1_d_inner, cfg.mamba_d_state,
                jnp.dtype(cfg.dtype).itemsize)), )
        if spec is not None and spec.operator == "gdn":
            from ..ops.gdn import scan_bytes
            return ((remat.GDN_SCAN, scan_bytes(
                x.shape[0], x.shape[1], cfg.gdn_v_heads, cfg.gdn_k_head_dim,
                cfg.gdn_v_head_dim, cfg.gdn_chunk_size,
                jnp.dtype(cfg.dtype).itemsize)), )
        if spec is None or spec.operator != "kda":
            return ()
        from ..ops.kda import scan_bytes
        return ((remat.KDA_SCAN, scan_bytes(
            x.shape[0], x.shape[1], cfg.num_attention_heads, cfg.kda_head_dim,
            cfg.kda_head_dim,
            cfg.kda_chunk_size, jnp.dtype(cfg.dtype).itemsize)), )

    tokens, itemsize = x.shape[0] * x.shape[1], jnp.dtype(cfg.dtype).itemsize
    attention = sum(spec is None or spec.operator in ("attention", "latent")
                    for spec in specs)
    a_kernels = tokens * cfg.num_attention_heads * (
        (cfg.v_head_dim or cfg.head_dim_) * itemsize + 4)    # output, log-sum-exp
    if cfg.dsa_topk:
        a_kernels += tokens * 2 * 4     # a row's threshold and tie bound
    always_kept, sources = attention * a_kernels, cfg.shared_sources()
    for i, spec in enumerate(specs):
        if spec is not None and spec.differential:   # its values are two heads wide
            always_kept += tokens * cfg.num_attention_heads * cfg.head_dim_ * itemsize
        if spec is not None and i in sources:        # what it hands on, once
            always_kept += sum(a.size * itemsize for a in jax.tree_util.tree_leaves(
                _shared_like(cfg, spec, x, source=True)))
    always_kept *= passes
    if cfg.looped_:     # each pass's normed stream, which the head reads
        always_kept += passes * x.size * itemsize
    plan = remat.plan_for(
        (repr(cfg), x.shape), prices_of, rows=x.shape[0],
        layer_input_bytes=x.size * itemsize, always_kept_bytes=always_kept,
        same_in_all_layers=cfg.scan_layers)
    return plan or (remat.RESIDUAL_NAMES, ) * (len(specs) * passes)


def _shared_like(cfg, spec, x, source: bool = False) -> tuple:
    """What a layer of ``spec`` is handed beside the stream ``x``, abstractly
    and as the trailing arguments of its call: () for a layer that reads
    nothing, else ``(keys and values, )`` or ``(memory, )``. ``source``: what
    such a layer hands ON instead."""
    if spec is None:
        return ()
    b, s = x.shape[:2]
    kv = jax.ShapeDtypeStruct((b, s, cfg.num_key_value_heads, cfg.head_dim_), cfg.dtype)
    memory = jax.ShapeDtypeStruct((b, s, cfg.mamba1_d_inner), cfg.dtype)
    if source:
        return ((kv, kv), ) if spec.operator == "attention" else (memory, )
    if spec.operator == "gmu":
        return (memory, )
    return ((kv, kv), ) if spec.kv_from >= 0 else ()


class _ScanBody(nn.Module):
    """nn.scan adapter: scan bodies must return (carry, out). With
    ``scan_chunk_size > 1`` one scan step applies a chunk of layers (the
    ZeRO-3 live-parameter governor's chunk)."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions, attn_mask=None):
        cfg = self.config
        layer_cls = _remat_layer_cls(cfg) if cfg.remat else LlamaDecoderLayer
        if cfg.scan_chunk_size <= 1:
            return layer_cls(cfg, name="layer")(x, cos, sin, positions, attn_mask), None
        for i in range(cfg.scan_chunk_size):
            x = layer_cls(cfg, name=f"layer_{i}")(x, cos, sin, positions, attn_mask)
        return x, None


class LlamaModel(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, attn_mask=None,
                 return_unembed=False, logits_to_keep=None, all_passes=False):
        """-> logits ``[B, S, V]``, or with ``return_unembed`` ``(x, w, b)``:
        the final norm's output and the raw head. A looped model
        (``LlamaConfig.looped_``) makes a stream a pass and hands back the
        last pass's, or with ``all_passes`` every pass's: logits ``[B, T, S,
        V]``, ``x`` ``[B, T, S, H]`` with the gates' pre-activations ``[B, T -
        1, S]`` float32 (None without ``exit_gate``) fourth. ``logits_to_keep``
        ``[n]``: the positions (the same in every row) whose logits are made."""
        cfg = self.config
        if positions is None:
            # block diffusion: both copies carry their tokens' own positions
            n = input_ids.shape[1] // (2 if cfg.block_diffusion_ else 1)
            positions = (jnp.arange(input_ids.shape[1]) % n)[None, :].astype(jnp.int32)
            positions = jnp.broadcast_to(positions, input_ids.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         embedding_init=nn.with_partitioning(nn.initializers.normal(0.02),
                                                             (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(input_ids)
        if cfg.embed_scale is not None:  # Gemma: sqrt(hidden) normalizer,
            # rounded through the compute dtype exactly as HF does
            x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        if cfg.embed_layernorm:  # BLOOM word_embeddings_layernorm
            x = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                             name="embed_layernorm")(x)
        if cfg.pos_embedding == "learned":
            # OPT-style learned positions (HF offsets the table by pos_offset)
            pos_table = nn.Embed(cfg.max_position_embeddings + cfg.pos_offset,
                                 cfg.hidden_size, dtype=cfg.dtype,
                                 embedding_init=nn.with_partitioning(
                                     nn.initializers.normal(0.02), (VOCAB, EMBED)),
                                 name="embed_positions")
            x = x + pos_table(positions + cfg.pos_offset)
        cos, sin = precompute_rope(cfg.rotary_dim or cfg.head_dim_,
                                   cfg.max_position_embeddings, cfg.rope_theta)
        # an init has no backward: nothing to plan (and no layer to price)
        kept = None if self.is_initializing() else _kept_plan(
            cfg, x, cos, sin, positions, attn_mask)

        if cfg.scan_layers:
            # scan over depth: O(1) HLO in layer count (the 70B compile path);
            # gathered-live params are hard-bounded to ONE scan step's chunk
            # (the ZeRO-3 max_live_parameters governor, zero_governor.py)
            if cfg.sliding_window_layers is not None:
                raise ValueError(
                    "scan_layers requires homogeneous layers; per-layer "
                    "sliding_window_layers patterns need scan_layers=False")
            if cfg.layer_specs is not None and len(set(cfg.layer_specs)) > 1:
                raise ValueError(
                    "scan_layers requires homogeneous layers; layer_specs "
                    f"of {len(set(cfg.layer_specs))} kinds need "
                    "scan_layers=False")
            if cfg.num_hidden_layers % cfg.scan_chunk_size != 0:
                raise ValueError(
                    f"num_hidden_layers={cfg.num_hidden_layers} not divisible "
                    f"by scan_chunk_size={cfg.scan_chunk_size}")
            # aux_loss rides the scan as a stacked per-step axis (the engine
            # sums all leaves, so stacking ≡ the unscanned reduce_fn sum)
            ScanLayer = nn.scan(_ScanBody,
                                variable_axes={"params": 0, "aux_loss": 0,
                                               **{family.collection: 0
                                                  for family in sown.FAMILIES.values()}},
                                split_rngs={"params": True},
                                in_axes=nn.broadcast,
                                length=cfg.num_hidden_layers // cfg.scan_chunk_size,
                                metadata_params={nn.PARTITION_NAME: "layers"})
            scanned = ScanLayer(cfg, name="layers")

            def stack(x, first):
                with remat.keeping(kept and kept[0]):     # one body: every layer's names
                    return scanned(x, cos, sin, positions, attn_mask)[0]
        else:
            layer_cls = _remat_layer_cls(cfg) if cfg.remat else LlamaDecoderLayer
            # what a layer hands to the layers after it beside the stream
            # (``shared_sources``): an argument of every layer that reads it,
            # so an input of its recomputation, its gradient summed over them
            sources = cfg.shared_sources()
            layers = [layer_cls(cfg, i, name=f"layers_{i}")
                      for i in range(cfg.num_hidden_layers)]

            def stack(x, first):
                handed = {}
                for i, layer in enumerate(layers):
                    reads = () if not sources or sources[i] is None else (handed[sources[i]], )
                    with remat.keeping(kept and kept[first + i]):
                        x = layer(x, cos, sin, positions, attn_mask, *reads)
                    if i in sources:
                        x, handed[i] = x
                return x

        norm = _make_norm(cfg, "norm")
        gates = None
        if cfg.looped_:
            x, gates = _passes(self, stack, norm, x)
        else:
            x = stack(x, 0)
            if cfg.block_diffusion_:
                # the clean copy carries no loss: only the noisy half is normed
                # and reaches the head
                x = x[:, :x.shape[1] // 2]
            x = norm(x)
        if logits_to_keep is not None:
            x = jnp.take(x, logits_to_keep, axis=-2)
        every = cfg.looped_ and all_passes
        if cfg.looped_ and not every:
            x = x[:, -1]
        if return_unembed:
            # chunked-CE path (ops/chunked_ce.py): hand back the raw unembed
            # weight [H, V] (+bias) instead of materialized logits; scale and
            # softcap are applied per chunk inside the op
            if cfg.tie_word_embeddings:
                w = embed.embedding.T
                return (x, w, None, gates) if every else (x, w, None)
            w, b = LMHead(cfg.vocab_size, cfg.dtype, use_bias=cfg.lm_head_bias,
                          name="lm_head")(x, return_params=True)
            return (x, w, b, gates) if every else (x, w, b)
        # unembed: bf16 inputs ride the MXU fast path (fp32 matmul is several×
        # slower), but the accumulator stays fp32 and the *output* is emitted
        # fp32 (preferred_element_type) — rounding logits to bf16 before the
        # CE logsumexp loses precision at large vocab sizes
        if cfg.tie_word_embeddings:
            logits = jax.lax.dot_general(
                x.astype(cfg.dtype), embed.embedding.astype(cfg.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            logits = LMHead(cfg.vocab_size, cfg.dtype, use_bias=cfg.lm_head_bias,
                            name="lm_head")(x)
        if cfg.logit_scale is not None:  # Cohere
            logits = logits * jnp.float32(cfg.logit_scale)
        if cfg.final_logit_softcapping is not None:  # Gemma-2
            cap = jnp.float32(cfg.final_logit_softcapping)
            logits = cap * jnp.tanh(logits / cap)
        return logits


def _passes(model, stack, norm, x):
    """A looped stack inside ``LlamaModel``: ``total_ut_steps`` passes over the
    same layers (``stack(x, first row of the plan)``), the ONE final norm
    after each, whose output the next pass reads and the head too -> (the
    normed streams ``[B, T, S, H]``, the exit gates' pre-activations ``[B, T
    - 1, S]`` float32 of every pass but the last, which is not read; None
    without ``exit_gate``)."""
    cfg = model.config
    if cfg.block_diffusion_:
        raise ValueError("a looped stack under block diffusion is not built")
    passes, streams, gates = cfg.total_ut_steps, [], []
    gate = None
    if cfg.exit_gate and passes > 1:
        gate = nn.Dense(1, dtype=jnp.float32, name="early_exit_gate", parent=model,
                        kernel_init=nn.initializers.normal(0.02))
    with sown.repeated(passes):
        for t in range(passes):
            with jax.named_scope(f"ds.loop.pass{t}"):
                x = norm(stack(x, t * cfg.num_hidden_layers))
                streams.append(x)
                if gate is not None and t < passes - 1:
                    with jax.named_scope("ds.loop.exit"):
                        gates.append(gate(x)[..., 0])
    return jnp.stack(streams, axis=1), jnp.stack(gates, axis=1) if gates else None


def cross_entropy_loss(logits, labels, ignore_index: int = -100, weights=None):
    """Token-mean CE with shift-by-one (causal LM). With ``weights`` [B, S]
    (float) nothing is shifted: position s predicts ``labels[s]``, weighted,
    and the sum is divided by all ``B * S`` positions (the same loss as
    ``ops.chunked_ce.chunked_cross_entropy_loss`` with ``weights``)."""
    if weights is not None:
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return ((logz - gold) * weights.astype(jnp.float32)).sum() / labels.size
    logits = logits[:, :-1].astype(jnp.float32)
    targets = labels[:, 1:]
    mask = (targets != ignore_index).astype(jnp.float32)
    targets = jnp.where(targets == ignore_index, 0, targets)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


class LlamaForCausalLM(nn.Module):
    """Engine-contract wrapper: returns scalar loss when labels given.

    Under ``objective == "block_diffusion"`` ``input_ids`` is ``xt ⊕ x0``
    [rows, 2L], ``positions`` its position ids, ``labels`` the targets
    ``x0`` [rows, L] and ``loss_weights`` [rows, L] the weight of each
    (1/t of its block where ``xt`` holds the mask id, else 0): the loss is
    ``sum(w * CE(logits_i, x0_i)) / (rows * L)``, unshifted; without labels
    the noisy half's logits [rows, L, V] come back."""
    config: LlamaConfig
    # what its operators sow for the host: all the training engine knows of
    # them (models/sown.py)
    sown_families = tuple(sown.FAMILIES.values())

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, attn_mask=None,
                 loss_weights=None, logits_to_keep=None, all_passes=False):
        cfg = self.config
        if labels is not None and (logits_to_keep is not None or all_passes):
            raise ValueError("logits_to_keep and all_passes are for a call without labels")
        if cfg.exit_loss_ and labels is not None:
            if loss_weights is not None:
                raise ValueError("the exit distribution's loss weighs each token "
                                 "itself: loss_weights are not its argument")
            return _exit_loss(self, input_ids, labels, positions, attn_mask)
        if cfg.block_diffusion_ and labels is not None:
            if loss_weights is None:
                raise ValueError("the block-diffusion loss needs loss_weights "
                                 "(data_pipeline.block_diffusion makes them)")
            masked = loss_weights > 0
            sown.sow(self, "diffusion", {
                "tokens": jnp.float32(labels.size),
                "masked_tokens": masked.sum().astype(jnp.float32),
                "t_sum": jnp.where(masked, 1.0 / jnp.where(masked, loss_weights, 1.0),
                                   0.0).sum().astype(jnp.float32)})
        elif loss_weights is not None:
            raise ValueError("loss_weights belong to the block-diffusion objective")
        if labels is not None and cfg.ce_chunk_size:
            from ..ops.chunked_ce import chunked_cross_entropy_loss
            x, w, b = LlamaModel(cfg, name="model")(input_ids, positions,
                                                    attn_mask,
                                                    return_unembed=True)
            # the head's matmuls run in here, not under `lm_head`
            with jax.named_scope("ds.head.loss"):
                return chunked_cross_entropy_loss(
                    x, w, b, labels, cfg.ce_chunk_size,
                    logit_scale=cfg.logit_scale,
                    softcap=cfg.final_logit_softcapping,
                    compute_dtype=cfg.dtype, weights=loss_weights)
        logits = LlamaModel(cfg, name="model")(input_ids, positions, attn_mask,
                                               logits_to_keep=logits_to_keep,
                                               all_passes=all_passes)
        if labels is None:
            return logits
        with jax.named_scope("ds.head.loss"):
            return cross_entropy_loss(logits, labels, weights=loss_weights)


def exit_distribution(gates):
    """``gates`` ``[B, T - 1, S]``, the exit gates' pre-activations of every
    pass but the last -> ``(p [B, T, S], H(p) [B, S])`` in float32: ``lambda_t
    = sigmoid(g_t)``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last
    pass taking what is left, ``prod_j (1 - lambda_j)``; ``H(p) = -sum_t p_t
    log p_t``. In logs: ``log p_t = log_sigmoid(g_t) + sum_{j<t}
    log_sigmoid(-g_j)``, so that no mass underflows to a ``0 * -inf``."""
    g = gates.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=1)       # log prod_{j<=t} (1 - lambda_j)
    before = jnp.pad(stay, ((0, 0), (1, 0), (0, 0)))        # ... prod_{j<t}
    log_p = before + jnp.pad(jax.nn.log_sigmoid(g), ((0, 0), (0, 1), (0, 0)))
    p = jnp.exp(log_p)
    return p, -(p * log_p).sum(axis=1)


def _exit_loss(module, input_ids, labels, positions, attn_mask):
    """The training loss of a looped model with an exit gate and
    ``exit_entropy_weight`` beta (``LlamaConfig.exit_loss_``), inside
    ``LlamaForCausalLM``: ``mean over the counted positions of [sum_t p_t CE_t
    - beta H(p)]``, ``p`` carrying a gradient through both terms; sows the
    ``loop`` family."""
    cfg = module.config
    x, w, b, gates = LlamaModel(cfg, name="model", parent=module)(
        input_ids, positions, attn_mask, return_unembed=True, all_passes=True)
    with jax.named_scope("ds.loop.exit"):
        p, entropy = exit_distribution(gates)
    with jax.named_scope("ds.head.loss"):
        if cfg.ce_chunk_size:
            from ..ops.chunked_ce import chunked_exit_cross_entropy
            loss, nll, counted = chunked_exit_cross_entropy(
                x, w, b, labels, p, cfg.ce_chunk_size, logit_scale=cfg.logit_scale,
                softcap=cfg.final_logit_softcapping, compute_dtype=cfg.dtype)
        else:
            loss, nll, counted = _dense_exit_cross_entropy(cfg, x, w, b, labels, p)
    with jax.named_scope("ds.loop.exit"):
        n = jnp.maximum(counted.sum(), 1.0)
        entropy = (entropy * counted).sum() / n
        if sown.wanted(module, "loop"):
            passes = lambda a: (a * counted[:, None]).sum(axis=(0, 2)) / n   # noqa: E731
            sown.sow(module, "loop", jax.lax.stop_gradient(
                {"exit_mass": passes(p), "ce": passes(nll), "exit_entropy": entropy}))
        return loss - jnp.float32(cfg.exit_entropy_weight) * entropy


def _dense_exit_cross_entropy(cfg, x, w, b, labels, weights, ignore_index: int = -100):
    """``ops.chunked_ce.chunked_exit_cross_entropy`` with the logits whole:
    the same three values."""
    logits = jax.lax.dot_general(x.astype(cfg.dtype), w.astype(cfg.dtype),
                                 (((3, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b
    if cfg.logit_scale is not None:
        logits = logits * jnp.float32(cfg.logit_scale)
    if cfg.final_logit_softcapping is not None:
        cap = jnp.float32(cfg.final_logit_softcapping)
        logits = cap * jnp.tanh(logits / cap)
    targets = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=ignore_index)
    counted = (targets != ignore_index).astype(jnp.float32)
    targets = jnp.where(targets == ignore_index, 0, targets)
    gold = jnp.take_along_axis(logits, targets[:, None, :, None], axis=-1)[..., 0]
    nll = (jax.nn.logsumexp(logits, axis=-1) - gold) * counted[:, None]
    loss = (nll * weights.astype(jnp.float32)).sum() / jnp.maximum(counted.sum(), 1.0)
    return loss, jax.lax.stop_gradient(nll), counted


def unbox_params(params):
    """Strip flax Partitioned metadata boxes → plain array pytree."""
    return jax.tree_util.tree_map(
        lambda x: x.unbox() if hasattr(x, "unbox") else x, params,
        is_leaf=lambda x: hasattr(x, "unbox"))


def logical_axis_tree(params):
    """Pytree of logical-axis tuples (or None) per leaf, for parallel/tp.py."""
    return jax.tree_util.tree_map(
        lambda x: tuple(x.names) if hasattr(x, "names") else None, params,
        is_leaf=lambda x: hasattr(x, "unbox"))


def init_llama(config: LlamaConfig, seed: int = 0, seq_len: int = 8,
               dtype=None):
    """Module + seeded parameters, fp32 on the default device. With
    ``dtype`` the init and the cast are one jitted program, so the tree is
    born at ``dtype``: the eager flax ``init`` leaves the whole fp32 tree on
    the device first — 15 GB at 16 Mistral-7B layers before a server casts
    it to bf16. (Eager stays the default: a fresh whole-init compile per
    call costs seconds, which small-model callers and the tests would pay
    hundreds of times.) A caller whose state is to be sharded runs the
    jitted form under ``jax.default_device(jax.devices("cpu")[0])`` and lets
    the engine place the shards, so that no chip ever holds the whole tree;
    jitted, the forward pass flax traces to shape the parameters is dead
    code, so it needs no kernel the host backend lacks."""
    model = LlamaForCausalLM(config)
    if config.block_diffusion_:     # xt ⊕ x0 of one block
        seq_len = 2 * config.diffusion_block_length
    ids = jnp.ones((1, seq_len), dtype=jnp.int32)

    def _init(key):
        return unbox_params(model.init(key, ids)["params"])

    key = jax.random.PRNGKey(seed)
    if dtype is None:
        return model, _init(key)
    return model, jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(dtype), _init(k)))(key)


class _StepSize(nn.Module):
    """Mamba-1's ``dt_proj`` and its softplus: ``softplus(W_dt delta + b_dt)``
    with the sum and the bias in float32 (the bias sits near ``-7`` where a
    step size is ``1e-3``: rounded to bf16 it would move the step size by
    percents). ``W_dt`` uniform within ``rank^-1/2``, ``b_dt`` so that the step
    sizes are log-uniform in [1e-3, 1e-1] (Mamba's own initialisation)."""
    features: int
    dtype: Any

    @nn.compact
    def __call__(self, delta):
        bound = delta.shape[-1] ** -0.5
        kernel = self.param("kernel", nn.with_partitioning(
            lambda key, shape, dtype=jnp.float32: jax.random.uniform(
                key, shape, dtype, -bound, bound), (None, HIDDEN)),
            (delta.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.with_partitioning(_dt_bias_init, (HIDDEN, )),
                          (self.features, ), jnp.float32)
        pre = jax.lax.dot_general(
            delta.astype(self.dtype), _use_cast(kernel, self.dtype),
            (((delta.ndim - 1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)
        return jax.nn.softplus(pre + bias)


class SelectiveScanMixer(nn.Module):
    """Mamba-1 (Gu & Dao, arXiv:2312.00752), the operator of a ``"mamba1"``
    layer: ``x | z = in_proj(u)`` (``mamba1_d_inner`` each); ``x = silu(conv(x)
    + bias)`` (``mamba_d_conv`` causal depthwise taps, ``ops/short_conv.py::
    causal_conv``); ``delta | B | C = x_proj(x)`` (``mamba1_dt_rank``,
    ``mamba_d_state``, ``mamba_d_state``); ``dt = softplus(dt_proj(delta))``,
    ``A = -exp(A_log)`` one rate a (channel, state); the selective scan with a
    state of ``mamba_d_state`` a channel (``ops/selective_scan.py``); ``out_proj(y
    * silu(z))``. ``hand_on``: also return ``y`` (the scan's output with its
    ``D`` term, before the gate): the memory a later ``"gmu"`` layer gates.
    Named ``mamba`` by its layer, as Mamba-2's mixer is. Sows ``selscan_stats``
    (only when mutable): the largest ``|h|`` and the mean ``dt``."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, u, hand_on=False):
        from ..ops.selective_scan import selective_scan
        from ..ops.short_conv import causal_conv
        cfg = self.config
        E, N, R = cfg.mamba1_d_inner, cfg.mamba_d_state, cfg.mamba1_dt_rank
        if not (E and R):
            raise ValueError("the mamba1 operator needs mamba1_d_inner and "
                             "mamba1_dt_rank")
        f32 = jnp.float32
        xz = _dense(2 * E, "in_proj", (EMBED, HIDDEN), cfg.dtype, keep=remat.MIXER_IN)(u)
        x, z = jnp.split(xz, 2, axis=-1)
        taps, conv_bias = _mamba_conv_params(self, cfg, E)
        # raw pallas_calls are not partitioned under GSPMD: as for flash, the
        # kernels run where the mesh is one device
        kernels = on_tpu() and all(n == 1 for n in _mesh_shape().values())
        x = remat.keep(causal_conv(x, taps, conv_bias, use_kernel=kernels,
                                   interpret=interpret_kernels()), remat.KERNEL_OUT)
        a_log = self.param("A_log", nn.with_partitioning(
            lambda key, shape, dtype=f32: jnp.broadcast_to(jnp.log(
                jnp.arange(1, shape[1] + 1, dtype=dtype)), shape), (HIDDEN, None)),
            (E, N), f32)
        d_skip = self.param("D", nn.with_partitioning(nn.initializers.ones, (HIDDEN, )),
                            (E, ), f32)
        # the scope closes before the kernels' call below: one that held it
        # would rename the instruction (docs/observability.md)
        with jax.named_scope("ds.selscan.dt"):
            dbc = _dense(R + 2 * N, "x_proj", (HIDDEN, None), cfg.dtype)(x)
            delta, B, C = jnp.split(dbc, [R, R + N], axis=-1)
            dt = _StepSize(E, cfg.dtype, name="dt_proj")(delta)
            rates = -jnp.exp(a_log.astype(f32))
        want_stats = sown.wanted(self, "selscan")
        y = selective_scan(x, dt, rates, B, C, d_skip, use_kernel=kernels,
                           interpret=interpret_kernels(), with_state_absmax=want_stats,
                           keep=remat.keeps(remat.SELSCAN_SCAN))
        if want_stats:
            y, top = y
            sown.sow(self, "selscan", {"state_absmax": top,
                                       "dt_mean": jax.lax.stop_gradient(jnp.mean(dt))})
        if hand_on:     # the layers after read it: kept once, whatever the plan
            y = remat.handed_on(y, remat.SHARED_MEMORY)
        gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
        out = _dense(cfg.hidden_size, "out_proj", (HIDDEN, EMBED), cfg.dtype,
                     keep=_keep_out(cfg, E))(gated)
        return (out, y) if hand_on else out


class GatedMemoryUnit(nn.Module):
    """The Gated Memory Unit of a ``"gmu"`` layer (SambaY, arXiv:2507.06607):
    ``out_proj(memory * silu(in_proj(u)))``, ``memory`` the scan output of an
    earlier ``"mamba1"`` layer (``mamba1_d_inner`` wide), no state and no
    token mixing of its own. Named ``mamba`` by its layer: in the family's
    checkpoints it is the Mamba module's ``in_proj`` and ``out_proj``."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, u, memory):
        cfg = self.config
        E = cfg.mamba1_d_inner
        if memory.shape[-1] != E:
            raise ValueError(f"a gmu layer gates a memory {E} wide: got {memory.shape}")
        gate = _dense(E, "in_proj", (EMBED, HIDDEN), cfg.dtype, keep=remat.MIXER_IN)(u)
        with jax.named_scope("ds.gmu.gate"):
            gated = (memory.astype(jnp.float32)
                     * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
        return _dense(cfg.hidden_size, "out_proj", (HIDDEN, EMBED), cfg.dtype,
                      keep=_keep_out(cfg, E))(gated)
