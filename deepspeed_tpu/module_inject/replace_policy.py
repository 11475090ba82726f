"""Per-architecture injection policies.

Reference: ``deepspeed/module_inject/replace_policy.py`` +
``containers/*`` (~20 archs): each policy knows an architecture's module
layout — which weights feed attention/MLP, which are column- vs row-parallel
— and maps HF modules onto the fused inference containers.

TPU equivalent: the "container" is the native flax Llama-family model
(``models/llama.py``) plus its paged-KV serving twin
(``inference/v2/model.py``); a policy here is (a) the HF→flax parameter name
map with layout fixups (torch Linear stores [out,in]; flax kernels are
[in,out]) and (b) the TP partition hints AutoTP consumes
(``parallel/tp.py``).
"""

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models.llama import LayerSpec, LlamaConfig


class HFCheckpointPolicy:
    """Base policy: llama-family weight map (LLaMA 2/3, Mistral, Qwen2 share
    the module graph; reference containers/llama.py, mistral, qwen2)."""

    arch: str = "llama"
    supports_bias: bool = False

    # AutoTP hints (reference policy.py container attrs)
    col_parallel = ["q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"]
    row_parallel = ["o_proj", "down_proj"]

    def config_from_hf(self, hf_config: Dict) -> LlamaConfig:
        """Map an HF config dict to LlamaConfig."""
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=hf_config.get("num_key_value_heads",
                                              hf_config["num_attention_heads"]),
            max_position_embeddings=hf_config.get("max_position_embeddings", 8192),
            rms_norm_eps=hf_config.get("rms_norm_eps", 1e-5),
            rope_theta=hf_config.get("rope_theta", 10000.0),
            tie_word_embeddings=hf_config.get("tie_word_embeddings", False),
        )

    def weight_map(self, layer: int, attention_bias: bool = False
                   ) -> Dict[str, Tuple[str, bool]]:
        """HF name -> (flax path under params['model'], transpose?)."""
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        out = {}
        if attention_bias:  # qwen2-style qkv biases (1-D: no transpose)
            for proj in ("q_proj", "k_proj", "v_proj"):
                out[p + f"self_attn.{proj}.bias"] = (f + f"self_attn/{proj}/bias", False)
        out.update({
            p + "self_attn.q_proj.weight": (f + "self_attn/q_proj/kernel", True),
            p + "self_attn.k_proj.weight": (f + "self_attn/k_proj/kernel", True),
            p + "self_attn.v_proj.weight": (f + "self_attn/v_proj/kernel", True),
            p + "self_attn.o_proj.weight": (f + "self_attn/o_proj/kernel", True),
            p + "mlp.gate_proj.weight": (f + "mlp/gate_proj/kernel", True),
            p + "mlp.up_proj.weight": (f + "mlp/up_proj/kernel", True),
            p + "mlp.down_proj.weight": (f + "mlp/down_proj/kernel", True),
            p + "input_layernorm.weight": (f + "input_layernorm/weight", False),
            p + "post_attention_layernorm.weight": (f + "post_attention_layernorm/weight",
                                                    False),
        })
        return out

    def global_map(self, tie_embeddings: bool) -> Dict[str, Tuple[str, bool]]:
        out = {
            "model.embed_tokens.weight": ("embed_tokens/embedding", False),
            "model.norm.weight": ("norm/weight", False),
        }
        if not tie_embeddings:
            out["lm_head.weight"] = ("lm_head/kernel", True)
        return out


class LlamaPolicy(HFCheckpointPolicy):
    arch = "llama"


class MistralPolicy(HFCheckpointPolicy):
    """Mistral: llama graph w/ sliding-window attention (reference
    containers/mistral)."""
    arch = "mistral"

    def config_from_hf(self, hf_config):
        cfg = super().config_from_hf(hf_config)
        import dataclasses
        return dataclasses.replace(cfg, sliding_window=hf_config.get("sliding_window"))


class Qwen2Policy(HFCheckpointPolicy):
    """Qwen2 adds attention qkv biases (reference containers/qwen2)."""
    arch = "qwen2"
    supports_bias = True

    def config_from_hf(self, hf_config):
        cfg = super().config_from_hf(hf_config)
        import dataclasses
        return dataclasses.replace(cfg, attention_bias=True)


class OlmoPolicy(HFCheckpointPolicy):
    """OLMo (AllenAI): llama module graph with NON-PARAMETRIC layernorm —
    no norm weights exist in the checkpoint — plus an optional q/k/v clamp
    (HF ``modeling_olmo.py`` OlmoLayerNorm / config.clip_qkv)."""
    arch = "olmo"

    def config_from_hf(self, hf_config):
        import dataclasses
        cfg = super().config_from_hf(hf_config)
        return dataclasses.replace(cfg, norm_type="layernorm_np",
                                   rms_norm_eps=1e-5,  # OlmoLayerNorm hardcodes
                                   clip_qkv=hf_config.get("clip_qkv"))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        return {k: v for k, v in out.items() if "layernorm" not in k}

    def global_map(self, tie_embeddings: bool):
        out = super().global_map(tie_embeddings)
        out.pop("model.norm.weight")  # non-parametric final norm
        return out


class Olmo2Policy(HFCheckpointPolicy):
    """OLMo2: parametric RMSNorm moved to the SUBLAYER OUTPUTS (post-norm:
    x + norm(attn(x)), x + norm(mlp(x))) plus RMSNorm on the flat q/k
    projections (HF ``modeling_olmo2.py`` Olmo2DecoderLayer/Olmo2Attention)."""
    arch = "olmo2"

    def config_from_hf(self, hf_config):
        import dataclasses
        cfg = super().config_from_hf(hf_config)
        return dataclasses.replace(cfg, qk_norm=True, post_norm=True)

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        out.pop(p + "input_layernorm.weight")  # no pre-norms in OLMo2
        out[p + "post_feedforward_layernorm.weight"] = \
            (f + "post_feedforward_layernorm/weight", False)
        out[p + "self_attn.q_norm.weight"] = (f + "self_attn/q_norm/weight", False)
        out[p + "self_attn.k_norm.weight"] = (f + "self_attn/k_norm/weight", False)
        return out


class CoherePolicy(HFCheckpointPolicy):
    """Cohere Command-R: weight-only layernorm, PARALLEL attn+mlp residual
    off ONE shared input norm, GPT-J-style interleaved rotary
    (repeat_interleave cos/sin), tied embeddings with ``logit_scale`` on the
    unembed (HF ``modeling_cohere.py`` — 'main diff from Llama')."""
    arch = "cohere"

    def config_from_hf(self, hf_config):
        import dataclasses
        if hf_config.get("use_qk_norm"):
            raise ValueError("cohere: use_qk_norm=True is not supported")
        cfg = super().config_from_hf(hf_config)
        return dataclasses.replace(
            cfg, norm_type="layernorm_nobias",
            rms_norm_eps=hf_config.get("layer_norm_eps", 1e-5),
            rope_interleaved=True,
            parallel_residual=True, parallel_residual_norms=1,
            tie_word_embeddings=hf_config.get("tie_word_embeddings", True),
            # HF CohereConfig default (NOT 1.0)
            logit_scale=hf_config.get("logit_scale", 0.0625))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        # one shared norm per layer; flax LayerNorm stores its weight as
        # "scale"
        out = {k: v for k, v in out.items()
               if "post_attention_layernorm" not in k}
        out[f"model.layers.{layer}.input_layernorm.weight"] = \
            (f"layers_{layer}/input_layernorm/scale", False)
        return out

    def global_map(self, tie_embeddings: bool):
        out = super().global_map(tie_embeddings)
        out["model.norm.weight"] = ("norm/scale", False)
        return out


class MixtralPolicy(HFCheckpointPolicy):
    """Mixtral: llama attention + sparse-MoE MLP (reference
    inference/v2/model_implementations/mixtral). Per-expert HF tensors are
    stacked into [E, ...] arrays — the layout the grouped einsum consumes."""
    arch = "mixtral"

    def config_from_hf(self, hf_config):
        cfg = super().config_from_hf(hf_config)
        import dataclasses
        return dataclasses.replace(
            cfg, num_local_experts=hf_config.get("num_local_experts", 8),
            num_experts_per_tok=hf_config.get("num_experts_per_tok", 2))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        # mixtral has no dense mlp — drop those entries
        return {k: v for k, v in out.items() if ".mlp." not in k}

    def moe_map(self, layer: int, num_experts: int):
        """HF names → (flax path, stacking) for the MoE block."""
        p = f"model.layers.{layer}.block_sparse_moe."
        f = f"layers_{layer}/block_sparse_moe/"
        gate = {p + "gate.weight": (f + "gate/kernel", True)}
        experts = {}
        for which, tr in (("w1", True), ("w2", True), ("w3", True)):
            experts[f + which] = [p + f"experts.{e}.{which}.weight" for e in range(num_experts)]
        return gate, experts


def _mlp_experts_map(layer: int, num_experts: int):
    """The ``mlp.gate`` / ``mlp.experts.E.{gate,up,down}_proj`` layout
    Qwen2-MoE and OLMoE share, onto the MoE block's stacked w1/w3/w2."""
    p = f"model.layers.{layer}.mlp."
    f = f"layers_{layer}/block_sparse_moe/"
    gate = {p + "gate.weight": (f + "gate/kernel", True)}
    experts = {}
    for hf_name, fx in (("gate_proj", "w1"), ("up_proj", "w3"),
                        ("down_proj", "w2")):
        experts[f + fx] = [p + f"experts.{e}.{hf_name}.weight"
                           for e in range(num_experts)]
    return gate, experts


class Qwen2MoePolicy(MixtralPolicy):
    """Qwen2-MoE (reference ``inference/v2/model_implementations/qwen_v2_moe``):
    qwen2 attention (qkv biases) + sparse MoE with NON-renormalized top-k
    and a sigmoid-gated shared expert."""
    arch = "qwen2_moe"
    supports_bias = True

    def config_from_hf(self, hf_config):
        if hf_config.get("mlp_only_layers") or hf_config.get("decoder_sparse_step", 1) != 1:
            raise ValueError("qwen2-moe variants mixing dense-MLP layers "
                             "(mlp_only_layers/decoder_sparse_step) are not supported")
        import dataclasses
        cfg = HFCheckpointPolicy.config_from_hf(self, hf_config)
        return dataclasses.replace(
            cfg,
            attention_bias=True,
            intermediate_size=hf_config["moe_intermediate_size"],
            num_local_experts=hf_config.get("num_experts", 60),
            num_experts_per_tok=hf_config.get("num_experts_per_tok", 4),
            moe_renormalize=bool(hf_config.get("norm_topk_prob", False)),
            shared_expert_intermediate_size=hf_config.get(
                "shared_expert_intermediate_size"))

    def moe_map(self, layer: int, num_experts: int):
        gate, experts = _mlp_experts_map(layer, num_experts)
        p = f"model.layers.{layer}.mlp."
        f = f"layers_{layer}/block_sparse_moe/"
        gate[p + "shared_expert_gate.weight"] = (f + "shared_expert_gate/kernel", True)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            gate[p + f"shared_expert.{proj}.weight"] = (
                f + f"shared_expert/{proj}/kernel", True)
        return gate, experts


class Qwen3NextPolicy(Qwen2MoePolicy):
    """Qwen3-Next (HF ``modeling_qwen3_next.py``, ``model_type: qwen3_next``):
    pre-norm layers, every RMSNorm zero-centred (``x / rms(x) * (1 + w)``) but
    the linear layers' output norm, whose mixer is, by ``layer_types`` or
    ``full_attention_interval`` (layer ``i`` is full attention where ``(i + 1)
    % interval == 0``), Gated DeltaNet (``GatedDeltaNetMixer``:
    ``linear_attn.in_proj_qkvz`` laid out key head by key head ``[q | k | v x
    r | z x r]``, ``in_proj_ba`` ``[b x r | a x r]`` alike, one ``conv1d`` over
    ``q | k | v``, ``A_log``, ``dt_bias``, ``norm``, ``out_proj``) or gated
    softmax attention (``q_proj`` twice as wide, a head's query and its gate
    side by side; per-head ``q_norm`` / ``k_norm``; rope on the first
    ``partial_rotary_factor`` of a head's lanes; ``o_proj(attn *
    sigmoid(gate))``); every FFN ``num_experts`` experts
    ``moe_intermediate_size`` wide, top-k of a softmax, renormalised
    (``norm_topk_prob``), beside a shared expert under a sigmoid gate of the
    token. The model holds the fused projections' columns kind by kind (all of
    q, then k, v, z; b, then a; the queries, then the gates): the special
    conversions permute them. A chip's share of the experts is the
    deployment's to set (``moe_experts_held``). Refused by name:
    ``mlp_only_layers``, a ``decoder_sparse_step`` other than 1, biases,
    ``rope_scaling``, another activation. The multi-token-prediction module is
    not built (the config has no key of it)."""
    arch = "qwen3_next"
    supports_bias = False
    row_parallel = ["o_proj", "down_proj", "out_proj"]
    col_parallel = HFCheckpointPolicy.col_parallel + ["in_proj_qkvz", "in_proj_ba"]

    def config_from_hf(self, hf_config):
        import dataclasses
        hf = dict(hf_config)
        refused = {"mlp_only_layers": bool(hf.get("mlp_only_layers")),
                   "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
                   "attention_bias": bool(hf.get("attention_bias")),
                   "rope_scaling": bool(hf.get("rope_scaling")),
                   "hidden_act": hf.get("hidden_act", "silu") != "silu"}
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"qwen3_next: {key}={hf.get(key)!r} is not supported")
        depth, every = hf["num_hidden_layers"], int(hf.get("full_attention_interval", 4))
        types = hf.get("layer_types") or [
            "full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(depth)]
        if len(types) != depth or set(types) - {"linear_attention", "full_attention"}:
            raise ValueError(f"qwen3_next: layer_types {types} for {depth} layers")
        width = hf["moe_intermediate_size"]
        head = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
        cfg = HFCheckpointPolicy.config_from_hf(
            self, {**hf, "intermediate_size": width,
                   "rms_norm_eps": hf.get("rms_norm_eps", 1e-6)})
        self.bind(dataclasses.replace(
            cfg, head_dim=head,
            rotary_dim=int(head * float(hf.get("partial_rotary_factor", 0.25))),
            qk_norm="head", norm_plus_one=True, attn_output_gate="elementwise",
            layer_specs=tuple(
                LayerSpec(operator="attention" if kind == "full_attention" else "gdn",
                          ffn="moe", ffn_width=width) for kind in types),
            num_local_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_renormalize=bool(hf.get("norm_topk_prob", True)),
            shared_expert_intermediate_size=hf.get("shared_expert_intermediate_size"),
            shared_expert_gated=True,
            gdn_k_heads=hf["linear_num_key_heads"],
            gdn_v_heads=hf["linear_num_value_heads"],
            gdn_k_head_dim=hf["linear_key_head_dim"],
            gdn_v_head_dim=hf["linear_value_head_dim"],
            gdn_d_conv=hf.get("linear_conv_kernel_dim", 4)))
        return self._cfg

    def bind(self, cfg: LlamaConfig):
        """As ``Lfm2MoePolicy.bind``: the name maps depend on the layer's kind."""
        self._cfg = cfg

    def weight_map(self, layer: int, attention_bias: bool = False):
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "input_layernorm.weight": (f + "operator_norm/weight", False),
               p + "post_attention_layernorm.weight": (f + "ffn_norm/weight", False)}
        if self._cfg.layer_specs[layer].operator == "gdn":
            m, fm = p + "linear_attn.", f + "self_attn/"
            out.update({m + "out_proj.weight": (fm + "out_proj/kernel", True),
                        m + "A_log": (fm + "A_log", False),
                        m + "dt_bias": (fm + "dt_bias", False),
                        m + "norm.weight": (fm + "norm_weight", False)})
        else:
            for proj in ("k_proj", "v_proj", "o_proj"):
                out[p + f"self_attn.{proj}.weight"] = (f + f"self_attn/{proj}/kernel", True)
            for norm in ("q_norm", "k_norm"):
                out[p + f"self_attn.{norm}.weight"] = (f + f"self_attn/{norm}/weight", False)
        return out

    def _special(self, layer: int):
        """HF name -> (our path, the HF row each of our columns is, or None
        for the convolution's taps)."""
        cfg = self._cfg
        p, f = f"model.layers.{layer}.", f"layers_{layer}/self_attn/"
        if cfg.layer_specs[layer].operator != "gdn":
            nq, hd = cfg.num_attention_heads, cfg.head_dim_
            rows = np.arange(nq * 2 * hd).reshape(nq, 2, hd)     # [head, query | gate, lane]
            return {p + "self_attn.q_proj.weight":
                    (f + "q_proj/kernel", rows.transpose(1, 0, 2).reshape(-1))}
        Hk, r = cfg.gdn_k_heads, cfg.gdn_v_heads // cfg.gdn_k_heads
        dk, dv = cfg.gdn_k_head_dim, cfg.gdn_v_head_dim
        by_head = np.arange(Hk * (2 * dk + 2 * r * dv)).reshape(Hk, -1)
        cuts = np.split(by_head, [dk, 2 * dk, 2 * dk + r * dv], axis=1)   # q, k, v, z
        ba = np.arange(Hk * 2 * r).reshape(Hk, 2, r)
        return {p + "linear_attn.in_proj_qkvz.weight":
                (f + "in_proj_qkvz/kernel", np.concatenate([c.reshape(-1) for c in cuts])),
                p + "linear_attn.in_proj_ba.weight":
                (f + "in_proj_ba/kernel", ba.transpose(1, 0, 2).reshape(-1)),
                p + "linear_attn.conv1d.weight": (f + "conv_weight", None)}

    def special_hf_names(self, layer: int):
        return list(self._special(layer))

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """A fused projection ``[out, hidden]`` in HF's head-by-head order ->
        our kernel ``[hidden, out]`` kind by kind; torch Conv1d's depthwise
        weight ``[C, 1, L]`` -> taps ``[L, C]`` (q | k | v, as HF convolves
        them)."""
        for hf_name, (path, rows) in self._special(layer).items():
            w = get_tensor(hf_name)
            put(path, w[:, 0, :].T if rows is None else w[rows].T)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        out = {}
        for hf_name, (path, rows) in self._special(layer).items():
            if rows is None:
                out[hf_name] = flat[path].T[:, None, :]
            else:
                out[hf_name] = np.empty_like(flat[path].T)
                out[hf_name][rows] = flat[path].T
        return out


class OlmoePolicy(MixtralPolicy):
    """OLMoE (HF ``modeling_olmoe.py``): PRE-norm llama layers whose
    attention RMS-normalizes the flat q/k projections (OLMo2's q_norm/k_norm
    without its post-norm residual) and whose every MLP is a sparse MoE of
    ``num_experts`` SwiGLU experts ``intermediate_size`` wide, top-k of a
    softmax router, NOT renormalized unless ``norm_topk_prob``; no shared
    expert, no biases."""
    arch = "olmoe"

    def config_from_hf(self, hf_config):
        import dataclasses
        if hf_config.get("attention_bias"):
            raise ValueError("olmoe: attention_bias=True (biases on q/k/v AND "
                             "o_proj) is not supported")
        cfg = HFCheckpointPolicy.config_from_hf(self, hf_config)
        return dataclasses.replace(
            cfg,
            clip_qkv=hf_config.get("clip_qkv"),
            qk_norm=True,
            num_local_experts=hf_config.get("num_experts", 64),
            num_experts_per_tok=hf_config.get("num_experts_per_tok", 8),
            moe_renormalize=bool(hf_config.get("norm_topk_prob", False)),
            # OlmoeConfig's class default; only training reads it
            router_aux_loss_coef=hf_config.get("router_aux_loss_coef", 0.01))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p = f"model.layers.{layer}.self_attn."
        f = f"layers_{layer}/self_attn/"
        out[p + "q_norm.weight"] = (f + "q_norm/weight", False)
        out[p + "k_norm.weight"] = (f + "k_norm/weight", False)
        return out

    def moe_map(self, layer: int, num_experts: int):
        return _mlp_experts_map(layer, num_experts)


class SdarMoePolicy(MixtralPolicy):
    """SDAR-MoE (JetLM ``modeling_sdar_moe.py``: Qwen3-MoE's blocks trained
    by block diffusion): PRE-norm llama layers, GQA attention without biases
    whose q and k are RMS-normalized over each head's ``head_dim`` (one
    weight a projection, ``q_norm`` / ``k_norm``) before the rotary
    embedding; every MLP a sparse MoE of ``num_experts`` SwiGLU experts
    ``moe_intermediate_size`` wide, top-k of a softmax router, renormalized
    where ``norm_topk_prob``; no shared expert, no router bias, untied head.
    The objective is block diffusion over blocks of ``block_length`` tokens
    (4, the released model's, where the config does not say). A chip's share
    of the experts and of the vocabulary is the deployment's to set
    (``moe_experts_held``, ``vocab_size``), not the checkpoint's."""
    arch = "sdar_moe"

    def config_from_hf(self, hf_config):
        import dataclasses
        if hf_config.get("mlp_only_layers") or hf_config.get("decoder_sparse_step", 1) != 1:
            raise ValueError("sdar_moe: variants mixing dense-MLP layers "
                             "(mlp_only_layers/decoder_sparse_step) are not supported")
        for key in ("attention_bias", "use_sliding_window", "rope_scaling"):
            if hf_config.get(key):
                raise ValueError(f"sdar_moe: {key}={hf_config[key]!r} is not supported")
        cfg = HFCheckpointPolicy.config_from_hf(self, hf_config)
        return dataclasses.replace(
            cfg,
            head_dim=hf_config.get("head_dim"),
            intermediate_size=hf_config["moe_intermediate_size"],
            qk_norm="head",
            num_local_experts=hf_config.get("num_experts", 128),
            num_experts_per_tok=hf_config.get("num_experts_per_tok", 8),
            moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
            router_aux_loss_coef=hf_config.get("router_aux_loss_coef", 0.0),
            objective="block_diffusion",
            diffusion_block_length=int(hf_config.get("block_length", 4)),
            diffusion_mask_id=hf_config.get("mask_token_id"))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p = f"model.layers.{layer}.self_attn."
        f = f"layers_{layer}/self_attn/"
        out[p + "q_norm.weight"] = (f + "q_norm/weight", False)
        out[p + "k_norm.weight"] = (f + "k_norm/weight", False)
        return out

    def moe_map(self, layer: int, num_experts: int):
        return _mlp_experts_map(layer, num_experts)


class KeyeVL2Policy(SdarMoePolicy):
    """Keye-VL-2.0's language model (``model_type: KeyeVL2``): Qwen3-MoE's
    blocks (``SdarMoePolicy``'s: per-head q/k RMSNorm, ``num_experts`` SwiGLU
    experts ``moe_intermediate_size`` wide, softmax top-k renormalized, no
    shared expert) under the causal objective, every attention layer a
    learned sparse attention: ``sa_config``'s indexer (``indexer_num_heads``
    heads ``indexer_head_dim`` wide, ONE key a token) scores every earlier
    token and each query attends its ``topk`` (``ops/dsa_attention.py``).
    ``rope_scaling`` may only be the default rotary with an ``mrope_section``:
    on token ids the three position components are equal, so M-RoPE is the
    one-dimensional rotary over the whole head. The vision tower is not
    built. The indexer's tensor names follow DeepSeek-V3.2's modeling code
    (``self_attn.indexer.{wq_b,wk,k_norm,weights_proj}``): assumed, the
    checkpoint is not here."""
    arch = "KeyeVL2"

    def config_from_hf(self, hf_config):
        import dataclasses
        rope = dict(hf_config.get("rope_scaling") or {})
        rope.pop("mrope_section", None)
        if any(v != "default" for v in rope.values()):
            raise ValueError(f"KeyeVL2: rope_scaling={hf_config['rope_scaling']!r} "
                             "is not supported (the default rotary with an "
                             "mrope_section is)")
        sa = hf_config.get("sa_config")
        if not sa:
            raise ValueError("KeyeVL2: no sa_config (the sparse attention's "
                             "indexer sizes and topk)")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("KeyeVL2: the indexer has ONE key a token; "
                             f"indexer_num_kv_heads={sa['indexer_num_kv_heads']}")
        cfg = SdarMoePolicy.config_from_hf(self, {**hf_config, "rope_scaling": None})
        return dataclasses.replace(
            cfg, objective="causal_lm", dsa_topk=int(sa["topk"]),
            dsa_index_heads=int(sa["indexer_num_heads"]),
            dsa_index_head_dim=int(sa["indexer_head_dim"]))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p = f"model.layers.{layer}.self_attn.indexer."
        f = f"layers_{layer}/self_attn/"
        out[p + "wq_b.weight"] = (f + "indexer_q_proj/kernel", True)
        out[p + "wk.weight"] = (f + "indexer_k_proj/kernel", True)
        out[p + "k_norm.weight"] = (f + "indexer_k_norm/scale", False)
        out[p + "k_norm.bias"] = (f + "indexer_k_norm/bias", False)
        out[p + "weights_proj.weight"] = (f + "indexer_weights_proj/kernel", True)
        return out


class Lfm2MoePolicy(HFCheckpointPolicy):
    """LFM2-MoE (HF ``modeling_lfm2_moe.py``): pre-norm layers
    (``operator_norm``, ``ffn_norm``) whose operator is, by ``layer_types``,
    a gated short convolution (``conv.in_proj`` to ``B | C | x``, depthwise
    causal ``conv.conv`` of ``conv_L_cache`` taps over ``B * x``, gated by
    ``C``, ``conv.out_proj``) or GQA attention with RMSNorm over each head's
    q and k (``q_layernorm``, ``k_layernorm``) and ``out_proj``; whose FFN is
    a SwiGLU ``intermediate_size`` wide in the first ``num_dense_layers``
    layers and afterwards ``num_experts`` experts ``moe_intermediate_size``
    wide, top-k of sigmoid scores plus ``expert_bias`` (a buffer), weighted
    by the unbiased scores over ``sum + 1e-6`` (``norm_topk_prob``) times
    ``routed_scaling_factor``. Final norm ``embedding_norm``, embeddings
    tied, no biases. A chip's share of the experts is the deployment's to
    set (``moe_experts_held``), not the checkpoint's."""
    arch = "lfm2_moe"
    row_parallel = ["o_proj", "down_proj", "out_proj"]
    col_parallel = HFCheckpointPolicy.col_parallel + ["in_proj"]

    def config_from_hf(self, hf_config):
        import dataclasses
        if hf_config.get("conv_bias"):
            raise ValueError("lfm2_moe: conv_bias=True is not supported")
        rope = hf_config.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"lfm2_moe: rope_type {rope['rope_type']!r} is "
                             "not supported")
        depth, dense = hf_config["num_hidden_layers"], hf_config.get("num_dense_layers", 0)
        types = hf_config["layer_types"]
        if len(types) != depth or set(types) - {"conv", "full_attention"}:
            raise ValueError(f"lfm2_moe: layer_types {types} for {depth} layers")
        specs = tuple(
            LayerSpec(operator="conv" if kind == "conv" else "attention",
                      ffn="dense" if i < dense else "moe",
                      ffn_width=hf_config["intermediate_size"] if i < dense
                      else hf_config["moe_intermediate_size"])
            for i, kind in enumerate(types))
        cfg = super().config_from_hf({
            **hf_config,
            "rms_norm_eps": hf_config.get("norm_eps", 1e-5),
            "rope_theta": rope.get("rope_theta", hf_config.get("rope_theta", 1e6)),
            "tie_word_embeddings": hf_config.get("tie_word_embeddings", True)})
        self.bind(dataclasses.replace(
            cfg, layer_specs=specs, qk_norm="head",
            conv_L_cache=hf_config.get("conv_L_cache", 3),
            num_local_experts=hf_config["num_experts"] if dense < depth else 0,
            num_experts_per_tok=hf_config.get("num_experts_per_tok", 4),
            moe_scoring="sigmoid",
            moe_selection_bias=bool(hf_config.get("use_expert_bias", True)),
            moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
            moe_renorm_eps=1e-6,   # in the modeling code, not a config key
            routed_scaling_factor=float(hf_config.get("routed_scaling_factor", 1.0))))
        return self._cfg

    def bind(self, cfg: LlamaConfig):
        """The name maps depend on each layer's kind: a policy made for an
        export is told the config (a conversion's is told by
        ``config_from_hf``)."""
        self._cfg = cfg

    def weight_map(self, layer: int, attention_bias: bool = False):
        spec = self._cfg.layer_specs[layer]
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "operator_norm.weight": (f + "operator_norm/weight", False),
               p + "ffn_norm.weight": (f + "ffn_norm/weight", False)}
        if spec.operator == "conv":
            for proj in ("in_proj", "out_proj"):
                out[p + f"conv.{proj}.weight"] = (f + f"conv/{proj}/kernel", True)
        else:
            for hf_name, fx in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                                ("v_proj", "v_proj"), ("out_proj", "o_proj")):
                out[p + f"self_attn.{hf_name}.weight"] = (
                    f + f"self_attn/{fx}/kernel", True)
            out[p + "self_attn.q_layernorm.weight"] = (f + "self_attn/q_norm/weight", False)
            out[p + "self_attn.k_layernorm.weight"] = (f + "self_attn/k_norm/weight", False)
        if spec.ffn == "dense":
            for hf_name, fx in (("w1", "gate_proj"), ("w3", "up_proj"),
                                ("w2", "down_proj")):
                out[p + f"feed_forward.{hf_name}.weight"] = (
                    f + f"mlp/{fx}/kernel", True)
        return out

    def moe_map(self, layer: int, num_experts: int):
        if self._cfg.layer_specs[layer].ffn != "moe":
            return {}, {}
        p = f"model.layers.{layer}.feed_forward."
        f = f"layers_{layer}/block_sparse_moe/"
        gate = {p + "gate.weight": (f + "gate/kernel", True)}
        if self._cfg.moe_selection_bias:
            gate[p + "expert_bias"] = (f + "expert_bias", False)
        experts = {f + which: [p + f"experts.{e}.{which}.weight"
                               for e in range(num_experts)]
                   for which in ("w1", "w3", "w2")}
        return gate, experts

    def _taps(self, layer: int):
        return (f"model.layers.{layer}.conv.conv.weight",
                f"layers_{layer}/conv/conv_weight")

    def special_hf_names(self, layer: int):
        is_conv = self._cfg.layer_specs[layer].operator == "conv"
        return [self._taps(layer)[0]] if is_conv else []

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """torch Conv1d's depthwise weight ``[C, 1, L]`` -> taps ``[L, C]``."""
        for hf_name in self.special_hf_names(layer):
            put(self._taps(layer)[1], get_tensor(hf_name)[:, 0, :].T)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        return {hf_name: flat[self._taps(layer)[1]].T[:, None, :]
                for hf_name in self.special_hf_names(layer)}

    def global_map(self, tie_embeddings: bool):
        out = super().global_map(tie_embeddings)
        out["model.embedding_norm.weight"] = out.pop("model.norm.weight")
        return out


class DeepseekV3Policy(HFCheckpointPolicy):
    """DeepSeek-V3 text blocks (HF ``modeling_deepseek.py``, ``model_type:
    deepseek_v3``; Kimi-VL's and Moonlight's language model): pre-norm
    layers of multi-head latent attention (``q_proj`` straight from the
    stream where ``q_lora_rank`` is null, ``kv_a_proj_with_mqa`` to a latent
    of ``kv_lora_rank`` and ONE rope key of ``qk_rope_head_dim``,
    ``kv_a_layernorm``, ``kv_b_proj`` to each head's ``qk_nope_head_dim``
    key part and ``v_head_dim`` values, scores over ``sqrt(nope + rope)``);
    a SwiGLU ``intermediate_size`` wide in the first
    ``first_k_dense_replace`` layers and afterwards ``n_routed_experts``
    experts ``moe_intermediate_size`` wide, top-k of sigmoid scores plus
    ``e_score_correction_bias`` (``topk_method: noaux_tc``, a buffer),
    weighted by the unbiased scores over ``sum + 1e-20`` (``norm_topk_prob``)
    times ``routed_scaling_factor``, beside ``n_shared_experts *
    moe_intermediate_size`` of ungated shared expert. The rotary embedding
    turns adjacent pairs of the rope slices (the modeling code
    de-interleaves, then rotates halves: the same rotation). ``n_group`` /
    ``topk_group`` limit the choice to the best groups (``LlamaConfig.
    moe_n_group``). Not built, and refused: ``q_lora_rank``, ``rope_scaling``
    (YaRN and its mscale), ``moe_layer_freq != 1``, biases. A chip's share
    of the experts and of the vocabulary is the deployment's to set
    (``moe_experts_held``, ``vocab_size``), not the checkpoint's."""
    arch = "deepseek_v3"
    col_parallel = ["q_proj", "kv_b_proj", "gate_proj", "up_proj"]

    def config_from_hf(self, hf_config):
        import dataclasses
        for key in ("q_lora_rank", "rope_scaling", "attention_bias"):
            if hf_config.get(key):
                raise ValueError(f"deepseek_v3: {key}={hf_config[key]!r} is not supported")
        if hf_config.get("moe_layer_freq", 1) != 1:
            raise ValueError("deepseek_v3: moe_layer_freq other than 1 is not supported")
        if (hf_config.get("scoring_func", "sigmoid") != "sigmoid"
                or hf_config.get("topk_method", "noaux_tc") != "noaux_tc"):
            raise ValueError("deepseek_v3: only the sigmoid router with a "
                             "selection bias (noaux_tc) is supported")
        depth = hf_config["num_hidden_layers"]
        dense = min(hf_config.get("first_k_dense_replace", 0), depth)
        experts = hf_config.get("n_routed_experts") or 0
        if not experts:
            dense = depth
        specs = tuple(
            LayerSpec(operator="latent", ffn="dense" if i < dense else "moe",
                      ffn_width=hf_config["intermediate_size"] if i < dense
                      else hf_config["moe_intermediate_size"])
            for i in range(depth))
        nope, rope = hf_config["qk_nope_head_dim"], hf_config["qk_rope_head_dim"]
        shared = hf_config.get("n_shared_experts") or 0
        cfg = super().config_from_hf(hf_config)
        self.bind(dataclasses.replace(
            cfg, layer_specs=specs, head_dim=nope + rope, rotary_dim=rope,
            rope_interleaved=True, kv_lora_rank=hf_config["kv_lora_rank"],
            v_head_dim=hf_config["v_head_dim"],
            num_local_experts=experts if dense < depth else 0,
            num_experts_per_tok=hf_config.get("num_experts_per_tok", 8),
            moe_scoring="sigmoid", moe_selection_bias=True,
            moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
            moe_renorm_eps=1e-20,   # in the modeling code, not a config key
            moe_n_group=int(hf_config.get("n_group") or 1),
            moe_topk_group=int(hf_config.get("topk_group") or 1),
            routed_scaling_factor=float(hf_config.get("routed_scaling_factor", 1.0)),
            shared_expert_intermediate_size=(
                shared * hf_config["moe_intermediate_size"] or None),
            shared_expert_gated=False))
        return self._cfg

    def bind(self, cfg: LlamaConfig):
        """As ``Lfm2MoePolicy.bind``: the name maps depend on the layer's kind."""
        self._cfg = cfg

    def weight_map(self, layer: int, attention_bias: bool = False):
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "input_layernorm.weight": (f + "operator_norm/weight", False),
               p + "post_attention_layernorm.weight": (f + "ffn_norm/weight", False),
               p + "self_attn.kv_a_layernorm.weight": (
                   f + "self_attn/kv_a_layernorm/weight", False)}
        for proj in ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj"):
            out[p + f"self_attn.{proj}.weight"] = (f + f"self_attn/{proj}/kernel", True)
        if self._cfg.layer_specs[layer].ffn == "dense":
            for proj in ("gate_proj", "up_proj", "down_proj"):
                out[p + f"mlp.{proj}.weight"] = (f + f"mlp/{proj}/kernel", True)
        return out

    def moe_map(self, layer: int, num_experts: int):
        if self._cfg.layer_specs[layer].ffn != "moe":
            return {}, {}
        gate, experts = _mlp_experts_map(layer, num_experts)
        p = f"model.layers.{layer}.mlp."
        f = f"layers_{layer}/block_sparse_moe/"
        gate[p + "gate.e_score_correction_bias"] = (f + "expert_bias", False)
        if self._cfg.shared_expert_intermediate_size:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                gate[p + f"shared_experts.{proj}.weight"] = (
                    f + f"shared_expert/{proj}/kernel", True)
        return gate, experts


class BailingHybridPolicy(DeepseekV3Policy):
    """Ling-3.0's hybrid blocks (``model_type: bailing_hybrid``): pre-norm
    layers whose mixer is Kimi Delta Attention (``KimiDeltaMixer``: ``q/k/v_
    proj`` through ``short_conv_kernel_size`` causal taps and SiLU
    (``linear_silu``), L2-normed q and k (``use_qk_norm``), a decay for every
    key channel from one full ``f_proj`` (``no_kda_lora``) bounded below by
    ``kda_lower_bound`` (``kda_safe_gate``), ``b_proj``, a sigmoid output gate
    ``g_proj`` under a per-head RMSNorm) and, every ``layer_group_size``-th
    layer, multi-head latent attention as ``DeepseekV3Policy`` reads it with a
    head-wise sigmoid gate before ``o_proj`` (``gated_attention_proj_
    granularity_type: head_wise``); a SwiGLU ``intermediate_size`` wide in the
    first ``first_k_dense_replace`` layers and afterwards ``num_experts``
    experts ``moe_intermediate_size`` wide, top-k of sigmoid scores plus a bias
    (``moe_router_enable_expert_bias``) inside the ``topk_group`` best of
    ``n_group`` groups, beside ``num_shared_experts`` of ungated shared expert.
    ``layer_types`` (``"kda"`` | ``"mla"`` a layer) states the kinds where a
    file keeps layers that do not start a group (``layer_offset`` then says
    which published layer is the first: the SwiGLU limits are read from
    there); else layer ``i`` is MLA where ``(i + 1) % layer_group_size == 0``.
    Not built, and refused by name: a non-zero ``expert_swiglu_limit_list`` /
    ``share_expert_swiglu_limit_list`` entry of a kept layer (the clamp's form
    is not in the config), ``mtp_loss_scaling_factor`` above 0 (the
    multi-token-prediction module: at the published 0 it adds nothing to the
    loss and is left out), an unbounded gate (``kda_safe_gate`` false), a
    low-rank decay gate (``use_kda_lora``), ``use_nGPT``, ``value_norm``,
    ``up_proj_norm``, ``scale_router_input``, ``use_mla_nope``, biases,
    ``q_lora_rank``, ``rope_scaling``, fewer key heads than query heads in the
    linear layers."""
    arch = "bailing_hybrid"
    col_parallel = DeepseekV3Policy.col_parallel + ["f_proj", "g_proj"]

    def config_from_hf(self, hf_config):
        import dataclasses
        hf = dict(hf_config)
        refused = {key: bool(hf.get(key)) for key in (
            "use_kda_lora", "use_nGPT", "value_norm", "up_proj_norm",
            "scale_router_input", "use_mla_nope", "use_bias", "use_qkv_bias",
            "num_kv_heads_for_linear_attn")}
        refused.update({
            "kda_safe_gate": not hf.get("kda_safe_gate", True),
            "no_kda_lora": not hf.get("no_kda_lora", True),
            "linear_silu": not hf.get("linear_silu", True),
            "use_qk_norm": not hf.get("use_qk_norm", True),
            "group_norm_size": hf.get("group_norm_size", 1) != 1,
            "mtp_loss_scaling_factor": (hf.get("mtp_loss_scaling_factor") or 0) > 0,
            "gated_attention_proj_granularity_type": hf.get(
                "gated_attention_proj_granularity_type", "head_wise") != "head_wise",
            "moe_router_enable_expert_bias": not hf.get(
                "moe_router_enable_expert_bias", True)})
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"bailing_hybrid: {key}={hf.get(key)!r} is not supported")
        depth, group = hf["num_hidden_layers"], int(hf.get("layer_group_size", 1))
        offset = int(hf.get("layer_offset", 0))
        types = hf.get("layer_types") or [
            "mla" if (offset + i + 1) % group == 0 else "kda" for i in range(depth)]
        if len(types) != depth or set(types) - {"kda", "mla"}:
            raise ValueError(f"bailing_hybrid: layer_types {types} for {depth} layers")
        for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            kept = list(hf.get(key) or ())[offset:offset + depth]
            if any(kept):
                raise ValueError(
                    f"bailing_hybrid: {key} is {kept} in layers {offset}-"
                    f"{offset + depth - 1}: a clamped SwiGLU is not supported (its "
                    "form is not in the config)")
        cfg = super().config_from_hf({
            **hf, "n_routed_experts": hf["num_experts"],
            "n_shared_experts": hf.get("num_shared_experts") or 0,
            "scoring_func": hf.get("score_function", hf.get("scoring_func", "sigmoid")),
            "moe_intermediate_size": hf["moe_intermediate_size"]})
        shared = ((hf.get("num_shared_experts") or 0)
                  * hf.get("moe_shared_expert_intermediate_size", 0)) or None
        specs = tuple(dataclasses.replace(spec, operator="latent" if kind == "mla" else "kda")
                      for spec, kind in zip(cfg.layer_specs, types))
        self.bind(dataclasses.replace(
            cfg, layer_specs=specs, attn_output_gate="head",
            shared_expert_intermediate_size=shared,
            kda_head_dim=hf["head_dim"],
            kda_d_conv=int(hf.get("short_conv_kernel_size", 4)),
            kda_gate_floor=float(hf.get("kda_lower_bound", -5))))
        return self._cfg

    def weight_map(self, layer: int, attention_bias: bool = False):
        if self._cfg.layer_specs[layer].operator == "latent":
            out = super().weight_map(layer)
            out[f"model.layers.{layer}.self_attn.gate_proj.weight"] = (
                f"layers_{layer}/self_attn/gate_proj/kernel", True)
            return out
        # the linear layers' names are this repo's (the published modeling
        # code is not in the catalog row): projections under ``self_attn``
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "input_layernorm.weight": (f + "operator_norm/weight", False),
               p + "post_attention_layernorm.weight": (f + "ffn_norm/weight", False)}
        for proj in ("q_proj", "k_proj", "v_proj", "f_proj", "b_proj", "g_proj", "o_proj"):
            out[p + f"self_attn.{proj}.weight"] = (f + f"self_attn/{proj}/kernel", True)
        for leaf in ("A_log", "dt_bias", "o_norm", "q_conv_weight", "k_conv_weight",
                     "v_conv_weight"):
            out[p + f"self_attn.{leaf}"] = (f + f"self_attn/{leaf}", False)
        if self._cfg.layer_specs[layer].ffn == "dense":
            for proj in ("gate_proj", "up_proj", "down_proj"):
                out[p + f"mlp.{proj}.weight"] = (f + f"mlp/{proj}/kernel", True)
        return out


class GraniteMoeHybridPolicy(HFCheckpointPolicy):
    """Granite 4.0-H, the dense hybrid (HF ``modeling_granitemoehybrid.py``):
    pre-norm layers whose mixer is, by ``layer_types``, a Mamba-2 layer
    (``mamba.in_proj`` to ``z | xBC | dt``, ``mamba.conv1d`` with bias and
    SiLU, the state-space scan, the gated ``mamba.norm``, ``mamba.out_proj``)
    or GQA attention without a position embedding; a SwiGLU
    ``shared_mlp`` (``input_linear`` holds gate and up, in that order) in
    every layer; ``embedding_multiplier`` on the embeddings,
    ``residual_multiplier`` on both branches, ``attention_multiplier`` as
    the scores' scale, logits over ``logits_scaling``; embeddings tied.
    Refused by name until a later change brings them: routed experts
    (``num_local_experts > 0``), ``mamba_n_groups > 1``, a
    ``position_embedding_type`` other than ``"nope"``, biases on the
    mixer's projections, another norm or activation."""
    arch = "granitemoehybrid"
    row_parallel = ["o_proj", "down_proj", "out_proj"]
    col_parallel = HFCheckpointPolicy.col_parallel + ["in_proj"]

    def config_from_hf(self, hf_config):
        import dataclasses
        refused = {
            "num_local_experts": hf_config.get("num_local_experts", 0) > 0,
            "mamba_n_groups": hf_config.get("mamba_n_groups", 1) > 1,
            "position_embedding_type":
                hf_config.get("position_embedding_type", "nope") != "nope",
            "normalization_function":
                hf_config.get("normalization_function", "rmsnorm") != "rmsnorm",
            "hidden_act": hf_config.get("hidden_act", "silu") != "silu",
            "mamba_proj_bias": bool(hf_config.get("mamba_proj_bias", False))}
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"granitemoehybrid: {key}={hf_config[key]!r} is "
                                 "not supported")
        depth, types = hf_config["num_hidden_layers"], hf_config["layer_types"]
        if len(types) != depth or set(types) - {"mamba", "attention"}:
            raise ValueError(f"granitemoehybrid: layer_types {types} for {depth} layers")
        heads, head = hf_config["mamba_n_heads"], hf_config["mamba_d_head"]
        if heads * head != hf_config.get("mamba_expand", 2) * hf_config["hidden_size"]:
            raise ValueError(f"granitemoehybrid: {heads} heads of {head} are not "
                             "mamba_expand * hidden_size")
        width = hf_config["shared_intermediate_size"]
        cfg = super().config_from_hf({
            **hf_config, "intermediate_size": width,
            "tie_word_embeddings": hf_config.get("tie_word_embeddings", True)})
        self.bind(dataclasses.replace(
            cfg,
            layer_specs=tuple(LayerSpec(operator=kind, ffn="dense", ffn_width=width)
                              for kind in types),
            pos_embedding="none", num_local_experts=0,
            attention_bias=bool(hf_config.get("attention_bias", False)),
            embed_scale=float(hf_config.get("embedding_multiplier", 1.0)),
            residual_multiplier=float(hf_config.get("residual_multiplier", 1.0)),
            attn_scale=float(hf_config.get("attention_multiplier", 1.0)),
            logit_scale=1.0 / float(hf_config.get("logits_scaling", 1.0)),
            mamba_n_heads=heads, mamba_d_head=head,
            mamba_d_state=hf_config["mamba_d_state"], mamba_n_groups=1,
            mamba_chunk_size=hf_config.get("mamba_chunk_size", 256),
            mamba_d_conv=hf_config.get("mamba_d_conv", 4),
            mamba_conv_bias=bool(hf_config.get("mamba_conv_bias", True))))
        return self._cfg

    def bind(self, cfg: LlamaConfig):
        """As ``Lfm2MoePolicy.bind``: the name maps depend on the layer's kind."""
        self._cfg = cfg

    def weight_map(self, layer: int, attention_bias: bool = False):
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "input_layernorm.weight": (f + "operator_norm/weight", False),
               p + "post_attention_layernorm.weight": (f + "ffn_norm/weight", False),
               p + "shared_mlp.output_linear.weight": (f + "mlp/down_proj/kernel", True)}
        if self._cfg.layer_specs[layer].operator == "mamba":
            m, fm = p + "mamba.", f + "mamba/"
            out.update({m + "in_proj.weight": (fm + "in_proj/kernel", True),
                        m + "out_proj.weight": (fm + "out_proj/kernel", True),
                        m + "dt_bias": (fm + "dt_bias", False),
                        m + "A_log": (fm + "A_log", False),
                        m + "D": (fm + "D", False),
                        m + "norm.weight": (fm + "norm_weight", False)})
            if self._cfg.mamba_conv_bias:
                out[m + "conv1d.bias"] = (fm + "conv_bias", False)
        else:
            for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
                out[p + f"self_attn.{proj}.weight"] = (
                    f + f"self_attn/{proj}/kernel", True)
                if attention_bias and proj != "o_proj":
                    out[p + f"self_attn.{proj}.bias"] = (
                        f + f"self_attn/{proj}/bias", False)
        return out

    def _special(self, layer: int):
        """HF name -> our path(s) of the tensors a plain map cannot express."""
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "shared_mlp.input_linear.weight":
               (f + "mlp/gate_proj/kernel", f + "mlp/up_proj/kernel")}
        if self._cfg.layer_specs[layer].operator == "mamba":
            out[p + "mamba.conv1d.weight"] = (f + "mamba/conv_weight", )
        return out

    def special_hf_names(self, layer: int):
        return list(self._special(layer))

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """``input_linear`` ``[2 F, hidden]`` -> gate and up kernels
        ``[hidden, F]``; torch Conv1d's depthwise weight ``[C, 1, L]`` -> taps
        ``[L, C]``."""
        for hf_name, paths in self._special(layer).items():
            w = get_tensor(hf_name)
            if len(paths) == 2:
                gate, up = np.split(w, 2, axis=0)
                put(paths[0], gate.T)
                put(paths[1], up.T)
            else:
                put(paths[0], w[:, 0, :].T)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        out = {}
        for hf_name, paths in self._special(layer).items():
            if len(paths) == 2:
                out[hf_name] = np.concatenate([flat[paths[0]].T, flat[paths[1]].T])
            else:
                out[hf_name] = flat[paths[0]].T[:, None, :]
        return out


class Phi4FlashPolicy(HFCheckpointPolicy):
    """Phi-4-mini-flash (``model_type: phi4flash``; SambaY, arXiv:2507.06607, with
    differential attention): pre-norm layers (``LayerNorm`` with bias, no
    position embedding) whose mixer follows from the layer's PUBLISHED index
    ``i`` of ``num_hidden_layers`` (half = ``num_hidden_layers // 2``, the
    self-decoder; every ``mb_per_layer``-th layer a state-space one):

        i even: Mamba-1 for i <= half (layer ``half`` also hands on its scan
                output), a Gated Memory Unit over that output for i > half
        i odd:  differential attention under ``sliding_window`` for i < half,
                full causal at i = half + 1 (which hands on its keys and
                values), cross-attention to those for i > half + 1

    and a SwiGLU without bias in every layer (the checkpoint's fused
    ``gate_up_proj`` is split here), a final LayerNorm, embedding and head
    tied. The state-space sizes are the family's (``d_state`` 16, ``d_conv``
    4, ``expand`` 2, ``dt_rank`` hidden / 16): the config has no key for
    them, a key of Mamba's name (``mamba_d_state``, ``mamba_d_conv``,
    ``mamba_expand``, ``mamba_dt_rank``) overrides. A stack cut out of the
    model gives its kinds as ``layer_types`` (``mamba`` | ``sliding_attention``
    | ``full_attention`` | ``gmu`` | ``cross_attention``) with ``layer_offset``,
    the published index of its first layer; the kinds must be the rule's, and
    a stack that keeps a reader without its source is refused by name.
    Refused too: ``mlp_bias``, ``lm_head_bias``, an ``mb_per_layer`` other than
    2, another activation."""
    arch = "phi4flash"
    row_parallel = ["o_proj", "down_proj", "out_proj"]
    col_parallel = HFCheckpointPolicy.col_parallel + ["in_proj"]
    KINDS = ("mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")

    @staticmethod
    def published_kinds(depth: int, mb_per_layer: int = 2):
        """The kind of every layer of the whole model, by the rule above."""
        half = depth // 2

        def kind(i):
            if i % mb_per_layer == 0:
                return "mamba" if i <= half else "gmu"
            if i < half:
                return "sliding_attention"
            return "full_attention" if i == half + 1 else "cross_attention"

        return [kind(i) for i in range(depth)]

    def config_from_hf(self, hf_config):
        import dataclasses
        refused = {"mlp_bias": bool(hf_config.get("mlp_bias", False)),
                   "lm_head_bias": bool(hf_config.get("lm_head_bias", False)),
                   "mb_per_layer": hf_config.get("mb_per_layer", 2) != 2,
                   "hidden_act": hf_config.get("hidden_act", "silu") != "silu"}
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"phi4flash: {key}={hf_config[key]!r} is not supported")
        depth, hidden = hf_config["num_hidden_layers"], hf_config["hidden_size"]
        published = hf_config.get("published", {}).get("num_hidden_layers", depth)
        offset = int(hf_config.get("layer_offset", 0))
        types = hf_config.get("layer_types") or self.published_kinds(published)
        rule = self.published_kinds(published)[offset:offset + depth]
        if len(types) != depth or list(types) != rule:
            raise ValueError(f"phi4flash: layer_types {list(types)} are not the kinds of "
                             f"published layers {offset}..{offset + depth - 1} of "
                             f"{published}: {rule}")
        half = published // 2
        memory, kv = half - offset, half + 1 - offset   # where the sources stand here
        window = hf_config.get("sliding_window")
        width = hf_config["intermediate_size"]

        def spec(kind):
            if kind == "mamba":
                return LayerSpec(operator="mamba1", ffn="dense", ffn_width=width)
            if kind == "gmu":
                return LayerSpec(operator="gmu", ffn="dense", ffn_width=width,
                                 memory_from=memory)
            return LayerSpec(
                operator="attention", ffn="dense", ffn_width=width, differential=True,
                window=int(window or 0) if kind == "sliding_attention" else 0,
                kv_from=kv if kind == "cross_attention" else -1)

        cfg = super().config_from_hf({
            **hf_config, "rms_norm_eps": hf_config.get("layer_norm_eps", 1e-5),
            "tie_word_embeddings": hf_config.get("tie_word_embeddings", True)})
        inner = int(hf_config.get("mamba_expand", 2)) * hidden
        cfg = dataclasses.replace(
            cfg, layer_specs=tuple(spec(kind) for kind in types),
            norm_type="layernorm", pos_embedding="none", num_local_experts=0,
            attention_bias=True, attention_out_bias=True, layer_index_offset=offset,
            mamba1_d_inner=inner,
            mamba1_dt_rank=int(hf_config.get("mamba_dt_rank", -(-hidden // 16))),
            mamba_d_state=int(hf_config.get("mamba_d_state", 16)),
            mamba_d_conv=int(hf_config.get("mamba_d_conv", 4)), mamba_conv_bias=True)
        try:
            cfg.shared_sources()
        except ValueError as e:
            raise ValueError(f"phi4flash: published layers {offset}..{offset + depth - 1} "
                             f"of {published}: {e}") from None
        self.bind(cfg)
        return cfg

    def bind(self, cfg: LlamaConfig):
        """As ``Lfm2MoePolicy.bind``: the name maps depend on the layer's kind."""
        self._cfg = cfg

    def global_map(self, tie_embeddings: bool):
        out = {"model.embed_tokens.weight": ("embed_tokens/embedding", False),
               "model.final_layernorm.weight": ("norm/scale", False),
               "model.final_layernorm.bias": ("norm/bias", False)}
        if not tie_embeddings:
            out["lm_head.weight"] = ("lm_head/kernel", True)
        return out

    def weight_map(self, layer: int, attention_bias: bool = False):
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "mlp.down_proj.weight": (f + "mlp/down_proj/kernel", True)}
        for hf, ours in (("input_layernorm", "operator_norm"),
                         ("post_attention_layernorm", "ffn_norm")):
            out[p + hf + ".weight"] = (f + ours + "/scale", False)
            out[p + hf + ".bias"] = (f + ours + "/bias", False)
        spec = self._cfg.layer_specs[layer]
        a = p + "attn."
        if spec.operator == "attention":
            fa = f + "self_attn/"
            out.update({a + "out_proj.weight": (fa + "o_proj/kernel", True),
                        a + "out_proj.bias": (fa + "o_proj/bias", False),
                        a + "inner_cross_attn.subln.weight": (fa + "subln", False)})
            for n in ("q1", "k1", "q2", "k2"):
                out[a + "inner_cross_attn.lambda_" + n] = (fa + "lambda_" + n, False)
            if spec.kv_from >= 0:       # a cross layer's Wqkv is its queries'
                out[a + "Wqkv.weight"] = (fa + "q_proj/kernel", True)
                out[a + "Wqkv.bias"] = (fa + "q_proj/bias", False)
            return out
        fm = f + "mamba/"
        out.update({a + "in_proj.weight": (fm + "in_proj/kernel", True),
                    a + "out_proj.weight": (fm + "out_proj/kernel", True)})
        if spec.operator == "mamba1":
            out.update({a + "x_proj.weight": (fm + "x_proj/kernel", True),
                        a + "dt_proj.weight": (fm + "dt_proj/kernel", True),
                        a + "dt_proj.bias": (fm + "dt_proj/bias", False),
                        a + "conv1d.bias": (fm + "conv_bias", False),
                        a + "A_log": (fm + "A_log", False), a + "D": (fm + "D", False)})
        return out

    def _special(self, layer: int):
        """HF name -> our path(s) of the tensors a plain map cannot express."""
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        out = {p + "mlp.gate_up_proj.weight":
               (f + "mlp/gate_proj/kernel", f + "mlp/up_proj/kernel")}
        spec = self._cfg.layer_specs[layer]
        if spec.operator == "mamba1":
            out[p + "attn.conv1d.weight"] = (f + "mamba/conv_weight", )
        elif spec.operator == "attention" and spec.kv_from < 0:
            for kind in ("weight", "bias"):
                leaf = "kernel" if kind == "weight" else "bias"
                out[p + "attn.Wqkv." + kind] = tuple(
                    f + f"self_attn/{proj}/{leaf}" for proj in ("q_proj", "k_proj", "v_proj"))
        return out

    def special_hf_names(self, layer: int):
        return list(self._special(layer))

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """``gate_up_proj`` ``[2 F, hidden]`` -> gate and up kernels ``[hidden,
        F]``; ``Wqkv`` (rows q | k | v) -> the three projections; torch
        Conv1d's depthwise weight ``[C, 1, L]`` -> taps ``[L, C]``."""
        hd = cfg.head_dim_
        rows = np.cumsum([cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd])
        for hf_name, paths in self._special(layer).items():
            w = get_tensor(hf_name)
            if len(paths) == 1:
                put(paths[0], w[:, 0, :].T)
                continue
            parts = np.split(w, 2 if len(paths) == 2 else rows, axis=0)
            for path, part in zip(paths, parts):
                put(path, part.T if part.ndim == 2 else part)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        out = {}
        for hf_name, paths in self._special(layer).items():
            if len(paths) == 1:
                out[hf_name] = flat[paths[0]].T[:, None, :]
            else:
                out[hf_name] = np.concatenate([flat[path].T for path in paths])
        return out


class GemmaPolicy(HFCheckpointPolicy):
    """Gemma (v1): llama graph with (1+weight) RMSNorm, sqrt(hidden) embed
    normalizer (rounded through the compute dtype, as HF does), tanh-gelu
    gated MLP, explicit head_dim, tied embeddings."""
    arch = "gemma"

    def config_from_hf(self, hf_config):
        import dataclasses
        cfg = super().config_from_hf(hf_config)
        return dataclasses.replace(
            cfg, tie_word_embeddings=True, norm_plus_one=True,
            head_dim=hf_config.get(
                "head_dim",
                hf_config["hidden_size"] // hf_config["num_attention_heads"]),
            embed_scale=float(hf_config["hidden_size"]) ** 0.5,
            mlp_type="geglu_tanh")


class Gemma2Policy(GemmaPolicy):
    """Gemma-2 adds sandwich norms (pre+post around both sublayers),
    attention/final logit softcapping, query_pre_attn_scalar-derived scale,
    and a sliding window on every EVEN layer (HF: ``not bool(layer_idx %
    2)``)."""
    arch = "gemma2"

    def config_from_hf(self, hf_config):
        import dataclasses
        cfg = super().config_from_hf(hf_config)
        return dataclasses.replace(
            cfg, sandwich_norm=True,
            attn_scale=float(hf_config.get("query_pre_attn_scalar", 256)) ** -0.5,
            attn_logit_softcapping=hf_config.get("attn_logit_softcapping", 50.0),
            final_logit_softcapping=hf_config.get("final_logit_softcapping", 30.0),
            sliding_window=hf_config.get("sliding_window"),
            sliding_window_layers=tuple(
                range(0, hf_config["num_hidden_layers"], 2)))

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        out[p + "pre_feedforward_layernorm.weight"] = \
            (f + "pre_feedforward_layernorm/weight", False)
        out[p + "post_feedforward_layernorm.weight"] = \
            (f + "post_feedforward_layernorm/weight", False)
        return out


class OuroPolicy(HFCheckpointPolicy):
    """Ouro (ByteDance's LoopLM, HF ``modeling_ouro.py``, ``model_type:
    ouro``): a Llama stack whose layers carry four norms, ``x +
    input_layernorm_2(attn(input_layernorm(x)))`` then ``a +
    post_attention_layernorm_2(mlp(post_attention_layernorm(a)))`` (the model's
    ``sandwich_norm`` layer, whose tree keeps Gemma-2's names: the map below
    renames three of the four), and which is applied ``total_ut_steps`` times
    over the same weights, the one final norm after every pass, an
    ``early_exit_gate`` (hidden -> 1, with a bias) reading each pass's normed
    stream. ``exit_entropy_weight`` is the training recipe's to set (the config
    has no key of it): without it the loss is the last pass's CE, HF's
    ``labels`` path at ``early_exit_threshold`` 1. Refused by name: a sliding
    window, ``rope_scaling``, biases, another activation, a layer type other
    than ``full_attention``, an exit threshold under 1 (serving's early exit
    is not built)."""
    arch = "ouro"

    def config_from_hf(self, hf_config):
        import dataclasses
        hf = dict(hf_config)
        refused = {"use_sliding_window": bool(hf.get("use_sliding_window")),
                   "rope_scaling": bool(hf.get("rope_scaling")),
                   "attention_bias": bool(hf.get("attention_bias")),
                   "hidden_act": hf.get("hidden_act", "silu") != "silu",
                   "layer_types": bool(set(hf.get("layer_types") or ())
                                       - {"full_attention"}),
                   "early_exit_threshold": float(hf.get("early_exit_threshold", 1.0)) < 1.0}
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"ouro: {key}={hf.get(key)!r} is not supported")
        if hf.get("layer_types") and len(hf["layer_types"]) != hf["num_hidden_layers"]:
            raise ValueError(f"ouro: {len(hf['layer_types'])} layer_types for "
                             f"{hf['num_hidden_layers']} layers")
        cfg = super().config_from_hf({**hf, "rms_norm_eps": hf.get("rms_norm_eps", 1e-6)})
        return dataclasses.replace(
            cfg, head_dim=hf.get("head_dim"), sandwich_norm=True,
            total_ut_steps=int(hf.get("total_ut_steps", 4)), exit_gate=True)

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p, f = f"model.layers.{layer}.", f"layers_{layer}/"
        for hf_name, ours in (("input_layernorm_2", "post_attention_layernorm"),
                              ("post_attention_layernorm", "pre_feedforward_layernorm"),
                              ("post_attention_layernorm_2", "post_feedforward_layernorm")):
            out[p + hf_name + ".weight"] = (f + ours + "/weight", False)
        return out

    def global_map(self, tie_embeddings: bool):
        out = super().global_map(tie_embeddings)
        out["model.early_exit_gate.weight"] = ("early_exit_gate/kernel", True)
        out["model.early_exit_gate.bias"] = ("early_exit_gate/bias", False)
        return out


class OPTPolicy(HFCheckpointPolicy):
    """OPT (reference ``module_inject/containers/opt.py`` +
    ``inference/v2/model_implementations/opt``): learned positions (table
    offset by 2 in HF), pre-LayerNorm, ReLU fc MLP, biases everywhere,
    tied lm_head. ``word_embed_proj_dim != hidden_size`` variants (350m's
    project_in/out) are out of scope."""
    arch = "opt"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        if hf_config.get("word_embed_proj_dim",
                         hf_config["hidden_size"]) != hf_config["hidden_size"]:
            raise ValueError("OPT variants with word_embed_proj_dim != hidden_size "
                             "(project_in/out) are not supported")
        if not hf_config.get("do_layer_norm_before", True):
            raise ValueError("OPT do_layer_norm_before=False (post-LN, the 350m "
                             "ordering) is not supported — the decoder here is "
                             "pre-LN only")
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["ffn_dim"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=hf_config["num_attention_heads"],
            max_position_embeddings=hf_config.get("max_position_embeddings", 2048),
            rms_norm_eps=1e-5,
            tie_word_embeddings=hf_config.get("tie_word_embeddings", True),
            attention_bias=hf_config.get("enable_bias", True),
            attention_out_bias=hf_config.get("enable_bias", True),
            norm_type="layernorm",
            pos_embedding="learned",
            pos_offset=2,
            mlp_type="relu_fc",
            mlp_bias=hf_config.get("enable_bias", True),
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"model.decoder.layers.{layer}."
        f = f"layers_{layer}/"
        out = {}
        for hf, fx in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                       ("v_proj", "v_proj"), ("out_proj", "o_proj")):
            out[p + f"self_attn.{hf}.weight"] = (f + f"self_attn/{fx}/kernel", True)
            if attention_bias:  # enable_bias=False checkpoints have none
                out[p + f"self_attn.{hf}.bias"] = (f + f"self_attn/{fx}/bias", False)
        if attention_bias:
            out.update({
                p + "fc1.bias": (f + "mlp/fc1/bias", False),
                p + "fc2.bias": (f + "mlp/fc2/bias", False),
            })
        out.update({
            p + "self_attn_layer_norm.weight": (f + "input_layernorm/scale", False),
            p + "self_attn_layer_norm.bias": (f + "input_layernorm/bias", False),
            p + "final_layer_norm.weight": (f + "post_attention_layernorm/scale", False),
            p + "final_layer_norm.bias": (f + "post_attention_layernorm/bias", False),
            p + "fc1.weight": (f + "mlp/fc1/kernel", True),
            p + "fc2.weight": (f + "mlp/fc2/kernel", True),
        })
        return out

    def global_map(self, tie_embeddings: bool):
        return {
            "model.decoder.embed_tokens.weight": ("embed_tokens/embedding", False),
            "model.decoder.embed_positions.weight": ("embed_positions/embedding", False),
            "model.decoder.final_layer_norm.weight": ("norm/scale", False),
            "model.decoder.final_layer_norm.bias": ("norm/bias", False),
        }


class PhiPolicy(HFCheckpointPolicy):
    """Phi-1/2 (reference ``inference/v2/model_implementations/phi``):
    parallel attention+MLP over ONE shared LayerNorm, partial rotary, GELU fc
    MLP, biases everywhere including the lm_head."""
    arch = "phi"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        if hf_config.get("qk_layernorm"):
            raise ValueError("phi qk_layernorm=True checkpoints are not supported "
                             "(q/k layernorm weights would be dropped)")
        hd = hf_config["hidden_size"] // hf_config["num_attention_heads"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=hf_config.get("num_key_value_heads")
            or hf_config["num_attention_heads"],
            max_position_embeddings=hf_config.get("max_position_embeddings", 2048),
            rms_norm_eps=hf_config.get("layer_norm_eps", 1e-5),
            rope_theta=hf_config.get("rope_theta", 10000.0),
            rotary_dim=int(hf_config.get("partial_rotary_factor", 0.5) * hd),
            attention_bias=True,
            attention_out_bias=True,
            norm_type="layernorm",
            mlp_type="gelu_tanh_fc",  # HF phi hidden_act "gelu_new"
            mlp_bias=True,
            parallel_residual=True,
            lm_head_bias=True,
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        out = {}
        for hf, fx in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                       ("v_proj", "v_proj"), ("dense", "o_proj")):
            out[p + f"self_attn.{hf}.weight"] = (f + f"self_attn/{fx}/kernel", True)
            out[p + f"self_attn.{hf}.bias"] = (f + f"self_attn/{fx}/bias", False)
        out.update({
            p + "input_layernorm.weight": (f + "input_layernorm/scale", False),
            p + "input_layernorm.bias": (f + "input_layernorm/bias", False),
            p + "mlp.fc1.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.fc1.bias": (f + "mlp/fc1/bias", False),
            p + "mlp.fc2.weight": (f + "mlp/fc2/kernel", True),
            p + "mlp.fc2.bias": (f + "mlp/fc2/bias", False),
        })
        return out

    def global_map(self, tie_embeddings: bool):
        return {
            "model.embed_tokens.weight": ("embed_tokens/embedding", False),
            "model.final_layernorm.weight": ("norm/scale", False),
            "model.final_layernorm.bias": ("norm/bias", False),
            "lm_head.weight": ("lm_head/kernel", True),
            "lm_head.bias": ("lm_head/bias", False),
        }


class FalconPolicy(HFCheckpointPolicy):
    """Falcon-7B family (reference ``module_inject/containers/`` falcon +
    ``inference/v2/model_implementations/falcon``): multi-query attention
    (1 KV head) with a FUSED query_key_value tensor, parallel attention+MLP
    over one LayerNorm, GELU fc MLP. The new_decoder_architecture (40B
    grouped ln_attn/ln_mlp) variant is out of scope."""
    arch = "falcon"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        if hf_config.get("new_decoder_architecture"):
            raise ValueError("falcon new_decoder_architecture (40B/180B ln_attn/"
                             "ln_mlp) is not supported; 7B-family only")
        if hf_config.get("alibi"):
            raise ValueError("falcon-rw alibi positions are not supported "
                             "(this model family uses rotary)")
        if not hf_config.get("multi_query", True):
            raise ValueError("falcon multi_query=False uses a per-head "
                             "interleaved fused qkv layout; not supported")
        if hf_config.get("bias"):
            raise ValueError("falcon bias=True checkpoints are not supported "
                             "(bias tensors have no conversion entries)")
        if not hf_config.get("parallel_attn", True):
            raise ValueError("falcon parallel_attn=False (sequential residual "
                             "with post-attention ln) is not supported")
        h = hf_config["hidden_size"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("ffn_hidden_size", 4 * h),
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=1 if hf_config.get("multi_query", True)
            else hf_config["num_attention_heads"],
            max_position_embeddings=hf_config.get("max_position_embeddings", 2048),
            rms_norm_eps=hf_config.get("layer_norm_epsilon", 1e-5),
            rope_theta=hf_config.get("rope_theta", 10000.0),
            tie_word_embeddings=hf_config.get("tie_word_embeddings", True),
            attention_bias=hf_config.get("bias", False),
            attention_out_bias=hf_config.get("bias", False),
            norm_type="layernorm",
            mlp_type="gelu_fc",
            mlp_bias=hf_config.get("bias", False),
            parallel_residual=hf_config.get("parallel_attn", True),
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"transformer.h.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "self_attention.dense.weight": (f + "self_attn/o_proj/kernel", True),
            p + "input_layernorm.weight": (f + "input_layernorm/scale", False),
            p + "input_layernorm.bias": (f + "input_layernorm/bias", False),
            p + "mlp.dense_h_to_4h.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.dense_4h_to_h.weight": (f + "mlp/fc2/kernel", True),
        }

    def special_hf_names(self, layer: int):
        """HF tensors convert_special consumes (streaming conversion buffers
        exactly these, nothing else)."""
        return [f"transformer.h.{layer}.self_attention.query_key_value.weight"]

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """Split the fused MQA query_key_value tensor: rows are
        [nq*hd | hd (k) | hd (v)]."""
        hf = f"transformer.h.{layer}.self_attention.query_key_value.weight"
        w = get_tensor(hf)  # [(nq + 2*nkv) * hd, h]
        hd = cfg.head_dim_
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        f = f"layers_{layer}/self_attn/"
        put(f + "q_proj/kernel", w[:nq * hd].T)
        put(f + "k_proj/kernel", w[nq * hd:(nq + nkv) * hd].T)
        put(f + "v_proj/kernel", w[(nq + nkv) * hd:].T)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        f = f"layers_{layer}/self_attn/"
        qkv = np.concatenate([flat[f + "q_proj/kernel"].T,
                              flat[f + "k_proj/kernel"].T,
                              flat[f + "v_proj/kernel"].T], axis=0)
        return {f"transformer.h.{layer}.self_attention.query_key_value.weight": qkv}

    def global_map(self, tie_embeddings: bool):
        out = {
            "transformer.word_embeddings.weight": ("embed_tokens/embedding", False),
            "transformer.ln_f.weight": ("norm/scale", False),
            "transformer.ln_f.bias": ("norm/bias", False),
        }
        if not tie_embeddings:
            out["lm_head.weight"] = ("lm_head/kernel", True)
        return out


class GPT2Policy(HFCheckpointPolicy):
    """GPT-2 (reference ``module_inject/containers/gpt2.py``): learned
    positions (no offset), pre-LN LayerNorm, gelu_new fc MLP, biases
    everywhere, fused Conv1D ``c_attn`` qkv. HF Conv1D stores weights
    ``[in, out]`` — already the flax kernel layout, so nothing transposes."""
    arch = "gpt2"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        h = hf_config["n_embd"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("n_inner") or 4 * h,
            num_hidden_layers=hf_config["n_layer"],
            num_attention_heads=hf_config["n_head"],
            num_key_value_heads=hf_config["n_head"],
            max_position_embeddings=hf_config.get("n_positions", 1024),
            rms_norm_eps=hf_config.get("layer_norm_epsilon", 1e-5),
            tie_word_embeddings=True,
            attention_bias=True,
            attention_out_bias=True,
            norm_type="layernorm",
            pos_embedding="learned",
            mlp_type="gelu_tanh_fc",  # HF activation_function "gelu_new"
            mlp_bias=True,
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"transformer.h.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "ln_1.weight": (f + "input_layernorm/scale", False),
            p + "ln_1.bias": (f + "input_layernorm/bias", False),
            p + "ln_2.weight": (f + "post_attention_layernorm/scale", False),
            p + "ln_2.bias": (f + "post_attention_layernorm/bias", False),
            p + "attn.c_proj.weight": (f + "self_attn/o_proj/kernel", False),
            p + "attn.c_proj.bias": (f + "self_attn/o_proj/bias", False),
            p + "mlp.c_fc.weight": (f + "mlp/fc1/kernel", False),
            p + "mlp.c_fc.bias": (f + "mlp/fc1/bias", False),
            p + "mlp.c_proj.weight": (f + "mlp/fc2/kernel", False),
            p + "mlp.c_proj.bias": (f + "mlp/fc2/bias", False),
        }

    def special_hf_names(self, layer: int):
        p = f"transformer.h.{layer}.attn.c_attn."
        return [p + "weight", p + "bias"]

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """Split fused c_attn: Conv1D weight [h, 3h] columns are [q | k | v]."""
        p = f"transformer.h.{layer}.attn.c_attn."
        w = get_tensor(p + "weight")  # [h, 3h], already [in, out]
        b = get_tensor(p + "bias")    # [3h]
        h = cfg.hidden_size
        f = f"layers_{layer}/self_attn/"
        for i, proj in enumerate(("q_proj", "k_proj", "v_proj")):
            put(f + f"{proj}/kernel", w[:, i * h:(i + 1) * h])
            put(f + f"{proj}/bias", b[i * h:(i + 1) * h])

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        f = f"layers_{layer}/self_attn/"
        p = f"transformer.h.{layer}.attn.c_attn."
        return {
            p + "weight": np.concatenate(
                [flat[f + f"{x}/kernel"] for x in ("q_proj", "k_proj", "v_proj")], axis=1),
            p + "bias": np.concatenate(
                [flat[f + f"{x}/bias"] for x in ("q_proj", "k_proj", "v_proj")]),
        }

    def global_map(self, tie_embeddings: bool):
        return {
            "transformer.wte.weight": ("embed_tokens/embedding", False),
            "transformer.wpe.weight": ("embed_positions/embedding", False),
            "transformer.ln_f.weight": ("norm/scale", False),
            "transformer.ln_f.bias": ("norm/bias", False),
        }


class GPTNeoXPolicy(HFCheckpointPolicy):
    """GPT-NeoX / Pythia (reference ``module_inject/containers/gptneox.py``):
    partial rotary (rotary_pct), two-norm parallel residual
    (x + attn(ln1 x) + mlp(ln2 x)), per-head-interleaved fused
    query_key_value, biases everywhere, untied embed_out."""
    arch = "gptneox"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        h = hf_config["hidden_size"]
        nq = hf_config["num_attention_heads"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("intermediate_size", 4 * h),
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=nq,
            num_key_value_heads=nq,
            max_position_embeddings=hf_config.get("max_position_embeddings", 2048),
            rms_norm_eps=hf_config.get("layer_norm_eps", 1e-5),
            rope_theta=hf_config.get("rotary_emb_base", 10000.0),
            rotary_dim=int(hf_config.get("rotary_pct", 0.25) * (h // nq)),
            tie_word_embeddings=False,
            attention_bias=True,
            attention_out_bias=True,
            norm_type="layernorm",
            mlp_type="gelu_fc",  # HF hidden_act "gelu" (erf)
            mlp_bias=True,
            parallel_residual=hf_config.get("use_parallel_residual", True),
            parallel_residual_norms=2,
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"gpt_neox.layers.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "input_layernorm.weight": (f + "input_layernorm/scale", False),
            p + "input_layernorm.bias": (f + "input_layernorm/bias", False),
            p + "post_attention_layernorm.weight": (f + "post_attention_layernorm/scale",
                                                    False),
            p + "post_attention_layernorm.bias": (f + "post_attention_layernorm/bias",
                                                  False),
            p + "attention.dense.weight": (f + "self_attn/o_proj/kernel", True),
            p + "attention.dense.bias": (f + "self_attn/o_proj/bias", False),
            p + "mlp.dense_h_to_4h.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.dense_h_to_4h.bias": (f + "mlp/fc1/bias", False),
            p + "mlp.dense_4h_to_h.weight": (f + "mlp/fc2/kernel", True),
            p + "mlp.dense_4h_to_h.bias": (f + "mlp/fc2/bias", False),
        }

    def special_hf_names(self, layer: int):
        p = f"gpt_neox.layers.{layer}.attention.query_key_value."
        return [p + "weight", p + "bias"]

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """Un-interleave fused qkv: rows are grouped PER HEAD as
        [q_i | k_i | v_i] (hd each), unlike falcon's [all q | k | v]."""
        p = f"gpt_neox.layers.{layer}.attention.query_key_value."
        hd = cfg.head_dim_
        nq = cfg.num_attention_heads
        w = get_tensor(p + "weight").reshape(nq, 3, hd, cfg.hidden_size)
        b = get_tensor(p + "bias").reshape(nq, 3, hd)
        f = f"layers_{layer}/self_attn/"
        for i, proj in enumerate(("q_proj", "k_proj", "v_proj")):
            put(f + f"{proj}/kernel", w[:, i].reshape(nq * hd, cfg.hidden_size).T)
            put(f + f"{proj}/bias", b[:, i].reshape(nq * hd))

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        hd = cfg.head_dim_
        nq = cfg.num_attention_heads
        f = f"layers_{layer}/self_attn/"
        w = np.stack([flat[f + f"{x}/kernel"].T.reshape(nq, hd, cfg.hidden_size)
                      for x in ("q_proj", "k_proj", "v_proj")], axis=1)
        b = np.stack([flat[f + f"{x}/bias"].reshape(nq, hd)
                      for x in ("q_proj", "k_proj", "v_proj")], axis=1)
        p = f"gpt_neox.layers.{layer}.attention.query_key_value."
        return {p + "weight": w.reshape(3 * nq * hd, cfg.hidden_size),
                p + "bias": b.reshape(3 * nq * hd)}

    def global_map(self, tie_embeddings: bool):
        return {
            "gpt_neox.embed_in.weight": ("embed_tokens/embedding", False),
            "gpt_neox.final_layer_norm.weight": ("norm/scale", False),
            "gpt_neox.final_layer_norm.bias": ("norm/bias", False),
            "embed_out.weight": ("lm_head/kernel", True),
        }


class InternLMPolicy(HFCheckpointPolicy):
    """InternLM-7B (reference ``module_inject/containers/internlm.py``):
    llama graph plus biases on all four attention projections."""
    arch = "internlm"

    def config_from_hf(self, hf_config):
        cfg = super().config_from_hf(hf_config)
        import dataclasses
        bias = hf_config.get("bias", True)
        return dataclasses.replace(cfg, attention_bias=bias, attention_out_bias=bias)

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        if attention_bias:
            p = f"model.layers.{layer}."
            f = f"layers_{layer}/"
            out[p + "self_attn.o_proj.bias"] = (f + "self_attn/o_proj/bias", False)
        return out


class Phi3Policy(HFCheckpointPolicy):
    """Phi-3 (reference ``inference/v2/model_implementations/phi3``): llama
    graph (rmsnorm, swiglu, untied head) with FUSED qkv_proj and
    gate_up_proj tensors."""
    arch = "phi3"

    def config_from_hf(self, hf_config):
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=hf_config.get("num_key_value_heads",
                                              hf_config["num_attention_heads"]),
            max_position_embeddings=hf_config.get("max_position_embeddings", 4096),
            rms_norm_eps=hf_config.get("rms_norm_eps", 1e-5),
            rope_theta=hf_config.get("rope_theta", 10000.0),
            tie_word_embeddings=hf_config.get("tie_word_embeddings", False),
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "self_attn.o_proj.weight": (f + "self_attn/o_proj/kernel", True),
            p + "mlp.down_proj.weight": (f + "mlp/down_proj/kernel", True),
            p + "input_layernorm.weight": (f + "input_layernorm/weight", False),
            p + "post_attention_layernorm.weight": (f + "post_attention_layernorm/weight",
                                                    False),
        }

    def special_hf_names(self, layer: int):
        p = f"model.layers.{layer}."
        return [p + "self_attn.qkv_proj.weight", p + "mlp.gate_up_proj.weight"]

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """qkv_proj rows are [all q | all k | all v]; gate_up_proj rows are
        [gate | up]."""
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        hd = cfg.head_dim_
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        w = get_tensor(p + "self_attn.qkv_proj.weight")
        put(f + "self_attn/q_proj/kernel", w[:nq * hd].T)
        put(f + "self_attn/k_proj/kernel", w[nq * hd:(nq + nkv) * hd].T)
        put(f + "self_attn/v_proj/kernel", w[(nq + nkv) * hd:].T)
        gu = get_tensor(p + "mlp.gate_up_proj.weight")
        put(f + "mlp/gate_proj/kernel", gu[:cfg.intermediate_size].T)
        put(f + "mlp/up_proj/kernel", gu[cfg.intermediate_size:].T)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "self_attn.qkv_proj.weight": np.concatenate(
                [flat[f + f"self_attn/{x}/kernel"].T for x in ("q_proj", "k_proj", "v_proj")],
                axis=0),
            p + "mlp.gate_up_proj.weight": np.concatenate(
                [flat[f + "mlp/gate_proj/kernel"].T, flat[f + "mlp/up_proj/kernel"].T],
                axis=0),
        }


class BaichuanPolicy(HFCheckpointPolicy):
    """Baichuan-7B: llama graph with a fused W_pack qkv tensor (rows
    [q | k | v]). The 13B variant uses ALiBi positions — not supported."""
    arch = "baichuan"

    def config_from_hf(self, hf_config):
        if hf_config.get("position_embedding", "rope").lower() == "alibi" or \
                hf_config.get("hidden_size", 0) >= 5120:
            raise ValueError("baichuan-13B (ALiBi positions) is not supported; "
                             "7B (rope) only")
        return super().config_from_hf(hf_config)

    def weight_map(self, layer: int, attention_bias: bool = False):
        out = super().weight_map(layer, attention_bias)
        p = f"model.layers.{layer}."
        # qkv arrive fused as W_pack (convert_special)
        for proj in ("q_proj", "k_proj", "v_proj"):
            out.pop(p + f"self_attn.{proj}.weight", None)
        return out

    def special_hf_names(self, layer: int):
        return [f"model.layers.{layer}.self_attn.W_pack.weight"]

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        w = get_tensor(f"model.layers.{layer}.self_attn.W_pack.weight")
        h = cfg.hidden_size
        f = f"layers_{layer}/self_attn/"
        put(f + "q_proj/kernel", w[:h].T)
        put(f + "k_proj/kernel", w[h:2 * h].T)
        put(f + "v_proj/kernel", w[2 * h:].T)

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        f = f"layers_{layer}/self_attn/"
        return {f"model.layers.{layer}.self_attn.W_pack.weight": np.concatenate(
            [flat[f + f"{x}/kernel"].T for x in ("q_proj", "k_proj", "v_proj")], axis=0)}


class BloomPolicy(HFCheckpointPolicy):
    """BLOOM (reference ``module_inject/containers/bloom.py``): ALiBi
    positions, embedding LayerNorm, per-head-interleaved fused
    query_key_value (same layout as NeoX), gelu-tanh MLP, biases
    everywhere, tied embeddings."""
    arch = "bloom"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        if hf_config.get("apply_residual_connection_post_layernorm"):
            raise ValueError("bloom apply_residual_connection_post_layernorm=True "
                             "is not supported (pre-LN residual only)")
        h = hf_config.get("hidden_size") or hf_config["n_embed"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=4 * h,
            num_hidden_layers=hf_config["n_layer"],
            num_attention_heads=hf_config["n_head"],
            num_key_value_heads=hf_config["n_head"],
            max_position_embeddings=hf_config.get("seq_length", 2048),
            rms_norm_eps=hf_config.get("layer_norm_epsilon", 1e-5),
            tie_word_embeddings=True,
            attention_bias=True,
            attention_out_bias=True,
            norm_type="layernorm",
            pos_embedding="alibi",
            embed_layernorm=True,
            mlp_type="gelu_tanh_fc",  # BloomGelu = tanh approximation
            mlp_bias=True,
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"transformer.h.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "input_layernorm.weight": (f + "input_layernorm/scale", False),
            p + "input_layernorm.bias": (f + "input_layernorm/bias", False),
            p + "post_attention_layernorm.weight": (f + "post_attention_layernorm/scale",
                                                    False),
            p + "post_attention_layernorm.bias": (f + "post_attention_layernorm/bias",
                                                  False),
            p + "self_attention.dense.weight": (f + "self_attn/o_proj/kernel", True),
            p + "self_attention.dense.bias": (f + "self_attn/o_proj/bias", False),
            p + "mlp.dense_h_to_4h.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.dense_h_to_4h.bias": (f + "mlp/fc1/bias", False),
            p + "mlp.dense_4h_to_h.weight": (f + "mlp/fc2/kernel", True),
            p + "mlp.dense_4h_to_h.bias": (f + "mlp/fc2/bias", False),
        }

    def special_hf_names(self, layer: int):
        p = f"transformer.h.{layer}.self_attention.query_key_value."
        return [p + "weight", p + "bias"]

    def convert_special(self, layer: int, cfg: LlamaConfig, get_tensor, put):
        """Fused qkv rows are grouped per head as [q_i | k_i | v_i]."""
        p = f"transformer.h.{layer}.self_attention.query_key_value."
        hd = cfg.head_dim_
        nq = cfg.num_attention_heads
        w = get_tensor(p + "weight").reshape(nq, 3, hd, cfg.hidden_size)
        b = get_tensor(p + "bias").reshape(nq, 3, hd)
        f = f"layers_{layer}/self_attn/"
        for i, proj in enumerate(("q_proj", "k_proj", "v_proj")):
            put(f + f"{proj}/kernel", w[:, i].reshape(nq * hd, cfg.hidden_size).T)
            put(f + f"{proj}/bias", b[:, i].reshape(nq * hd))

    def export_special(self, layer: int, cfg: LlamaConfig, flat):
        hd = cfg.head_dim_
        nq = cfg.num_attention_heads
        f = f"layers_{layer}/self_attn/"
        w = np.stack([flat[f + f"{x}/kernel"].T.reshape(nq, hd, cfg.hidden_size)
                      for x in ("q_proj", "k_proj", "v_proj")], axis=1)
        b = np.stack([flat[f + f"{x}/bias"].reshape(nq, hd)
                      for x in ("q_proj", "k_proj", "v_proj")], axis=1)
        p = f"transformer.h.{layer}.self_attention.query_key_value."
        return {p + "weight": w.reshape(3 * nq * hd, cfg.hidden_size),
                p + "bias": b.reshape(3 * nq * hd)}

    def global_map(self, tie_embeddings: bool):
        return {
            "transformer.word_embeddings.weight": ("embed_tokens/embedding", False),
            "transformer.word_embeddings_layernorm.weight": ("embed_layernorm/scale",
                                                             False),
            "transformer.word_embeddings_layernorm.bias": ("embed_layernorm/bias", False),
            "transformer.ln_f.weight": ("norm/scale", False),
            "transformer.ln_f.bias": ("norm/bias", False),
        }


class GPTJPolicy(HFCheckpointPolicy):
    """GPT-J (reference ``module_inject/containers/gptj.py``): interleaved
    (adjacent-pair) partial rotary, single-norm parallel residual, gelu_new
    fc MLP (biased), bias-free attention, untied lm_head WITH bias."""
    arch = "gptj"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        h = hf_config["n_embd"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("n_inner") or 4 * h,
            num_hidden_layers=hf_config["n_layer"],
            num_attention_heads=hf_config["n_head"],
            num_key_value_heads=hf_config["n_head"],
            max_position_embeddings=hf_config.get("n_positions", 2048),
            rms_norm_eps=hf_config.get("layer_norm_epsilon", 1e-5),
            rotary_dim=hf_config.get("rotary_dim", 64),
            rope_interleaved=True,
            tie_word_embeddings=False,
            norm_type="layernorm",
            mlp_type="gelu_tanh_fc",  # HF activation_function "gelu_new"
            mlp_bias=True,
            parallel_residual=True,
            lm_head_bias=True,
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"transformer.h.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "ln_1.weight": (f + "input_layernorm/scale", False),
            p + "ln_1.bias": (f + "input_layernorm/bias", False),
            p + "attn.q_proj.weight": (f + "self_attn/q_proj/kernel", True),
            p + "attn.k_proj.weight": (f + "self_attn/k_proj/kernel", True),
            p + "attn.v_proj.weight": (f + "self_attn/v_proj/kernel", True),
            p + "attn.out_proj.weight": (f + "self_attn/o_proj/kernel", True),
            p + "mlp.fc_in.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.fc_in.bias": (f + "mlp/fc1/bias", False),
            p + "mlp.fc_out.weight": (f + "mlp/fc2/kernel", True),
            p + "mlp.fc_out.bias": (f + "mlp/fc2/bias", False),
        }

    def global_map(self, tie_embeddings: bool):
        return {
            "transformer.wte.weight": ("embed_tokens/embedding", False),
            "transformer.ln_f.weight": ("norm/scale", False),
            "transformer.ln_f.bias": ("norm/bias", False),
            "lm_head.weight": ("lm_head/kernel", True),
            "lm_head.bias": ("lm_head/bias", False),
        }


class GPTNeoPolicy(HFCheckpointPolicy):
    """GPT-Neo (reference ``module_inject/containers/gptneo.py``): learned
    positions, alternating global/LOCAL (sliding-window) attention,
    UNSCALED attention logits (no 1/sqrt(d)), bias-free qkv with biased
    out_proj, gelu_new MLP, tied embeddings."""
    arch = "gptneo"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        h = hf_config["hidden_size"]
        # attention_types [[["global","local"], N]] -> per-layer pattern
        pattern = []
        for spec, count in hf_config.get("attention_types",
                                         [[["global"], hf_config["num_layers"]]]):
            pattern.extend(list(spec) * count)
        local_layers = tuple(i for i, t in enumerate(pattern) if t == "local")
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("intermediate_size") or 4 * h,
            num_hidden_layers=hf_config["num_layers"],
            num_attention_heads=hf_config["num_heads"],
            num_key_value_heads=hf_config["num_heads"],
            max_position_embeddings=hf_config.get("max_position_embeddings", 2048),
            rms_norm_eps=hf_config.get("layer_norm_epsilon", 1e-5),
            tie_word_embeddings=True,
            attention_out_bias=True,
            norm_type="layernorm",
            pos_embedding="learned",
            mlp_type="gelu_tanh_fc",
            mlp_bias=True,
            sliding_window=hf_config.get("window_size", 256) if local_layers else None,
            sliding_window_layers=local_layers or None,
            attn_scale=1.0,  # GPT-Neo does not scale attention logits
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"transformer.h.{layer}."
        f = f"layers_{layer}/"
        return {
            p + "ln_1.weight": (f + "input_layernorm/scale", False),
            p + "ln_1.bias": (f + "input_layernorm/bias", False),
            p + "ln_2.weight": (f + "post_attention_layernorm/scale", False),
            p + "ln_2.bias": (f + "post_attention_layernorm/bias", False),
            p + "attn.attention.q_proj.weight": (f + "self_attn/q_proj/kernel", True),
            p + "attn.attention.k_proj.weight": (f + "self_attn/k_proj/kernel", True),
            p + "attn.attention.v_proj.weight": (f + "self_attn/v_proj/kernel", True),
            p + "attn.attention.out_proj.weight": (f + "self_attn/o_proj/kernel", True),
            p + "attn.attention.out_proj.bias": (f + "self_attn/o_proj/bias", False),
            p + "mlp.c_fc.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.c_fc.bias": (f + "mlp/fc1/bias", False),
            p + "mlp.c_proj.weight": (f + "mlp/fc2/kernel", True),
            p + "mlp.c_proj.bias": (f + "mlp/fc2/bias", False),
        }

    def global_map(self, tie_embeddings: bool):
        return {
            "transformer.wte.weight": ("embed_tokens/embedding", False),
            "transformer.wpe.weight": ("embed_positions/embedding", False),
            "transformer.ln_f.weight": ("norm/scale", False),
            "transformer.ln_f.bias": ("norm/bias", False),
        }


class Starcoder2Policy(HFCheckpointPolicy):
    """StarCoder2: GQA + LayerNorm + biased gelu-tanh fc MLP + sliding
    window + tied embeddings (maps onto existing variant knobs)."""
    arch = "starcoder2"
    col_parallel = ["q_proj", "k_proj", "v_proj", "fc1"]
    row_parallel = ["o_proj", "fc2"]

    def config_from_hf(self, hf_config):
        bias = hf_config.get("use_bias", True)
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=hf_config.get("num_key_value_heads",
                                              hf_config["num_attention_heads"]),
            max_position_embeddings=hf_config.get("max_position_embeddings", 4096),
            rms_norm_eps=hf_config.get("norm_epsilon", 1e-5),
            rope_theta=hf_config.get("rope_theta", 10000.0),
            tie_word_embeddings=hf_config.get("tie_word_embeddings", True),
            attention_bias=bias,
            attention_out_bias=bias,
            norm_type="layernorm",
            mlp_type="gelu_tanh_fc",  # HF "gelu_pytorch_tanh"
            mlp_bias=bias,
            sliding_window=hf_config.get("sliding_window"),
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        out = {}
        for hf, fx in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                       ("v_proj", "v_proj"), ("o_proj", "o_proj")):
            out[p + f"self_attn.{hf}.weight"] = (f + f"self_attn/{fx}/kernel", True)
            if attention_bias:
                out[p + f"self_attn.{hf}.bias"] = (f + f"self_attn/{fx}/bias", False)
        if attention_bias:
            out[p + "mlp.c_fc.bias"] = (f + "mlp/fc1/bias", False)
            out[p + "mlp.c_proj.bias"] = (f + "mlp/fc2/bias", False)
        out.update({
            p + "mlp.c_fc.weight": (f + "mlp/fc1/kernel", True),
            p + "mlp.c_proj.weight": (f + "mlp/fc2/kernel", True),
            p + "input_layernorm.weight": (f + "input_layernorm/scale", False),
            p + "input_layernorm.bias": (f + "input_layernorm/bias", False),
            p + "post_attention_layernorm.weight": (f + "post_attention_layernorm/scale",
                                                    False),
            p + "post_attention_layernorm.bias": (f + "post_attention_layernorm/bias",
                                                  False),
        })
        return out

    def global_map(self, tie_embeddings: bool):
        out = {
            "model.embed_tokens.weight": ("embed_tokens/embedding", False),
            "model.norm.weight": ("norm/scale", False),
            "model.norm.bias": ("norm/bias", False),
        }
        if not tie_embeddings:
            out["lm_head.weight"] = ("lm_head/kernel", True)
        return out


class StableLmPolicy(HFCheckpointPolicy):
    """StableLM: llama graph with LayerNorm(+bias) norms, partial rotary,
    optional qkv biases, untied head."""
    arch = "stablelm"

    def config_from_hf(self, hf_config):
        if hf_config.get("use_parallel_residual"):
            raise ValueError("stablelm use_parallel_residual=True (NeoX form) "
                             "checkpoints are not supported by this policy")
        if hf_config.get("qk_layernorm"):
            raise ValueError("stablelm qk_layernorm=True is not supported")
        hd = hf_config["hidden_size"] // hf_config["num_attention_heads"]
        return LlamaConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            num_key_value_heads=hf_config.get("num_key_value_heads",
                                              hf_config["num_attention_heads"]),
            max_position_embeddings=hf_config.get("max_position_embeddings", 4096),
            rms_norm_eps=hf_config.get("layer_norm_eps", 1e-5),
            rope_theta=hf_config.get("rope_theta", 10000.0),
            rotary_dim=int(hf_config.get("partial_rotary_factor", 0.25) * hd),
            tie_word_embeddings=hf_config.get("tie_word_embeddings", False),
            attention_bias=hf_config.get("use_qkv_bias", False),
            norm_type="layernorm",
        )

    def weight_map(self, layer: int, attention_bias: bool = False):
        p = f"model.layers.{layer}."
        f = f"layers_{layer}/"
        out = {}
        for hf, fx in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                       ("v_proj", "v_proj"), ("o_proj", "o_proj")):
            out[p + f"self_attn.{hf}.weight"] = (f + f"self_attn/{fx}/kernel", True)
        if attention_bias:
            for proj in ("q_proj", "k_proj", "v_proj"):
                out[p + f"self_attn.{proj}.bias"] = (f + f"self_attn/{proj}/bias", False)
        out.update({
            p + "mlp.gate_proj.weight": (f + "mlp/gate_proj/kernel", True),
            p + "mlp.up_proj.weight": (f + "mlp/up_proj/kernel", True),
            p + "mlp.down_proj.weight": (f + "mlp/down_proj/kernel", True),
            p + "input_layernorm.weight": (f + "input_layernorm/scale", False),
            p + "input_layernorm.bias": (f + "input_layernorm/bias", False),
            p + "post_attention_layernorm.weight": (f + "post_attention_layernorm/scale",
                                                    False),
            p + "post_attention_layernorm.bias": (f + "post_attention_layernorm/bias",
                                                  False),
        })
        return out

    def global_map(self, tie_embeddings: bool):
        out = {
            "model.embed_tokens.weight": ("embed_tokens/embedding", False),
            "model.norm.weight": ("norm/scale", False),
            "model.norm.bias": ("norm/bias", False),
        }
        if not tie_embeddings:
            out["lm_head.weight"] = ("lm_head/kernel", True)
        return out


class BertPolicy:
    """BERT encoder (reference ``module_inject/containers/bert.py``
    HFBertLayerPolicy): post-LN bidirectional layers, MLM head tied to the
    word embeddings. Converts HF ``BertForMaskedLM`` into
    ``models/bert.py BertForMaskedLM`` (root-less param tree)."""
    arch = "bert"
    root = None  # flax tree has no "model" wrapper; paths carry "bert/"
    # tied-decoder duplicates + buffers the conversion legitimately skips
    ignored_suffixes = ("cls.predictions.decoder.weight",
                        "cls.predictions.decoder.bias",
                        "embeddings.position_ids",
                        "seq_relationship.weight", "seq_relationship.bias",
                        "pooler.dense.weight", "pooler.dense.bias")
    col_parallel = ["query", "key", "value", "intermediate"]
    row_parallel = ["output", "mlp_output"]

    def config_from_hf(self, hf_config):
        from ..models.bert import BertConfig
        return BertConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_hidden_layers=hf_config["num_hidden_layers"],
            num_attention_heads=hf_config["num_attention_heads"],
            max_position_embeddings=hf_config.get("max_position_embeddings", 512),
            type_vocab_size=hf_config.get("type_vocab_size", 2),
            layer_norm_eps=hf_config.get("layer_norm_eps", 1e-12),
        )

    def weight_map(self, layer: int, attention_bias: bool = True):
        p = f"bert.encoder.layer.{layer}."
        f = f"bert/layer_{layer}/"
        out = {}
        for hf, fx in (("attention.self.query", "attention/query"),
                       ("attention.self.key", "attention/key"),
                       ("attention.self.value", "attention/value"),
                       ("attention.output.dense", "attention/output"),
                       ("intermediate.dense", "intermediate"),
                       ("output.dense", "mlp_output")):
            out[p + hf + ".weight"] = (f + fx + "/kernel", True)
            out[p + hf + ".bias"] = (f + fx + "/bias", False)
        for hf, fx in (("attention.output.LayerNorm", "attention_layernorm"),
                       ("output.LayerNorm", "output_layernorm")):
            out[p + hf + ".weight"] = (f + fx + "/scale", False)
            out[p + hf + ".bias"] = (f + fx + "/bias", False)
        return out

    def global_map(self, tie_embeddings: bool):
        return {
            "bert.embeddings.word_embeddings.weight": ("bert/word_embeddings/embedding",
                                                       False),
            "bert.embeddings.position_embeddings.weight":
                ("bert/position_embeddings/embedding", False),
            "bert.embeddings.token_type_embeddings.weight":
                ("bert/token_type_embeddings/embedding", False),
            "bert.embeddings.LayerNorm.weight": ("bert/embeddings_layernorm/scale", False),
            "bert.embeddings.LayerNorm.bias": ("bert/embeddings_layernorm/bias", False),
            "cls.predictions.transform.dense.weight": ("transform/kernel", True),
            "cls.predictions.transform.dense.bias": ("transform/bias", False),
            "cls.predictions.transform.LayerNorm.weight": ("transform_layernorm/scale",
                                                           False),
            "cls.predictions.transform.LayerNorm.bias": ("transform_layernorm/bias",
                                                         False),
            "cls.predictions.bias": ("decoder_bias", False),
        }


class DistilBertPolicy(BertPolicy):
    """DistilBERT (reference ``module_inject/containers/distil_bert.py``):
    the BERT graph minus token-type embeddings, different HF naming."""
    arch = "distilbert"
    ignored_suffixes = ("vocab_projector.weight", "embeddings.position_ids")

    def config_from_hf(self, hf_config):
        from ..models.bert import BertConfig
        return BertConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["dim"],
            intermediate_size=hf_config["hidden_dim"],
            num_hidden_layers=hf_config["n_layers"],
            num_attention_heads=hf_config["n_heads"],
            max_position_embeddings=hf_config.get("max_position_embeddings", 512),
            layer_norm_eps=1e-12,
            distilbert=True,
        )

    def weight_map(self, layer: int, attention_bias: bool = True):
        p = f"distilbert.transformer.layer.{layer}."
        f = f"bert/layer_{layer}/"
        out = {}
        for hf, fx in (("attention.q_lin", "attention/query"),
                       ("attention.k_lin", "attention/key"),
                       ("attention.v_lin", "attention/value"),
                       ("attention.out_lin", "attention/output"),
                       ("ffn.lin1", "intermediate"),
                       ("ffn.lin2", "mlp_output")):
            out[p + hf + ".weight"] = (f + fx + "/kernel", True)
            out[p + hf + ".bias"] = (f + fx + "/bias", False)
        for hf, fx in (("sa_layer_norm", "attention_layernorm"),
                       ("output_layer_norm", "output_layernorm")):
            out[p + hf + ".weight"] = (f + fx + "/scale", False)
            out[p + hf + ".bias"] = (f + fx + "/bias", False)
        return out

    def global_map(self, tie_embeddings: bool):
        return {
            "distilbert.embeddings.word_embeddings.weight":
                ("bert/word_embeddings/embedding", False),
            "distilbert.embeddings.position_embeddings.weight":
                ("bert/position_embeddings/embedding", False),
            "distilbert.embeddings.LayerNorm.weight": ("bert/embeddings_layernorm/scale",
                                                       False),
            "distilbert.embeddings.LayerNorm.bias": ("bert/embeddings_layernorm/bias",
                                                     False),
            "vocab_transform.weight": ("transform/kernel", True),
            "vocab_transform.bias": ("transform/bias", False),
            "vocab_layer_norm.weight": ("transform_layernorm/scale", False),
            "vocab_layer_norm.bias": ("transform_layernorm/bias", False),
            "vocab_projector.bias": ("decoder_bias", False),
        }


_POLICIES = {
    "llama": LlamaPolicy,
    "LlamaForCausalLM": LlamaPolicy,
    "mistral": MistralPolicy,
    "MistralForCausalLM": MistralPolicy,
    "qwen2": Qwen2Policy,
    "Qwen2ForCausalLM": Qwen2Policy,
    "mixtral": MixtralPolicy,
    "MixtralForCausalLM": MixtralPolicy,
    "olmoe": OlmoePolicy,
    "OlmoeForCausalLM": OlmoePolicy,
    "sdar_moe": SdarMoePolicy,
    "SDARMoeForCausalLM": SdarMoePolicy,
    "KeyeVL2": KeyeVL2Policy,
    "KeyeVL2ForConditionalGeneration": KeyeVL2Policy,
    "bailing_hybrid": BailingHybridPolicy,
    "BailingHybridForCausalLM": BailingHybridPolicy,
    "deepseek_v3": DeepseekV3Policy,
    "DeepseekV3ForCausalLM": DeepseekV3Policy,
    "lfm2_moe": Lfm2MoePolicy,
    "Lfm2MoeForCausalLM": Lfm2MoePolicy,
    "granitemoehybrid": GraniteMoeHybridPolicy,
    "GraniteMoeHybridForCausalLM": GraniteMoeHybridPolicy,
    "qwen2_moe": Qwen2MoePolicy,
    "qwen2moe": Qwen2MoePolicy,
    "Qwen2MoeForCausalLM": Qwen2MoePolicy,
    "qwen3_next": Qwen3NextPolicy,
    "Qwen3NextForCausalLM": Qwen3NextPolicy,
    "ouro": OuroPolicy,
    "OuroForCausalLM": OuroPolicy,
    "gemma": GemmaPolicy,
    "GemmaForCausalLM": GemmaPolicy,
    "gemma2": Gemma2Policy,
    "Gemma2ForCausalLM": Gemma2Policy,
    "opt": OPTPolicy,
    "OPTForCausalLM": OPTPolicy,
    "phi": PhiPolicy,
    "PhiForCausalLM": PhiPolicy,
    "falcon": FalconPolicy,
    "FalconForCausalLM": FalconPolicy,
    "gpt2": GPT2Policy,
    "GPT2LMHeadModel": GPT2Policy,
    "gptneox": GPTNeoXPolicy,
    "gpt_neox": GPTNeoXPolicy,
    "GPTNeoXForCausalLM": GPTNeoXPolicy,
    "internlm": InternLMPolicy,
    "InternLMForCausalLM": InternLMPolicy,
    "phi3": Phi3Policy,
    "Phi3ForCausalLM": Phi3Policy,
    "phi4flash": Phi4FlashPolicy,
    "Phi4FlashForCausalLM": Phi4FlashPolicy,
    "baichuan": BaichuanPolicy,
    "BaichuanForCausalLM": BaichuanPolicy,
    "bloom": BloomPolicy,
    "BloomForCausalLM": BloomPolicy,
    "bert": BertPolicy,
    "BertForMaskedLM": BertPolicy,
    "distilbert": DistilBertPolicy,
    "DistilBertForMaskedLM": DistilBertPolicy,
    "gptj": GPTJPolicy,
    "GPTJForCausalLM": GPTJPolicy,
    "gptneo": GPTNeoPolicy,
    "gpt_neo": GPTNeoPolicy,
    "GPTNeoForCausalLM": GPTNeoPolicy,
    "starcoder2": Starcoder2Policy,
    "Starcoder2ForCausalLM": Starcoder2Policy,
    "stablelm": StableLmPolicy,
    "StableLmForCausalLM": StableLmPolicy,
    "olmo": OlmoPolicy,
    "OlmoForCausalLM": OlmoPolicy,
    "olmo2": Olmo2Policy,
    "Olmo2ForCausalLM": Olmo2Policy,
    "cohere": CoherePolicy,
    "CohereForCausalLM": CoherePolicy,
}

SUPPORTED_ARCHS = sorted({p.arch for p in _POLICIES.values()})


def policy_for(arch_or_model_type: str) -> HFCheckpointPolicy:
    """Reference replace_policy.py generic_policies lookup."""
    pol = _POLICIES.get(arch_or_model_type)
    if pol is None:
        raise ValueError(f"no injection policy for '{arch_or_model_type}'; "
                         f"supported: {SUPPORTED_ARCHS}")
    return pol()
