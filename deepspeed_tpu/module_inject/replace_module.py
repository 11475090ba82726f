"""HF checkpoint conversion + injection entry points.

Reference: ``module_inject/replace_module.py:183 replace_transformer_layer``
— walks an HF torch model replacing decoder layers with fused containers and
sharding weights. TPU equivalent: *convert once* into the native flax param
tree (the fused "container" is the whole jitted model), then serve through
``init_inference`` (TP via AutoTP shardings) or the v2 ragged engine.
"""

from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from ..models.llama import LlamaConfig
from ..utils.logging import logger
from .replace_policy import HFCheckpointPolicy, policy_for


def _nest(flat: Dict[str, np.ndarray]) -> Dict:
    """'a/b/c': x  →  {'a': {'b': {'c': x}}}"""
    out: Dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def _to_numpy(x) -> np.ndarray:
    if hasattr(x, "detach"):  # torch tensor without importing torch
        x = x.detach().cpu().float().numpy()
    return np.asarray(x)


def convert_hf_checkpoint(arch: str,
                          hf_state_dict: Dict[str, Any],
                          hf_config: Dict,
                          dtype=jnp.bfloat16) -> Tuple[LlamaConfig, Dict]:
    """HF state dict (torch tensors or arrays) → (LlamaConfig, flax params
    compatible with models/llama.py + inference/v2)."""
    policy = policy_for(arch)
    cfg = policy.config_from_hf(hf_config)
    flat: Dict[str, np.ndarray] = {}
    consumed = set()

    def take(hf_name: str, flax_path: str, transpose: bool):
        if hf_name not in hf_state_dict:
            raise KeyError(f"HF checkpoint missing '{hf_name}' (arch={arch})")
        w = _to_numpy(hf_state_dict[hf_name])
        if transpose:
            w = w.T  # torch Linear [out,in] → flax kernel [in,out]
        flat[flax_path] = w.astype(np.float32)
        consumed.add(hf_name)

    for hf_name, (flax_path, tr) in policy.global_map(cfg.tie_word_embeddings).items():
        take(hf_name, flax_path, tr)
    for layer in range(cfg.num_hidden_layers):
        for hf_name, (flax_path, tr) in policy.weight_map(
                layer, attention_bias=cfg.attention_bias).items():
            take(hf_name, flax_path, tr)
        if hasattr(policy, "moe_map") and cfg.num_local_experts > 0:
            gate, experts = policy.moe_map(layer, cfg.num_local_experts)
            for hf_name, (flax_path, tr) in gate.items():
                take(hf_name, flax_path, tr)
            for flax_path, hf_names in experts.items():
                stacked = np.stack([_to_numpy(hf_state_dict[n]).T for n in hf_names])
                flat[flax_path] = stacked.astype(np.float32)  # [E, in, out]
                consumed.update(hf_names)
        if hasattr(policy, "convert_special"):
            # fused tensors the plain name map can't express (falcon MQA qkv)
            def get_tensor(name):
                consumed.add(name)
                return _to_numpy(hf_state_dict[name])

            def put(path, arr):
                flat[path] = np.asarray(arr, np.float32)

            policy.convert_special(layer, cfg, get_tensor, put)

    ignored = tuple(getattr(policy, "ignored_suffixes", ())) + ("rotary_emb.inv_freq", )
    leftovers = [k for k in hf_state_dict if k not in consumed
                 and not k.endswith(ignored)]
    if leftovers:
        logger.warning(f"unconverted HF tensors: {leftovers[:8]}"
                       f"{'...' if len(leftovers) > 8 else ''}")

    root = getattr(policy, "root", "model")
    params = {root: _nest(flat)} if root else _nest(flat)
    return cfg, params


def export_hf_checkpoint(arch: str, config: LlamaConfig, params: Dict) -> Dict[str, np.ndarray]:
    """Inverse conversion: flax params → HF-layout state dict (numpy)."""
    policy = policy_for(arch)
    if hasattr(policy, "bind"):     # name maps that depend on the layer's kind
        policy.bind(config)
    flat = {}

    def walk(node, prefix=""):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{prefix}{k}/")
        else:
            flat[prefix[:-1]] = np.asarray(node, dtype=np.float32)

    root = getattr(policy, "root", "model")
    walk(params.get(root, params) if root else params)
    out = {}
    maps = dict(policy.global_map(config.tie_word_embeddings))
    for layer in range(config.num_hidden_layers):
        maps.update(policy.weight_map(layer, attention_bias=config.attention_bias))
        if hasattr(policy, "export_special"):
            out.update(policy.export_special(layer, config, flat))
        if hasattr(policy, "moe_map") and config.num_local_experts > 0:
            gate, experts = policy.moe_map(layer, config.num_local_experts)
            maps.update(gate)
            for flax_path, hf_names in experts.items():
                stacked = flat[flax_path]  # [E, in, out]
                for e, hf_name in enumerate(hf_names):
                    out[hf_name] = stacked[e].T
    for hf_name, (flax_path, transpose) in maps.items():
        w = flat[flax_path]
        out[hf_name] = w.T if transpose else w
    return out


def convert_hf_safetensors(arch: str,
                           model_dir: str,
                           hf_config: Optional[Dict] = None,
                           dtype=jnp.bfloat16) -> Tuple[LlamaConfig, Dict]:
    """Streaming conversion from a safetensors checkpoint directory.

    Tensors are read ONE AT A TIME from each ``*.safetensors`` shard and cast
    to the target dtype immediately, so peak host RAM ≈ the converted tree
    (in `dtype`) + one tensor. The whole-dict path (:func:`convert_hf_checkpoint`)
    holds source fp32 AND converted fp32 simultaneously — a 70B model cannot
    do that on a host. Fused tensors a policy converts via
    ``convert_special`` (falcon qkv) and stacked MoE experts are buffered
    only until their conversion completes.
    """
    import glob
    import json
    import os
    from safetensors import safe_open

    if hf_config is None:
        with open(os.path.join(model_dir, "config.json")) as f:
            hf_config = json.load(f)
    policy = policy_for(arch)
    cfg = policy.config_from_hf(hf_config)
    np_dtype = jnp.dtype(dtype)

    mapping: Dict[str, Tuple[str, bool]] = dict(policy.global_map(cfg.tie_word_embeddings))
    stack_map: Dict[str, Tuple[str, int]] = {}   # hf expert tensor -> (path, e)
    stack_shapes: Dict[str, int] = {}
    for layer in range(cfg.num_hidden_layers):
        mapping.update(policy.weight_map(layer, attention_bias=cfg.attention_bias))
        if hasattr(policy, "moe_map") and cfg.num_local_experts > 0:
            gate, experts = policy.moe_map(layer, cfg.num_local_experts)
            mapping.update(gate)
            for flax_path, hf_names in experts.items():
                stack_shapes[flax_path] = len(hf_names)
                for e, n in enumerate(hf_names):
                    stack_map[n] = (flax_path, e)

    special_names = set()
    if hasattr(policy, "special_hf_names"):
        for layer in range(cfg.num_hidden_layers):
            special_names.update(policy.special_hf_names(layer))

    flat: Dict[str, np.ndarray] = {}
    extras: Dict[str, np.ndarray] = {}  # declared convert_special inputs only
    stack_filled: Dict[str, set] = {}
    skipped = []
    shards = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not shards:
        raise FileNotFoundError(f"no *.safetensors under {model_dir}")
    for shard in shards:
        with safe_open(shard, framework="numpy") as f:
            for name in f.keys():
                if name in mapping:
                    path, tr = mapping[name]
                    w = f.get_tensor(name)
                    flat[path] = (w.T if tr else w).astype(np_dtype)
                elif name in stack_map:
                    path, e = stack_map[name]
                    w = f.get_tensor(name).T
                    if path not in flat:
                        flat[path] = np.empty((stack_shapes[path], *w.shape), np_dtype)
                    flat[path][e] = w.astype(np_dtype)
                    stack_filled.setdefault(path, set()).add(e)
                elif name in special_names:
                    extras[name] = f.get_tensor(name)
                elif not name.endswith("rotary_emb.inv_freq"):
                    skipped.append(name)
    if skipped:
        logger.warning(f"unconverted checkpoint tensors: {skipped[:8]}"
                       f"{'...' if len(skipped) > 8 else ''}")
    if hasattr(policy, "convert_special"):
        for layer in range(cfg.num_hidden_layers):
            def get_tensor(name):
                return extras.pop(name)  # freed as consumed

            def put(path, arr):
                flat[path] = np.asarray(arr).astype(np_dtype)

            policy.convert_special(layer, cfg, get_tensor, put)
    missing = [v[0] for k, v in mapping.items() if v[0] not in flat]
    # np.empty preallocation makes a partially-filled expert stack look
    # present — verify every expert slot was actually written
    for path, n in stack_shapes.items():
        if path not in flat:
            missing.append(path)
        elif len(stack_filled.get(path, ())) != n:
            missing.append(f"{path} (only {len(stack_filled.get(path, ()))}/{n} "
                           f"experts present)")
    if missing:
        raise KeyError(f"checkpoint under {model_dir} is missing tensors for: "
                       f"{missing[:6]}{'...' if len(missing) > 6 else ''}")
    root = getattr(policy, "root", "model")
    return cfg, ({root: _nest(flat)} if root else _nest(flat))


def replace_transformer_layer(arch_or_model_type: str,
                              hf_state_dict: Dict[str, Any],
                              hf_config: Dict,
                              tp_size: int = 1,
                              dtype=jnp.bfloat16):
    """Reference entry name kept (replace_module.py:183): converts the HF
    checkpoint and returns a TP-sharded v1 InferenceEngine over it."""
    import deepspeed_tpu
    cfg, params = convert_hf_checkpoint(arch_or_model_type, hf_state_dict, hf_config,
                                        dtype=dtype)
    from ..models.llama import LlamaForCausalLM
    model = LlamaForCausalLM(cfg)
    return deepspeed_tpu.init_inference(
        model, config={"dtype": "bfloat16" if dtype == jnp.bfloat16 else "float32",
                       "tensor_parallel": {"tp_size": tp_size}},
        params=params)


def merge_peft_adapter(arch: str,
                       config: LlamaConfig,
                       params: Dict,
                       adapter_dir: Optional[str] = None,
                       adapter_state: Optional[Dict[str, Any]] = None,
                       adapter_config: Optional[Dict] = None) -> Dict:
    """Merge a PEFT LoRA adapter into converted flax params, in place.

    The serving-side counterpart of ``linear/optimized_linear.py``'s LoRA
    training (reference deploys adapters by merging before inference):
    every ``...<module>.lora_A.weight`` / ``lora_B.weight`` pair becomes
    ``W += (B @ A) * scaling`` on the matching base weight, located through
    the same policy name maps the checkpoint conversion used — so any
    supported arch accepts adapters with zero per-arch code.

    ``scaling`` follows PEFT: ``lora_alpha / r`` (``lora_alpha / sqrt(r)``
    when ``use_rslora``). Pass either ``adapter_dir`` (reads
    ``adapter_config.json`` + ``adapter_model.safetensors``) or
    ``adapter_state`` (+ ``adapter_config``).
    """
    if adapter_dir is not None:
        import json
        import os
        with open(os.path.join(adapter_dir, "adapter_config.json")) as f:
            adapter_config = json.load(f)
        from safetensors import safe_open
        adapter_state = {}
        with safe_open(os.path.join(adapter_dir, "adapter_model.safetensors"),
                       framework="numpy") as f:
            for k in f.keys():
                adapter_state[k] = f.get_tensor(k)
    if adapter_state is None:
        raise ValueError("pass adapter_dir or adapter_state")
    adapter_config = adapter_config or {}
    r = int(adapter_config.get("r", 8))
    alpha = float(adapter_config.get("lora_alpha", r))
    alpha_pattern = adapter_config.get("alpha_pattern") or {}
    if adapter_config.get("fan_in_fan_out"):
        raise ValueError("fan_in_fan_out adapters are not supported")
    if adapter_config.get("use_dora"):
        raise ValueError("DoRA adapters (use_dora) need magnitude "
                         "renormalization; plain merge would be silently "
                         "wrong — merge with PEFT first")

    def _scaling(module: str, r_m: int) -> float:
        # per-module alpha — PEFT's own pattern rule (get_pattern_key):
        # keys are names OR regexes matched as (^|.*\.)key$ ; per-module
        # rank comes from the tensor itself (rank_pattern-safe)
        import re
        a = alpha
        for key, val in alpha_pattern.items():
            if re.match(rf"(^|.*\.){key}$", module):
                a = float(val)
                break
        return a / (r_m ** 0.5 if adapter_config.get("use_rslora") else r_m)

    policy = policy_for(arch)
    name_map: Dict[str, Tuple[str, bool]] = dict(
        policy.global_map(config.tie_word_embeddings))
    for layer in range(config.num_hidden_layers):
        name_map.update(policy.weight_map(layer,
                                          attention_bias=config.attention_bias))

    # pair up PEFT names: base_model.model.<module>.lora_A[.default].weight
    pairs: Dict[str, Dict[str, np.ndarray]] = {}
    unmatched = []
    for name, w in adapter_state.items():
        for part in ("lora_A", "lora_B"):
            tag = f".{part}."
            if tag in name:
                module = name.split(tag)[0]
                for prefix in ("base_model.model.", "base_model.", ""):
                    if module.startswith(prefix):
                        module = module[len(prefix):]
                        break
                pairs.setdefault(module, {})[part] = _to_numpy(w)
                break
        else:
            unmatched.append(name)
    if unmatched:
        # lora_embedding_A/B, trained biases (bias='lora_only'/'all'),
        # modules_to_save full weights, DoRA magnitudes — dropping any of
        # these would serve silently-wrong logits
        raise ValueError(
            "adapter contains tensors a plain lora_A/lora_B merge cannot "
            f"represent: {unmatched[:6]}{'...' if len(unmatched) > 6 else ''}")

    root = getattr(policy, "root", "model")
    tree = params[root] if root else params
    merged = []
    for module, ab in sorted(pairs.items()):
        if set(ab) != {"lora_A", "lora_B"}:
            raise ValueError(f"adapter module '{module}' missing "
                             f"lora_{'B' if 'lora_A' in ab else 'A'}")
        hf_name = module + ".weight"
        if hf_name not in name_map:
            raise ValueError(
                f"adapter targets '{module}', which has no plain weight "
                f"mapping for arch={arch} (fused/special tensors can't "
                "take merged adapters)")
        flax_path, transpose = name_map[hf_name]
        r_m = ab["lora_A"].shape[0]  # tensor-derived rank (rank_pattern)
        delta = (ab["lora_B"].astype(np.float32)
                 @ ab["lora_A"].astype(np.float32)) * _scaling(module, r_m)
        if transpose:
            delta = delta.T  # flax kernel orientation [in, out]
        node = tree
        parts = flax_path.split("/")
        for p in parts[:-1]:
            node = node[p]
        leaf = node[parts[-1]]
        if tuple(delta.shape) != tuple(leaf.shape):
            raise ValueError(f"adapter delta {delta.shape} != base "
                             f"{tuple(leaf.shape)} for '{module}'")
        node[parts[-1]] = (np.asarray(leaf, np.float32) + delta).astype(
            np.asarray(leaf).dtype)
        merged.append(module)
    if not merged:
        raise ValueError("no lora_A/lora_B tensors found in the adapter")
    logger.info(f"merged LoRA adapter into {len(merged)} modules "
                f"(r={r}, alpha={alpha})")
    return params
