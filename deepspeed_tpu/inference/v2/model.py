"""Ragged (paged-KV) model implementation for the v2 engine.

Reference: ``deepspeed/inference/v2/model_implementations/
inference_transformer_base.py`` + the ragged kernels under
``inference/v2/kernels/ragged_ops/`` (blocked_flash, linear_blocked_kv_rotary,
logits_gather). TPU design:

- The whole forward is ONE jitted function ``(params, cache, batch) ->
  (logits, cache)`` with the cache donated — the paged-KV write is a single
  scatter of per-token flat slots, history read is a gather of the dense
  block table; both static-shaped (bucketed), MXU-friendly einsums do the
  attention. This replaces the reference's per-op CUDA kernel chain
  (qkv+rotary → blocked flash → moe/mlp → logits_gather).
- Logits are computed only for each sequence's final token
  (reference logits_gather: "saves cost on unembedding").
- Consumes the same param tree as ``models/llama.py`` (the training model) so
  a trained checkpoint serves directly.
"""

import functools
import re
from typing import Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp

def _smap(f, mesh, in_specs, out_specs, manual):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(manual), check_vma=False)

from .config_v2 import KVCacheConfig
from ...models.llama import LlamaConfig, precompute_rope
from ...observability import get_registry
from ...ops.normalization import rms_norm
from ...ops.paged_attention import paged_attention
from ...ops.grouped_matmul import moe_grouped_mlp
from .ragged.ragged_wrapper import RaggedBatch
from .ragged.sequence_descriptor import BaseSequenceDescriptor
from ...ops.registry import interpret_kernels, on_tpu

_obs = get_registry()
_tp_wire_moved = _obs.counter(
    "ds_tp_wire_bytes_moved_total",
    "Receive-side interconnect bytes moved by the per-layer TP output "
    "collectives (reduce-scatter + all-gather two-step, or the "
    "plain-precision psum equivalent when the wire is fp)")
_tp_wire_saved = _obs.counter(
    "ds_tp_wire_bytes_saved_total",
    "Interconnect bytes saved by the blockwise-int8 TP wire vs moving the "
    "same activations at their compute dtype")

_SERVE_COMPILE_WATCH = None


def _serving_compile_watch():
    """Process-wide :class:`~...observability.xla.CompileWatch` for the
    serving compile cache: every bucketed forward / fused-decode /
    fused-spec program shares one watch so ``ds_compiles_total{key}`` /
    ``ds_compile_cache_hits_total{key}`` count across engines."""
    global _SERVE_COMPILE_WATCH
    if _SERVE_COMPILE_WATCH is None:
        from ...observability.xla import CompileWatch
        _SERVE_COMPILE_WATCH = CompileWatch(registry=_obs)
    return _SERVE_COMPILE_WATCH


def _named_program(name: str, fn, **statics):
    """``fn`` with ``statics`` bound, under a ``__name__``: ``jax.jit`` names
    a program's module after it (``jit_<name>`` on the device trace's "XLA
    Modules" line), and a ``functools.partial`` has none (``jit__unknown``)."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs, **statics)
    program.__name__ = program.__qualname__ = name
    return program


def _compile_key_str(key) -> str:
    """Flatten a ``_fwd_cache`` key tuple into a Prometheus-safe label."""
    return re.sub(r"[^0-9A-Za-z_.,:=\[\]()+-]", "", "serve:" + repr(key))


def _kernel(d):
    """Weight accessor: dequantizes WoQ kernels in-graph (XLA fuses the
    dequant into the consuming matmul; HBM holds int8)."""
    k = d["kernel"]
    return k.dequantized() if hasattr(k, "dequantized") else k


def check_woq_tp_support(config: LlamaConfig, quantize, tp_size: int,
                         group_size: int = 512) -> dict:
    """Capability check for weight-quantization × tensor-parallel combos.

    Replaces the former blanket mutual exclusion: packed kernels + their
    per-block scales now shard shard-major along the AutoTP dims, so only
    genuinely unsupported combos are refused — packing granularities the
    quantizer cannot honor, or a combo where NO kernel is shardable (which
    would silently serve a fully-replicated "TP" engine, the failure mode
    the old ValueError guarded against). Kernels that are individually
    non-divisible simply replicate, matching the fp heuristics.

    Returns ``{kernel class: shardable}`` (empty when the combo is trivially
    fine, i.e. no quantization or tp_size == 1); raises ``ValueError`` with
    an actionable message naming the combo otherwise.
    """
    if quantize is None or tp_size <= 1:
        return {}
    combo = f"quantize={quantize!r} x tp={tp_size}"
    if quantize == "int4" and group_size % 2:
        raise ValueError(
            f"unsupported combo {combo}: int4 nibble-packing needs an even "
            f"quantization group_size, got {group_size}")
    if quantize == "fp6" and group_size % 4:
        raise ValueError(
            f"unsupported combo {combo}: fp6 e3m2 packs 4 codes per 3 bytes "
            f"and needs group_size % 4 == 0, got {group_size}")
    hd, nq, nkv = (config.head_dim_, config.num_attention_heads,
                   config.num_key_value_heads)
    shardable = {
        "q_proj/o_proj": (nq * hd) % tp_size == 0,
        "k_proj/v_proj": (nkv * hd) % tp_size == 0,
        "mlp": (config.num_local_experts == 0
                and config.intermediate_size % tp_size == 0),
    }
    if not any(shardable.values()):
        raise ValueError(
            f"unsupported combo {combo}: no quantized kernel is shardable "
            f"(attn q/o dim {nq * hd}, k/v dim {nkv * hd}, mlp intermediate "
            f"{config.intermediate_size}"
            + (" [MoE experts replicate under TP]"
               if config.num_local_experts else "")
            + f" — none divisible by tp={tp_size}), so every chip would hold "
            f"the full quantized model: a silently-replicated 'TP' engine. "
            f"Pick a tp_size dividing the head or MLP dims, or serve "
            f"unquantized.")
    return shardable


def _tp_wire_matmul(x, w, mesh, block: int):
    """Row-parallel output projection with an EXPLICIT quantized-wire
    reduction: local partial matmul → fp32 → blockwise-int8
    reduce-scatter → blockwise-int8 all-gather (comm/bucketing.py wire
    kernels), replacing the plain-precision psum GSPMD would insert. The
    all-gather dequant is deterministic, so every worker reconstructs the
    identical full output — activations stay replicated downstream exactly
    like the implicit path. Quantization residual is dropped (serving has
    no cross-step error-feedback channel).

    ``x`` [T, K] activations (K = the sharded contraction dim), ``w``
    [K, M] row-sharded kernel. Caller guarantees ``K % tp == 0``.
    """
    from jax.sharding import PartitionSpec as P
    from ...comm.bucketing import all_gather_bucket, reduce_scatter_bucket
    T, K = x.shape
    M = w.shape[-1]
    n = T * M
    tp = mesh.shape["model"]
    pad = (-n) % (tp * block)

    def _local(x_l, w_l):
        part = (x_l @ w_l).astype(jnp.float32).reshape(-1)
        if pad:
            part = jnp.concatenate([part, jnp.zeros((pad, ), jnp.float32)])
        shard, _ = reduce_scatter_bucket(part, ("model", ), tier="int8",
                                         block_size=block)
        full = all_gather_bucket(shard, ("model", ), tier="int8",
                                 block_size=block)
        return full[:n].reshape(T, M)

    out = _smap(_local, mesh, (P(None, "model"), P("model", None)),
                P(None, None), {"model"})(x, w)
    return out.astype(x.dtype)


def _rope_tok(x, cos, sin, positions, rotary_dim=None, interleaved=False):
    """Token-major rope: x [T, H, D], positions [T]; partial rotary (Phi)
    rotates only the leading rotary_dim dims; ``interleaved`` = GPT-J
    adjacent-pair layout."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
        return jnp.concatenate([_rope_tok(xr, cos, sin, positions,
                                          interleaved=interleaved), xp],
                               -1).astype(x.dtype)
    c = cos[positions][:, None, :]
    s = sin[positions][:, None, :]
    if interleaved:
        x1, x2 = x[..., ::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


def _norm_tok(x, p, cfg):
    """rmsnorm or layernorm variant per the config (token-major):
    "layernorm" scale+bias, "layernorm_nobias" (Cohere) scale only,
    "layernorm_np" (OLMo) non-parametric."""
    if cfg.norm_type.startswith("layernorm"):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
        if cfg.norm_type == "layernorm":
            out = out * p["scale"] + p["bias"]
        elif cfg.norm_type == "layernorm_nobias":
            out = out * p["scale"]
        return out.astype(x.dtype)
    w = p["weight"]
    if getattr(cfg, "norm_plus_one", False):
        # Gemma stores (weight - 1); the +1 must happen in fp32 — in bf16
        # the ~1e-3 learned deltas round away against 1.0 (HF GemmaRMSNorm
        # also computes (1 + weight.float()) in fp32)
        w = 1.0 + w.astype(jnp.float32)
    return rms_norm(x, w, cfg.rms_norm_eps)


def _mlp_tok(x, lp, cfg, row_out=None, lora_add=None, layer=0):
    """Dense MLP variants (token-major): swiglu | gelu_fc | relu_fc.
    ``row_out(y, kernel, cls)`` routes the row-parallel down-projection —
    the TP wire hook; None = the plain matmul. ``lora_add(y, name, inp,
    layer)`` is the multi-LoRA delta hook on gate/up/down projections;
    None = base weights only."""
    mm = row_out or (lambda y, k, cls: y @ k)
    la = lora_add or (lambda y, name, inp, layer: y)
    mlp = lp["mlp"]
    if cfg.mlp_type in ("swiglu", "geglu_tanh"):
        pre = la(x @ _kernel(mlp["gate_proj"]), "gate_proj", x, layer)
        gate = (jax.nn.silu(pre) if cfg.mlp_type == "swiglu"
                else jax.nn.gelu(pre, approximate=True))
        inner = gate * la(x @ _kernel(mlp["up_proj"]), "up_proj", x, layer)
        return la(mm(inner, _kernel(mlp["down_proj"]), "mlp_out"),
                  "down_proj", inner, layer)
    act = {"gelu_fc": lambda y: jax.nn.gelu(y, approximate=False),
           "gelu_tanh_fc": lambda y: jax.nn.gelu(y, approximate=True),
           "relu_fc": jax.nn.relu}[cfg.mlp_type]
    h = x @ _kernel(mlp["fc1"])
    if "bias" in mlp["fc1"]:
        h = h + mlp["fc1"]["bias"]
    out = mm(act(h), _kernel(mlp["fc2"]), "mlp_out")
    if "bias" in mlp["fc2"]:
        out = out + mlp["fc2"]["bias"]
    return out


class RaggedLlamaModel:
    """Paged-KV decode/prefill model over a Llama param tree."""

    def __init__(self, config: LlamaConfig, params, dtype=jnp.bfloat16, kv_block_size: int = 64,
                 attn_backend: str = "auto", quantize=None, tp_size: int = 1,
                 kv_cache_dtype: Optional[str] = None,
                 tp_wire_dtype: Optional[str] = None,
                 tp_wire_overrides: Optional[dict] = None,
                 tp_wire_block: int = 256,
                 devices=None):
        if config.layer_specs is not None or config.moe_experts_held is not None:
            # this forward is attention + one global FFN a layer over all
            # the router's experts: a convolution's state beside pages
            # (ROADMAP R4) and a share-holding expert layer are training's
            raise NotImplementedError(
                "serving a model with per-layer layer_specs or a share of "
                "the experts (moe_experts_held) is not supported")
        self.config = config
        # explicit device subset (disaggregated serving: each group's
        # engine pins params + KV to its own devices). None = process
        # default placement, byte-identical to the pre-disagg behavior.
        self.devices = tuple(devices) if devices is not None else None
        self.dtype = dtype
        self.kv_block_size = kv_block_size
        if quantize not in (None, "int8", "fp6", "int4"):
            raise ValueError("quantize must be None, 'int8', 'fp6' or 'int4', "
                             f"got {quantize!r}")
        self._quantize = quantize
        if kv_cache_dtype not in (None, "int8", "bfloat16", "float32"):
            raise ValueError("kv_cache_dtype must be None/int8/bfloat16/"
                             f"float32, got {kv_cache_dtype!r}")
        # int8: KV pages stored 1 byte/element + per-slot-vector fp32 scales
        # (vLLM-class KV quantization — beyond the reference's FastGen);
        # dequant happens in-kernel on the paged path
        self._kv_cache_dtype = kv_cache_dtype
        self.tp_size = int(tp_size or 1)
        self._kv_pad = 0  # KV-head padding for nondivisible GQA under TP
        if quantize is not None:
            from ...linear.config import QuantizationConfig as _QC
            check_woq_tp_support(config, quantize, self.tp_size,
                                 _QC().group_size)
        # TP collective wire: explicit tp_wire_dtype > DS_TPU_TP_WIRE env >
        # default "fp" (the bit-identical GSPMD path). Resolved per layer
        # class; an all-fp map leaves the traced program literally untouched.
        from ...parallel.tp import resolve_tp_wire
        self._tp_wire, self._tp_wire_source = resolve_tp_wire(
            tp_wire_dtype, tp_wire_overrides)
        self._wire_block = int(tp_wire_block or 256)
        self._wire_static = (tuple(sorted(self._tp_wire.items()))
                             if self.tp_size > 1 and any(
                                 v == "int8" for v in self._tp_wire.values())
                             else None)
        # "paged" = Pallas blocked-flash decode kernel (TPU only; the tests
        # run it in interpret mode), "dense" = XLA gather of the full
        # history window, "auto" = paged on TPU, dense elsewhere
        if attn_backend == "auto":
            attn_backend = "paged" if on_tpu() else "dense"
        assert attn_backend in ("paged", "dense"), attn_backend
        self._mesh_ctx = None
        self._cache_sharding = None
        if self.tp_size > 1:
            # TP serving (reference FastGen serves TP-sharded): weights are
            # column/row-sharded over the mesh model axis via the AutoTP
            # heuristics; GSPMD propagates head-sharded attention and inserts
            # the per-layer psum on the row-parallel projections
            from ...comm.mesh import (MeshContext, get_mesh_context,
                                      mesh_is_initialized, set_mesh_context)
            if self.devices is not None:
                # disaggregated group: a PRIVATE mesh over exactly these
                # devices — never registered globally, so the prefill and
                # decode groups' TP engines coexist in one process
                if len(self.devices) % self.tp_size != 0:
                    raise ValueError(
                        f"tp_size={self.tp_size} does not divide the "
                        f"{len(self.devices)}-device group")
                ctx = MeshContext.create(
                    axis_sizes={"model": self.tp_size, "data": -1},
                    devices=list(self.devices))
            elif mesh_is_initialized():
                ctx = get_mesh_context()
                if ctx.axis_size("model") != self.tp_size:
                    raise ValueError(
                        f"tp_size={self.tp_size} but the initialized mesh has "
                        f"model={ctx.axis_size('model')} — if that mesh "
                        f"belongs to a discarded engine, call "
                        f"deepspeed_tpu.comm.reset_mesh_context() first")
            else:
                ctx = MeshContext.create(
                    axis_sizes={"model": self.tp_size, "data": -1})
                set_mesh_context(ctx)
            self._mesh_ctx = ctx
            if attn_backend == "paged":
                # a raw pallas_call can't auto-partition under GSPMD, but
                # attention is embarrassingly parallel over heads: the paged
                # branch runs the kernel per head-block inside a
                # partial-manual shard_map (same design as ulysses_flash).
                # KV heads not divisible by tp pad up to the next multiple
                # (reference sharding/attn.py handles uneven head splits;
                # here padded heads carry zero K/V/q and their outputs are
                # sliced off after the kernel). ALiBi stays on the kernel:
                # global-head slopes are computed once and each shard gets
                # its slice through the shard_map, so head identity
                # survives the split.
                rem = config.num_key_value_heads % self.tp_size
                if rem:
                    self._kv_pad = self.tp_size - rem
                    from ...utils.logging import logger
                    logger.info(
                        f"TP serving: kv_heads={config.num_key_value_heads} "
                        f"pads to {config.num_key_value_heads + self._kv_pad} "
                        f"for tp={self.tp_size} (paged kernel keeps running; "
                        f"padded heads are dead weight, not a dense fallback)")
        self.attn_backend = attn_backend
        if self._mesh_ctx is not None:
            # place each leaf DIRECTLY into its TP sharding — a plain
            # jnp.asarray would commit the full tree to one device first,
            # and a model that needs TP to fit per-chip HBM would OOM right
            # there. Host leaves cast on host (ml_dtypes bf16); device
            # leaves reshard then cast per-shard.
            from ...parallel.tp import tp_shardings
            shardings = tp_shardings(params, self._mesh_ctx)

            def _place(x, s):
                if isinstance(x, jax.Array):
                    return jax.device_put(x, s).astype(dtype)
                return jax.device_put(np.asarray(x).astype(dtype), s)

            self.params = jax.tree_util.tree_map(_place, params, shardings)
            # KV cache [2L, slot, KV*D] shards over the folded head dim —
            # each chip holds 1/tp of the cache, the memory point of TP
            # serving (heads are contiguous D-wide strips, so the model-axis
            # split lands on head boundaries). Paged backend: nondivisible
            # KV pads to a tp multiple (above), so the head dim always
            # shards. Dense backend with kv_heads % tp != 0 replicates
            # (correct, larger).
            from jax.sharding import NamedSharding, PartitionSpec as P
            n_kv = config.num_key_value_heads + self._kv_pad
            spec = (P(None, None, "model")
                    if n_kv % self.tp_size == 0 else P())
            self._cache_sharding = NamedSharding(self._mesh_ctx.mesh, spec)
        elif self.devices is not None:
            # single-device group (disagg without TP): COMMIT params to the
            # group's lead device so every jitted forward — and the KV
            # cache it donates — executes there instead of on the process
            # default device
            from jax.sharding import SingleDeviceSharding
            dev = self.devices[0]

            def _place1(x):
                if isinstance(x, jax.Array):
                    return jax.device_put(x, dev).astype(dtype)
                return jax.device_put(np.asarray(x).astype(dtype), dev)

            self.params = jax.tree_util.tree_map(_place1, params)
            self._cache_sharding = SingleDeviceSharding(dev)
        else:
            self.params = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, dtype=dtype), params)
        if quantize is not None:
            # WoQ (reference inference/v2 mixed_gemm + linear/quantization):
            # per-layer matmul weights stored packed (int8 / fp6-e3m2 /
            # int4) + scales, dequantized in-graph. Router gates / norms /
            # embeddings / lm_head stay fp. Under TP the packed values AND
            # per-block scales are laid out SHARD-MAJOR along the same
            # model-axis dim the AutoTP heuristics pick for the fp kernel
            # (parallel/tp.woq_shard_dim), each shard quantized
            # independently so no block crosses a shard boundary — a chip
            # holds 1/tp of the quantized bytes and dequantizes its own
            # segment locally in-graph. Kernels the heuristics would not
            # shard (MoE experts, non-divisible dims) stay flat+replicated.
            from ...linear.config import QuantizationConfig
            from ...linear.quantization import QuantizedParameter
            qcfg = QuantizationConfig(
                q_bits={"int8": 8, "fp6": 6, "int4": 4}[quantize])
            tp = self.tp_size
            if tp > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P
                from ...parallel.tp import woq_shard_dim
                sh_shard = NamedSharding(self._mesh_ctx.mesh, P("model"))
                sh_repl = NamedSharding(self._mesh_ctx.mesh, P())

            def _quantize_one(w, path):
                sd = woq_shard_dim(path, w.shape, tp) if tp > 1 else None
                qp = QuantizedParameter.quantize(
                    w, qcfg, shard_dim=sd,
                    shards=(tp if sd is not None else 1))
                if tp > 1:
                    sh = sh_shard if sd is not None else sh_repl
                    qp = QuantizedParameter(
                        jax.device_put(qp.values, sh),
                        jax.device_put(qp.scales, sh),
                        qp.shape, qp.block_size, qp.dtype, qp.q_bits,
                        qp.shard_dim, qp.shards)
                return qp

            model_p = self.params["model"]
            for lname, lp in model_p.items():
                if not lname.startswith("layers_"):
                    continue
                def _maybe_q(node, prefix):
                    for key, sub in list(node.items()):
                        if key in ("gate", "shared_expert_gate"):
                            continue
                        if isinstance(sub, dict):
                            if "kernel" in sub and getattr(sub["kernel"], "ndim", 0) >= 2:
                                sub["kernel"] = _quantize_one(
                                    sub["kernel"], f"{prefix}/{key}/kernel")
                            else:
                                _maybe_q(sub, f"{prefix}/{key}")
                        elif key in ("w1", "w2", "w3") and getattr(sub, "ndim", 0) >= 2:
                            node[key] = _quantize_one(sub, f"{prefix}/{key}")
                _maybe_q(lp, lname)
        # unembed in fp32 (reference keeps logits fp32; lm_head lives under
        # "model" in the training tree)
        if "lm_head" in params.get("model", {}):
            if self._mesh_ctx is not None:
                # mesh-replicated placement, same shard-first discipline as
                # _place: jnp.asarray would commit to (or keep) one device
                # and clash with the tp-mesh params inside the jitted forward
                from jax.sharding import NamedSharding, PartitionSpec as P
                repl = NamedSharding(self._mesh_ctx.mesh, P())
                fp32_put = lambda x: jax.device_put(
                    np.asarray(x, np.float32) if not isinstance(x, jax.Array)
                    else x, repl).astype(jnp.float32)
            elif self.devices is not None:
                dev0 = self.devices[0]
                fp32_put = lambda x: jax.device_put(
                    np.asarray(x, np.float32) if not isinstance(x, jax.Array)
                    else x, dev0).astype(jnp.float32)
            else:
                fp32_put = lambda x: jnp.asarray(x, jnp.float32)
            self.params["model"]["lm_head"] = jax.tree_util.tree_map(
                fp32_put, params["model"]["lm_head"])
        self._state_manager = None
        self._fwd_cache = {}  # bucket key -> compiled fn
        self._last_dispatch_fn = None  # WatchedJit behind the latest dispatch
        # Multi-LoRA: when an AdapterRegistry is attached, its stacked
        # factor bank rides every dispatch as a TRACED operand (shapes
        # fixed at registry construction), so hot adapter loads never
        # change a compile key
        self._adapters = None

    def set_adapter_registry(self, registry) -> None:
        """Attach the multi-LoRA adapter registry. Must happen before the
        first dispatch: the bank operand is part of every traced program's
        call signature, and attaching later would recompile the world."""
        if self._fwd_cache:
            raise RuntimeError("set_adapter_registry must precede the first "
                               "dispatch (the compiled programs' signatures "
                               "are fixed at trace time)")
        self._adapters = registry

    def _adapter_args(self, n_rows: int, adapter_slots):
        """(bank, per-seq slots [n_rows]) operand pair, or (None, None)
        when no registry is attached. ``adapter_slots=None`` with a
        registry means an all-identity wave (slot 0 everywhere)."""
        if self._adapters is None:
            return None, None
        if adapter_slots is None:
            slots = jnp.zeros(n_rows, jnp.int32)
        else:
            slots = jnp.asarray(adapter_slots, jnp.int32)
        return self._adapters.bank, slots

    # ---- state-manager plumbing (reference inference_model_base) ----

    def set_state_manager(self, state_manager) -> None:
        self._state_manager = state_manager

    def kv_cache_config(self) -> KVCacheConfig:
        cfg = self.config
        return KVCacheConfig(
            block_size=self.kv_block_size,
            cache_shape=(cfg.num_hidden_layers,
                         cfg.num_key_value_heads + self._kv_pad, cfg.head_dim_),
            cache_dtype=(self._kv_cache_dtype
                         or ("bfloat16" if self.dtype == jnp.bfloat16
                             else "float32")),
            cache_sharding=self._cache_sharding)

    # ---- scheduling arithmetic (reference get_kv_requirements) ----

    def get_kv_requirements(self, seq_desc: BaseSequenceDescriptor, max_new_tokens: int,
                            max_new_blocks: int) -> Tuple[int, int]:
        """How many of `max_new_tokens` fit given `max_new_blocks` free blocks;
        returns (schedulable_tokens, blocks_needed)."""
        bs = self.kv_block_size
        total = seq_desc.seen_tokens + max_new_tokens
        req_blocks = (total + bs - 1) // bs - seq_desc.cur_allocated_blocks
        if req_blocks <= max_new_blocks:
            return max_new_tokens, max(0, req_blocks)
        capacity = (seq_desc.cur_allocated_blocks + max_new_blocks) * bs - seq_desc.seen_tokens
        return max(0, capacity), max_new_blocks

    def get_remaining_block_capacity(self, seq_desc: BaseSequenceDescriptor) -> int:
        return seq_desc.cur_allocated_blocks * self.kv_block_size - seq_desc.seen_tokens

    def maybe_allocate_kv(self, seq_desc, n_new_tokens: int) -> None:
        _, req = self.get_kv_requirements(seq_desc, n_new_tokens,
                                          self._state_manager.free_blocks)
        if req > 0:
            seq_desc.extend_kv_cache(self._state_manager.allocate_blocks(req))

    def maybe_free_kv(self, seq_desc) -> None:
        """Mid-sequence trailing-window block release (reference
        ``inference_model_base.py:234`` — the sliding-window example in its
        docstring). Global attention retains every block until flush; when
        ALL layers attend through a local window, tokens at positions
        ``<= seen - W`` can never be attended again, so whole leading blocks
        return to the allocator while the sequence keeps decoding."""
        W = self._uniform_window
        if W is None:
            return
        # the next query position is seen_tokens; the window mask keeps
        # key_pos > q_pos - W, so the first position still reachable is
        # seen - W + 1 — blocks wholly below it are dead
        first_needed = seq_desc.seen_tokens - W + 1
        if first_needed <= 0:
            return
        freed = seq_desc.free_prefix_blocks(first_needed // self.kv_block_size)
        if freed:
            self._state_manager.release_blocks(freed)

    @functools.cached_property
    def _uniform_window(self):
        """max window when EVERY layer attends locally, else None (any
        global layer pins the whole history). Pure function of the config —
        hoisted off the per-token decode path."""
        cfg = self.config
        if cfg.sliding_window is None:
            return None
        from ...models.llama import _layer_window
        windows = [_layer_window(cfg, l) for l in range(cfg.num_hidden_layers)]
        return None if any(w is None for w in windows) else max(windows)

    def prepare_batch(self, batch) -> None:
        pass

    # ---- TP wire accounting (host-side static arithmetic) ----

    def tp_wire_cost(self, n_tokens: int) -> dict:
        """Receive-side interconnect bytes for ONE forward feeding
        ``n_tokens`` tokens through the per-layer TP output collectives.
        Pure host arithmetic mirroring the traced program (the in-graph
        collective can't count itself): per wired row-parallel matmul of
        ``n = n_tokens * hidden`` output elements over a tp-worker ring,
        both collectives of the two-step move ``2*(tp-1)/tp`` of the wire
        array remotely — int8 wire = codes (1 B/elem on the padded length)
        + fp32 scale and zero per ``wire_block``; the fp equivalent moves
        the partial sums at the activation dtype. Returns
        ``{"moved", "fp_equiv", "saved"}`` in bytes.
        """
        if self.tp_size <= 1:
            return {"moved": 0, "fp_equiv": 0, "saved": 0}
        cfg, tp, block = self.config, self.tp_size, self._wire_block
        itemsize = jnp.dtype(self.dtype).itemsize
        factor = 2.0 * (tp - 1) / tp
        classes = []
        if (cfg.num_attention_heads * cfg.head_dim_) % tp == 0:
            classes.append("attn_out")
        if cfg.num_local_experts == 0 and cfg.intermediate_size % tp == 0:
            classes.append("mlp_out")
        moved = fp_equiv = 0.0
        for cls in classes:
            n = n_tokens * cfg.hidden_size
            fp_n = factor * n * itemsize
            if self._tp_wire.get(cls) == "int8":
                n_tot = n + ((-n) % (tp * block))
                m = factor * (n_tot + 8 * (n_tot // block))
            else:
                m = fp_n
            moved += m * cfg.num_hidden_layers
            fp_equiv += fp_n * cfg.num_hidden_layers
        return {"moved": int(moved), "fp_equiv": int(fp_equiv),
                "saved": int(max(0.0, fp_equiv - moved))}

    def _bump_wire_counters(self, n_tokens: int) -> None:
        if self.tp_size <= 1:
            return
        cost = self.tp_wire_cost(n_tokens)
        if cost["moved"]:
            _tp_wire_moved.inc(cost["moved"])
        if cost["saved"]:
            _tp_wire_saved.inc(cost["saved"])
        from ...comm.comms_logging import get_comms_logger
        cl = get_comms_logger()
        if cl.enabled and cost["moved"]:
            tier = ("int8" if any(v == "int8"
                                  for v in self._tp_wire.values()) else "fp")
            cl.append("all_reduce", f"tp_wire[{tier}]", 0.0, cost["moved"],
                      n_participants=self.tp_size)

    # ---- forward ----

    def forward(self, batch: RaggedBatch, window_logits: bool = False,
                adapter_slots=None) -> jax.Array:
        """``window_logits``: return [S, N, vocab] logits for every fed
        token (the speculative verifier's one-pass need) instead of the
        final-token [S, vocab] gather. ``adapter_slots``: per-SEQUENCE
        adapter slot ids [S] (multi-LoRA); None = identity everywhere."""
        kv = self._state_manager.kv_cache
        key = (batch.bucket_key, window_logits)
        fn = self._fwd_cache.get(key)
        if fn is None:
            # under TP the cache's head sharding is pinned on the OUTPUT too:
            # the donated buffer must come back with the same layout or the
            # next step pays a reshard and the donation is wasted (int8
            # caches are a (data, scales) pytree — mirror its real layout)
            kw = ({"out_shardings": (None, jax.tree_util.tree_map(
                       lambda a: a.sharding, kv.cache))}
                  if self._mesh_ctx is not None else {})
            fn = jax.jit(_named_program(
                "ds_ragged_forward", _ragged_forward, config=self.config,
                block_size=self.kv_block_size,
                attn_backend=self.attn_backend,
                tp_size=self.tp_size,
                kv_pad=self._kv_pad,
                tp_wire=self._wire_static,
                wire_block=self._wire_block,
                window_logits=window_logits,
                mesh=(self._mesh_ctx.mesh
                      if self._mesh_ctx is not None else None)),
                         donate_argnums=(1, ), **kw)
            fn = _serving_compile_watch().wrap(fn, _compile_key_str(key))
            self._fwd_cache[key] = fn
        self._last_dispatch_fn = fn
        bank, slots = self._adapter_args(batch.q_tok_idx.shape[0],
                                         adapter_slots)
        if bank is not None:
            logits, new_cache = fn(self.params, kv.cache, batch, bank, slots)
        else:
            logits, new_cache = fn(self.params, kv.cache, batch)
        kv.update(new_cache)
        self._bump_wire_counters(batch.tokens.shape[0])
        return logits

    def cow_copy_block(self, src_block: int, dst_block: int) -> None:
        """Copy one KV block's slots ``src_block`` -> ``dst_block`` inside
        the paged pool: the prefix cache's copy-on-write fork. One jitted
        dynamic gather/scatter along the flat slot axis (the PR-15
        handoff-landing idiom), cache donated so the pool is updated in
        place; block indices are traced operands so every fork reuses the
        same compiled program. Copying the WHOLE block is safe even when
        only the first ``p`` slots are shared: causal attention means those
        slots are bit-identical to what the forking sequence would compute,
        and the stale tail slots are overwritten by the fork's own prefill
        before ``seen_tokens`` ever lets a read touch them."""
        kv = self._state_manager.kv_cache
        fn = self._fwd_cache.get("cow_copy")
        if fn is None:
            def _cow(cache, src, dst, *, block_size):
                def _one(arr):
                    blk = jax.lax.dynamic_slice_in_dim(
                        arr, src * block_size, block_size, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(
                        arr, blk, dst * block_size, axis=1)
                return jax.tree_util.tree_map(_one, cache)

            kw = ({"out_shardings": jax.tree_util.tree_map(
                       lambda a: a.sharding, kv.cache)}
                  if self._mesh_ctx is not None else {})
            fn = jax.jit(_named_program("ds_kv_cow", _cow,
                                        block_size=self.kv_block_size),
                         donate_argnums=(0, ), **kw)
            fn = _serving_compile_watch().wrap(fn, "cow_copy_block")
            self._fwd_cache["cow_copy"] = fn
        kv.update(fn(kv.cache, jnp.int32(src_block), jnp.int32(dst_block)))

    def fused_decode(self, tokens, seq_lens, live, block_table, n_steps: int,
                     sampling: Optional[dict] = None, fetch: bool = True,
                     adapter_slots=None):
        """``n_steps`` decode steps in ONE XLA program (lax.scan over the
        single-token ragged forward). The TPU-native answer to the
        reference v1 engine's CUDA-graph decode capture
        (``inference/engine.py:527 _create_cuda_graph``): where CUDA graphs
        amortize kernel-launch overhead by replaying a recorded decode step,
        this amortizes the per-dispatch host latency by scanning K
        steps inside the compiled program — sampling, KV append and
        position advance all stay on device.

        Host contract: every live row's block table already covers
        ``seq_lens + n_steps`` tokens (the engine pre-allocates); ``live`` is
        0/1 per row (bucket padding rows are 0 — their KV writes drop to the
        OOB slot and their position never advances, exactly like padding in
        the per-step path).

        ``sampling=None`` keeps the original greedy program (argmax
        in-trace, byte-identical compile key) and returns int32
        [n_steps, S] generated tokens (rows of dead sequences repeat their
        input token). With ``sampling`` (a dict of per-row arrays —
        ``keys`` [S, 2] uint32, ``temps``/``top_ps``/``penalties`` [S] f32,
        ``top_ks``/``eos_ids``/``n_out``/``min_new`` [S] int32, optional
        ``seen_mask`` [S, V] bool, and static flags ``want_logprobs``/
        ``use_penalty``/``use_eos_mask``), each scan step runs logit
        controls → ops/sampling.sample_core → feed-back, and the call
        returns ``(toks [n_steps, S], logprobs [n_steps, S], new_keys
        [S, 2])`` in one host transfer.

        ``fetch=False`` returns the same tuple as LAZY device arrays: the
        program is dispatched (JAX dispatch is async) but the host does
        not block on the result — the continuous-fusion scheduler feeds
        prefill chunks while the wave runs, then fetches. The KV cache
        ref is already rebound to the program's (lazy) output, so any
        forward dispatched afterwards serializes behind the wave through
        the donated-cache data dependency."""
        kv = self._state_manager.kv_cache
        total_slots = kv.num_blocks * kv.block_size
        S, B = tokens.shape[0], block_table.shape[1]
        if sampling is None:
            key = ("fused", S, B, n_steps)
            statics = {}
        else:
            statics = {"want_logprobs": bool(sampling["want_logprobs"]),
                       "use_penalty": bool(sampling["use_penalty"]),
                       "use_eos_mask": bool(sampling["use_eos_mask"])}
            key = ("fused_sampled", S, B, n_steps,
                   tuple(sorted(statics.items())))
        fn = self._fwd_cache.get(key)
        if fn is None:
            if self._mesh_ctx is not None:
                cache_sh = jax.tree_util.tree_map(lambda a: a.sharding,
                                                  kv.cache)
                out_sh = ((None, cache_sh) if sampling is None
                          else (None, None, None, cache_sh))
                kw = {"out_shardings": out_sh}
            else:
                kw = {}
            fn = jax.jit(_named_program(
                "ds_fused_decode", _fused_decode_loop, config=self.config,
                block_size=self.kv_block_size,
                attn_backend=self.attn_backend,
                tp_size=self.tp_size,
                kv_pad=self._kv_pad,
                tp_wire=self._wire_static,
                wire_block=self._wire_block,
                total_slots=total_slots,
                n_steps=n_steps,
                sample=sampling is not None,
                **statics,
                mesh=(self._mesh_ctx.mesh
                      if self._mesh_ctx is not None else None)),
                         donate_argnums=(1, ), **kw)
            fn = _serving_compile_watch().wrap(fn, _compile_key_str(key))
            self._fwd_cache[key] = fn
        self._last_dispatch_fn = fn
        args = (self.params, kv.cache, jnp.asarray(tokens),
                jnp.asarray(seq_lens), jnp.asarray(live),
                jnp.asarray(block_table))
        bank, slots = self._adapter_args(S, adapter_slots)
        akw = ({} if bank is None
               else {"adapter_bank": bank, "adapter_slots": slots})
        if sampling is None:
            out, new_cache = fn(*args, **akw)
            kv.update(new_cache)
            self._bump_wire_counters(S * n_steps)
            if not fetch:
                return out
            return np.asarray(out)
        sargs = {k: (jnp.asarray(v) if v is not None else None)
                 for k, v in sampling.items()
                 if k not in ("want_logprobs", "use_penalty", "use_eos_mask")}
        out, lps, new_keys, new_cache = fn(*args, **sargs, **akw)
        kv.update(new_cache)
        self._bump_wire_counters(S * n_steps)
        if not fetch:
            return out, lps, new_keys
        out, lps, new_keys = jax.device_get((out, lps, new_keys))
        return np.asarray(out), np.asarray(lps), np.asarray(new_keys)

    def fused_spec_decode(self, tokens, seq_lens, live, block_table, hist,
                          hist_len, ngrams, max_drafts, n_steps: int,
                          draft_width: int, max_ngram: int,
                          sampling: Optional[dict] = None,
                          fetch: bool = True, adapter_slots=None):
        """``n_steps`` speculative draft/verify windows in ONE XLA program
        — the speculative sibling of ``fused_decode``. Each scan iteration
        drafts up to ``draft_width`` tokens per row from a carried
        token-history ring buffer (``ops/sampling.ngram_draft_ring``),
        feeds ``1 + draft_width`` tokens through the multi-token ragged
        forward with ``window_logits=True``, verifies the drafts on device
        (argmax match for greedy rows, point-mass rejection sampling for
        sampled rows) and advances each row by its accepted length + 1.

        Rollback never leaves the device: KV slots are a pure function of
        position, so a rejected tail's writes are simply overwritten by
        the next window's feed (which always starts at the accepted
        position and spans at least as far) — the host-side
        ``seq.rollback()`` of the per-token path has no fused equivalent
        to pay for.

        Host contract: every live row's block table covers
        ``seq_lens + n_steps * (1 + draft_width)`` tokens (worst case all
        drafts accepted), and the history ring is laid out with the token
        for logical position p at ``hist[:, p % W]``.

        Returns one host fetch: ``(out [n_steps, S, 1+draft_width] int32,
        n_emit [n_steps, S] int32, dlen [n_steps, S] int32, new_keys)``
        where window w of row i emitted ``out[w, i, :n_emit[w, i]]``
        tokens after drafting ``dlen[w, i]`` (accepted = n_emit - 1), and
        ``new_keys`` is None for the greedy program. ``fetch=False``
        returns the same tuple as LAZY device arrays (see
        :meth:`fused_decode`) so the scheduler can overlap host work with
        the in-flight windows."""
        kv = self._state_manager.kv_cache
        total_slots = kv.num_blocks * kv.block_size
        S, B = tokens.shape[0], block_table.shape[1]
        W = hist.shape[1]
        key = ("fused_spec", S, B, W, n_steps, draft_width, max_ngram,
               sampling is not None)
        fn = self._fwd_cache.get(key)
        if fn is None:
            if self._mesh_ctx is not None:
                cache_sh = jax.tree_util.tree_map(lambda a: a.sharding,
                                                  kv.cache)
                out_sh = ((None, None, None, cache_sh) if sampling is None
                          else (None, None, None, None, cache_sh))
                kw = {"out_shardings": out_sh}
            else:
                kw = {}
            fn = jax.jit(_named_program(
                "ds_fused_spec_decode", _fused_spec_decode_loop,
                config=self.config,
                block_size=self.kv_block_size,
                attn_backend=self.attn_backend,
                tp_size=self.tp_size,
                kv_pad=self._kv_pad,
                tp_wire=self._wire_static,
                wire_block=self._wire_block,
                total_slots=total_slots,
                n_steps=n_steps,
                d=draft_width,
                max_ngram=max_ngram,
                sample=sampling is not None,
                mesh=(self._mesh_ctx.mesh
                      if self._mesh_ctx is not None else None)),
                         donate_argnums=(1, ), **kw)
            fn = _serving_compile_watch().wrap(fn, _compile_key_str(key))
            self._fwd_cache[key] = fn
        self._last_dispatch_fn = fn
        args = (self.params, kv.cache, jnp.asarray(tokens),
                jnp.asarray(seq_lens), jnp.asarray(live),
                jnp.asarray(block_table), jnp.asarray(hist),
                jnp.asarray(hist_len), jnp.asarray(ngrams),
                jnp.asarray(max_drafts))
        bank, slots = self._adapter_args(S, adapter_slots)
        akw = ({} if bank is None
               else {"adapter_bank": bank, "adapter_slots": slots})
        if sampling is None:
            out, n_emit, dlen, new_cache = fn(*args, **akw)
            kv.update(new_cache)
            self._bump_wire_counters(S * (1 + draft_width) * n_steps)
            if not fetch:
                return out, n_emit, dlen, None
            out, n_emit, dlen = jax.device_get((out, n_emit, dlen))
            return np.asarray(out), np.asarray(n_emit), np.asarray(dlen), None
        sargs = {k: jnp.asarray(v) for k, v in sampling.items()}
        out, n_emit, dlen, new_keys, new_cache = fn(*args, **sargs, **akw)
        kv.update(new_cache)
        self._bump_wire_counters(S * (1 + draft_width) * n_steps)
        if not fetch:
            return out, n_emit, dlen, new_keys
        out, n_emit, dlen, new_keys = jax.device_get(
            (out, n_emit, dlen, new_keys))
        return (np.asarray(out), np.asarray(n_emit), np.asarray(dlen),
                np.asarray(new_keys))

    def last_wave_flops(self) -> float:
        """XLA cost-analysis FLOPs of the most recently dispatched program
        (the wave just harvested) — the numerator of the serving wave-MFU
        gauge. 0.0 when nothing dispatched yet or the backend exposes no
        cost analysis (the gauge then simply stays unset)."""
        w = self._last_dispatch_fn
        if w is None or not hasattr(w, "program_flops"):
            return 0.0
        try:
            return float(w.program_flops() or 0.0)
        except Exception:  # pragma: no cover — telemetry must not break serving
            return 0.0


def _ragged_forward(params, cache, batch: RaggedBatch, adapter_bank=None,
                    adapter_slots=None, *, config: LlamaConfig,
                    block_size: int, attn_backend: str = "dense",
                    tp_size: int = 1, kv_pad: int = 0, mesh=None,
                    tp_wire=None, wire_block: int = 256,
                    window_logits: bool = False):
    """One ragged step: embed → L×(paged attn + mlp) → final-token logits.

    ``adapter_bank`` (multi-LoRA, traced): ``{"factors": {target: (A
    [n_slots, L, in, r], B [n_slots, L, r, out])}, "scale": [n_slots]}``
    plus ``adapter_slots`` [S] per-sequence slot ids. Each targeted
    projection gains ``y += B[slot] @ (A[slot] @ x) * scale`` via ONE pair
    of grouped GEMMs over the slot-sorted token wave — the sort is hoisted
    here and shared by every layer/target. Slot 0 holds zero factors, so
    identity rows add an exact 0.0 and base streams stay bit-identical."""
    cfg = config
    T = batch.tokens.shape[0]
    S, B = batch.block_table.shape
    L = B * block_size  # history window bucket
    hd, nq, nkv = cfg.head_dim_, cfg.num_attention_heads, cfg.num_key_value_heads
    g = nq // nkv

    # int8 KV: the cache arrives as a (data_i8, scales_f32) pytree — half
    # the KV HBM per token; pages dequantize at read (in-kernel on the
    # paged path)
    kv_quant = isinstance(cache, tuple)
    if kv_quant:
        cache_data, cache_scales = cache
    else:
        cache_data, cache_scales = cache, None

    p = params["model"]
    x = p["embed_tokens"]["embedding"][batch.tokens]  # [T, E]
    if cfg.embed_scale is not None:  # Gemma sqrt(hidden) normalizer
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    if cfg.embed_layernorm:  # BLOOM word_embeddings_layernorm
        x = _norm_tok(x, {"scale": p["embed_layernorm"]["scale"],
                          "bias": p["embed_layernorm"]["bias"]}, cfg)
    if cfg.pos_embedding == "learned":  # OPT (table offset by pos_offset)
        x = x + p["embed_positions"]["embedding"][batch.token_pos + cfg.pos_offset]
    cos, sin = precompute_rope(cfg.rotary_dim or hd, cfg.max_position_embeddings,
                               cfg.rope_theta)

    # per-seq query gather indices come host-precomputed as [S, N] where N
    # buckets the largest burst — N=1 for pure decode, so attention work is
    # S×N×history instead of S×T×history (the decode fast path)
    q_tok_idx = batch.q_tok_idx
    N = q_tok_idx.shape[1]
    seq_lens = batch.seq_seen + batch.seq_n_new  # valid key region per seq

    if attn_backend not in ("paged", "dense"):
        raise ValueError(f"unknown attn_backend {attn_backend!r}")
    if attn_backend == "dense":
        # XLA fallback: gather the full bucketed history window per layer
        j = jnp.arange(L, dtype=jnp.int32)
        slot_grid = batch.block_table[:, j // block_size] * block_size + j % block_size
        n_idx = jnp.arange(N, dtype=jnp.int32)
        q_valid = n_idx[None, :] < batch.seq_n_new[:, None]  # [S, N]
        q_abs = batch.seq_seen[:, None] + n_idx[None, :]
        key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]
        attn_mask = (key_pos <= q_abs[:, :, None]) & \
            (key_pos < seq_lens[:, None, None]) & q_valid[:, :, None]  # [S, N, L]

    # token → (seq, rel) scatter-back indices
    rel = batch.token_pos - batch.seq_seen[batch.token_seq]  # [T]

    # TP wire routing for the row-parallel output projections: a class gated
    # to "int8" rides the explicit quantized two-step (lives inside whatever
    # scan calls this forward); "fp" (or no TP) keeps the plain matmul whose
    # psum GSPMD inserts — byte-identical to the pre-wire program. The
    # lm_head class is accounted but currently a no-op: the unembed is
    # replicated, so no TP reduce exists there to quantize.
    wire = dict(tp_wire) if tp_wire else {}

    def _row_out(y, kern, cls):
        if (wire.get(cls) == "int8" and tp_size > 1 and mesh is not None
                and y.shape[-1] % tp_size == 0):
            return _tp_wire_matmul(y, kern, mesh, wire_block)
        return y @ kern

    # multi-LoRA: hoist the slot sort ONCE per forward (it depends only on
    # the wave's slot assignment), then each targeted projection pays two
    # rank-r grouped GEMMs regardless of how many adapters are live
    lora = None
    if adapter_bank is not None:
        from ...ops.grouped_matmul import lora_grouped_delta, lora_sort_slots
        slots_tok = adapter_slots[batch.token_seq]  # [T] per-token slot
        n_slots = adapter_bank["scale"].shape[0]
        l_order, l_gsz = lora_sort_slots(slots_tok, n_slots)
        l_scale = adapter_bank["scale"][slots_tok][l_order]

        def lora(name, inp, layer):
            ab = adapter_bank["factors"].get(name)
            if ab is None:
                return None
            a, b = ab
            return lora_grouped_delta(inp, a[:, layer], b[:, layer],
                                      l_scale, l_order, l_gsz)

    def _lora_add(y, name, inp, layer):
        if lora is None:
            return y
        d = lora(name, inp, layer)
        return y if d is None else y + d.astype(y.dtype)

    for l in range(cfg.num_hidden_layers):
        lp = p[f"layers_{l}"]
        # post_norm (OLMo2): the raw stream feeds the sublayers, norms land
        # on the sublayer outputs below; None param: OLMo's np-norm
        h = x if cfg.post_norm else _norm_tok(x, lp.get("input_layernorm"), cfg)

        def proj(name, heads, norm=None):
            y = h @ _kernel(lp["self_attn"][name])
            y = _lora_add(y, name, h, l)
            if "bias" in lp["self_attn"][name]:  # qwen2/OPT/Phi biases
                y = y + lp["self_attn"][name]["bias"]
            if cfg.clip_qkv is not None:  # OLMo clamp — BEFORE qk-norm,
                y = jnp.clip(y, -cfg.clip_qkv, cfg.clip_qkv)  # as llama.py
            if norm is not None:  # OLMo2 qk-norm on the FLAT projection
                y = rms_norm(y, lp["self_attn"][norm]["weight"],
                             cfg.rms_norm_eps)
            return y.reshape(T, heads, hd)

        q = proj("q_proj", nq, "q_norm" if cfg.qk_norm else None)
        k = proj("k_proj", nkv, "k_norm" if cfg.qk_norm else None)
        v = proj("v_proj", nkv)
        if cfg.pos_embedding == "rope":
            q = _rope_tok(q, cos, sin, batch.token_pos, cfg.rotary_dim,
                          cfg.rope_interleaved)
            k = _rope_tok(k, cos, sin, batch.token_pos, cfg.rotary_dim,
                          cfg.rope_interleaved)

        # paged write: the cache is [2L, slot, KV*D] (k row 2l, v row 2l+1 —
        # see kv_cache.py: the slot-major fold makes this scatter IN-PLACE
        # on the donated buffer; the old head-major layout forced two
        # whole-cache transposed copies per forward). kv_pad > 0:
        # nondivisible-GQA TP — the cache rides padded KV heads (zeros) so
        # the head dim splits evenly over the model axis
        if kv_pad:
            k_w = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0)))  # [T, KV+p, D]
            v_w = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0)))
        else:
            k_w, v_w = k, v
        KVt = nkv + kv_pad
        if kv_quant:
            # int8 cache: per-slot-vector symmetric quant at write time —
            # one scale per (k|v, head, token) over head_dim; scales are
            # slot-major [2L, slots, KV] so this scatter is in-place too
            for row, w in ((2 * l, k_w), (2 * l + 1, v_w)):
                wf = w.astype(jnp.float32)
                sc = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1) / 127.0, 1e-8)
                w_i8 = jnp.clip(jnp.round(wf / sc[..., None]),
                                -127, 127).astype(jnp.int8)
                cache_data = cache_data.at[row, batch.token_slot, :].set(
                    w_i8.reshape(T, KVt * hd), mode="drop")
                cache_scales = cache_scales.at[row, batch.token_slot, :].set(
                    sc, mode="drop")
        else:
            cache_data = cache_data.at[2 * l, batch.token_slot, :].set(
                k_w.reshape(T, KVt * hd).astype(cache_data.dtype), mode="drop")
            cache_data = cache_data.at[2 * l + 1, batch.token_slot, :].set(
                v_w.reshape(T, KVt * hd).astype(cache_data.dtype), mode="drop")

        # queries head-major [S, N, H, D] (H = KV*G kv-major = the natural
        # q head order); padded KV heads append G zero q heads at the END
        q_s = q[q_tok_idx]  # [S, N, nq, hd]
        if kv_pad:
            q_s = jnp.pad(q_s, ((0, 0), (0, 0), (0, kv_pad * g), (0, 0)))

        if attn_backend == "paged":
            # Pallas blocked-flash: stream the block-table pages, online
            # softmax — no history gather (ops/paged_attention.py); local
            # windows, ALiBi, and custom scale are handled in-kernel
            from ...models.llama import _layer_window
            kernel_kw = dict(page_size=block_size,
                             window=_layer_window(cfg, l),
                             attn_scale=cfg.attn_scale,
                             softcap=cfg.attn_logit_softcapping,
                             interpret=interpret_kernels())
            has_alibi = cfg.pos_embedding == "alibi"
            if tp_size > 1:
                # TP: kernel per LOCAL head block inside a partial-manual
                # shard_map (heads are independent — no collectives); q and
                # the cache shard on their head dims, metadata replicated.
                # ``mesh`` is the model's OWN mesh, threaded in explicitly —
                # a global lookup at retrace time could bind a newer
                # engine's mesh and clash with this jit's pinned shardings.
                # ALiBi: slopes are a GLOBAL-head table sharded alongside
                # the heads, so each shard biases with its true head
                # identity (reference sharding/attn.py).
                from jax.sharding import PartitionSpec as P
                hspec = P(None, None, "model", None)  # q/o [S, N, H, D]
                cspec = P(None, None, "model")  # [2L, slot, KV*D] head fold
                rep = P()
                # optional extra operands ride the shard_map with their own
                # specs: int8 scales shard with the heads, slopes likewise
                extra, extra_specs = [], []
                if kv_quant:
                    extra.append(cache_scales)
                    extra_specs.append(P(None, None, "model"))
                if has_alibi:
                    from ...models.llama import alibi_slopes
                    slopes = jnp.asarray(alibi_slopes(nq)).reshape(nkv, g)
                    if kv_pad:
                        slopes = jnp.pad(slopes, ((0, kv_pad), (0, 0)))
                    extra.append(slopes)
                    extra_specs.append(P("model", None))

                def _paged_local(q_l, cache_l, bt, seen, lens, *rest):
                    rest = list(rest)
                    kw = dict(kernel_kw)
                    if kv_quant:
                        kw["cache_scales"] = rest.pop(0)
                    if has_alibi:
                        kw["slopes"] = rest.pop(0)
                    return paged_attention(q_l, cache_l, l, bt, seen,
                                           lens, **kw)

                ctx = _smap(
                    _paged_local, mesh,
                    tuple([hspec, cspec, rep, rep, rep] + extra_specs),
                    hspec, {"model"},
                )(q_s, cache_data, batch.block_table, batch.seq_seen,
                  seq_lens, *extra)
            else:
                ctx = paged_attention(q_s, cache_data, l, batch.block_table,
                                      batch.seq_seen, seq_lens,
                                      use_alibi=has_alibi,
                                      cache_scales=cache_scales,
                                      **kernel_kw)
            if kv_pad:
                ctx = ctx[:, :, :nq]  # drop the padded heads' outputs
            ctx = ctx.astype(x.dtype).reshape(S, N, nq * hd)
        else:
            # dense backend never pads KV heads (kv_pad is paged-only)
            k_h = cache_data[2 * l][slot_grid].reshape(S, L, nkv, hd)
            v_h = cache_data[2 * l + 1][slot_grid].reshape(S, L, nkv, hd)
            if kv_quant:  # int8: dequant the gathered window
                k_sc = cache_scales[2 * l][slot_grid]       # [S, L, KV]
                v_sc = cache_scales[2 * l + 1][slot_grid]
                k_h = k_h.astype(jnp.float32) * k_sc[..., None]
                v_h = v_h.astype(jnp.float32) * v_sc[..., None]
            k_h = k_h.astype(jnp.float32)  # [S, L, KV, D]
            v_h = v_h.astype(x.dtype)
            qf = q_s.reshape(S, N, nkv, g, hd).astype(jnp.float32)
            scale = (cfg.attn_scale if cfg.attn_scale is not None
                     else 1.0 / float(np.sqrt(hd)))
            scores = jnp.einsum("snkgd,slkd->snkgl", qf, k_h) * jnp.float32(scale)
            if cfg.attn_logit_softcapping is not None:  # Gemma-2, pre-mask
                from ...ops.attention import softcap_scores
                scores = softcap_scores(scores,
                                        jnp.float32(cfg.attn_logit_softcapping))
            from ...models.llama import _layer_window
            window = _layer_window(cfg, l)
            if window is not None:
                # Mistral/GPT-Neo local attention: keys older than the window
                keep = key_pos > q_abs[:, :, None] - window  # [S, N, L]
                scores = jnp.where(keep[:, :, None, None, :], scores, -1e30)
            if cfg.pos_embedding == "alibi":
                from ...models.llama import alibi_slopes
                slopes = jnp.asarray(alibi_slopes(nq)).reshape(nkv, g)
                # [S, N, KV, G, L]: slope_h * (key_pos - query_abs_pos)
                dist = (key_pos[:, :, None, None, :]
                        - q_abs[:, :, None, None, None]).astype(jnp.float32)
                scores = scores + slopes[None, None, :, :, None] * dist
            scores = jnp.where(attn_mask[:, :, None, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            ctx = jnp.einsum("snkgl,slkd->snkgd", probs, v_h).reshape(S, N, nq * hd)

        # back to token-major and project out
        ctx_tok = ctx[batch.token_seq, jnp.clip(rel, 0, N - 1)]  # [T, H*D]
        attn_out = _row_out(ctx_tok, _kernel(lp["self_attn"]["o_proj"]),
                            "attn_out")
        attn_out = _lora_add(attn_out, "o_proj", ctx_tok, l)
        if "bias" in lp["self_attn"]["o_proj"]:
            attn_out = attn_out + lp["self_attn"]["o_proj"]["bias"]

        def _ffn(h_in):
            """Dense MLP or Mixtral-style MoE block (matches models/llama.py)."""
            if cfg.num_local_experts == 0:
                return _mlp_tok(h_in, lp, cfg, _row_out, _lora_add, l)
            moe = lp["block_sparse_moe"]
            logits = h_in.astype(jnp.float32) @ moe["gate"]["kernel"].astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
            if cfg.moe_renormalize:  # Mixtral; Qwen2-MoE keeps raw mass
                w = w / jnp.sum(w, -1, keepdims=True)
            w = w.astype(x.dtype)
            # grouped GEMM: FLOPs ∝ top-k, not E (ops/grouped_matmul.py)
            def _w(name):
                t = moe[name]
                return t.dequantized() if hasattr(t, "dequantized") else t
            moe_out = moe_grouped_mlp(h_in, _w("w1"), _w("w3"), _w("w2"), idx, w)
            if cfg.shared_expert_intermediate_size:  # Qwen2-MoE shared expert
                se = moe["shared_expert"]
                shared = (jax.nn.silu(h_in @ _kernel(se["gate_proj"]))
                          * (h_in @ _kernel(se["up_proj"]))) @ _kernel(se["down_proj"])
                g = h_in.astype(jnp.float32) @ moe["shared_expert_gate"]["kernel"]
                moe_out = moe_out + jax.nn.sigmoid(g).astype(x.dtype) * shared
            return moe_out

        if cfg.sandwich_norm:  # Gemma-2: pre+post norms on both sublayers
            x = x + _norm_tok(attn_out, lp["post_attention_layernorm"], cfg)
            h2 = _norm_tok(x, lp["pre_feedforward_layernorm"], cfg)
            x = x + _norm_tok(_ffn(h2), lp["post_feedforward_layernorm"], cfg)
            continue
        if cfg.post_norm:  # OLMo2: x + norm(attn(x)), then x + norm(ffn(x))
            x = x + _norm_tok(attn_out, lp["post_attention_layernorm"], cfg)
            x = x + _norm_tok(_ffn(x), lp["post_feedforward_layernorm"], cfg)
            continue
        if cfg.parallel_residual:
            # Falcon/Phi: attention and MLP both read the SAME normed input;
            # GPT-NeoX (parallel_residual_norms=2): MLP norms x independently
            h_mlp = (_norm_tok(x, lp.get("post_attention_layernorm"), cfg)
                     if cfg.parallel_residual_norms == 2 else h)
            x = x + attn_out + _ffn(h_mlp)
            continue
        x = x + attn_out
        x = x + _ffn(_norm_tok(x, lp.get("post_attention_layernorm"), cfg))

    x = _norm_tok(x, p.get("norm"), cfg)
    if window_logits:
        # speculative verification: logits for EVERY fed token of each
        # sequence ([S, N, E] via the q_tok_idx bucket) instead of the
        # final-token gather — the verifier needs next-token distributions
        # at all draft positions in ONE pass
        final = x[q_tok_idx].astype(jnp.float32)     # [S, N, E]
    else:
        final = x[batch.last_token_idx].astype(jnp.float32)  # [S, E]
    if cfg.tie_word_embeddings:
        logits = final @ p["embed_tokens"]["embedding"].astype(jnp.float32).T
    else:
        logits = final @ p["lm_head"]["kernel"].astype(jnp.float32)
        if "bias" in p["lm_head"]:  # Phi
            logits = logits + p["lm_head"]["bias"].astype(jnp.float32)
    if cfg.logit_scale is not None:  # Cohere
        logits = logits * jnp.float32(cfg.logit_scale)
    if cfg.final_logit_softcapping is not None:  # Gemma-2
        cap = jnp.float32(cfg.final_logit_softcapping)
        logits = cap * jnp.tanh(logits / cap)
    return logits, ((cache_data, cache_scales) if kv_quant else cache_data)


def _fused_decode_loop(params, cache, tokens, seq_lens, live, block_table,
                       keys=None, temps=None, top_ks=None, top_ps=None,
                       penalties=None, eos_ids=None, n_out=None, min_new=None,
                       seen_mask=None, adapter_bank=None, adapter_slots=None,
                       *,
                       config, block_size, attn_backend, tp_size, kv_pad,
                       total_slots, n_steps, mesh, tp_wire=None,
                       wire_block=256, sample=False,
                       want_logprobs=False, use_penalty=False,
                       use_eos_mask=False):
    """K single-token ragged steps under one lax.scan: each iteration builds
    the pure-decode RaggedBatch **in-trace** (for one new token per sequence
    every field is a function of (block_table, seq_lens, tokens) — compare
    the host fast path in ``ragged_wrapper.py finalize``) and reuses
    ``_ragged_forward`` unchanged, so every model feature (GQA/ALiBi/windows/
    MoE/int8-KV/TP) composes by construction. Dead (padding) rows write to
    the OOB drop slot and never advance — identical to how ``finalize`` pads
    short batches.

    ``sample=False`` is the original greedy program (argmax in-program).
    ``sample=True`` runs the on-device sampler per step (ops/sampling):
    logit controls (repetition penalty over a carried [S, V] presence mask,
    eos masking while ``n_out + step < min_new``) then
    temperature/top-k/top-p Gumbel-max with one key split per row per step
    — the identical op chain the batched per-token dispatch runs, so token
    streams match the per-token path bit-for-bit under the same keys."""
    S, B = block_table.shape
    ar = jnp.arange(S, dtype=jnp.int32)
    live_i = live.astype(jnp.int32)
    if sample:
        from ...ops import sampling as dsamp
        if not use_penalty:
            seen_mask = jnp.zeros((S, 1), bool)  # dead carry, shape-stable

    def body(carry, step):
        cache, toks, lens, keys, seen = carry
        slot = block_table[ar, lens // block_size] * block_size + lens % block_size
        slot = jnp.where(live_i > 0, slot, total_slots)  # padding → scatter drop
        batch = RaggedBatch(
            tokens=toks, token_seq=ar, token_pos=lens, token_slot=slot,
            seq_start=ar, seq_n_new=live_i, seq_seen=lens,
            block_table=block_table, last_token_idx=ar,
            q_tok_idx=ar[:, None])
        logits, cache = _ragged_forward(
            params, cache, batch, adapter_bank, adapter_slots,
            config=config, block_size=block_size,
            attn_backend=attn_backend, tp_size=tp_size, kv_pad=kv_pad,
            mesh=mesh, tp_wire=tp_wire, wire_block=wire_block)
        if not sample:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lps = jnp.zeros(S, jnp.float32)
        else:
            ctrl = dsamp.apply_logit_controls(
                logits,
                seen_mask=seen if use_penalty else None,
                penalties=penalties if use_penalty else None,
                eos_ids=eos_ids if use_eos_mask else None,
                block_eos=((n_out + step) < min_new) if use_eos_mask
                else None)
            nxt, lps, keys = dsamp.sample_core(
                ctrl, keys, temps, top_ks, top_ps,
                want_logprobs=want_logprobs)
        nxt = jnp.where(live_i > 0, nxt, toks)
        if sample and use_penalty:
            # the sampled token joins each row's history set before the
            # next step — exactly the host-side mask rebuild the per-token
            # path performs between dispatches
            seen = seen.at[ar, nxt].set(True)
        lens = lens + live_i
        return (cache, nxt, lens, keys, seen), (nxt, lps)

    if not sample:
        keys = jnp.zeros((S, 2), jnp.uint32)
    carry0 = (cache, tokens, seq_lens, keys, seen_mask if sample
              else jnp.zeros((S, 1), bool))
    (cache, _, _, keys, _), (out, lps) = jax.lax.scan(
        body, carry0, jnp.arange(n_steps, dtype=jnp.int32))
    if not sample:
        return out, cache
    return out, lps, keys, cache


def _fused_spec_decode_loop(params, cache, tokens, seq_lens, live, block_table,
                            hist, hist_len, ngrams, max_drafts,
                            keys=None, temps=None, top_ks=None, top_ps=None,
                            adapter_bank=None, adapter_slots=None, *,
                            config, block_size, attn_backend, tp_size, kv_pad,
                            total_slots, n_steps, d, max_ngram, mesh,
                            tp_wire=None, wire_block=256, sample=False):
    """K speculative windows under one lax.scan — the speculative sibling
    of ``_fused_decode_loop``. Each iteration: draft from the carried
    history ring, build the multi-token RaggedBatch **in-trace** (1+d
    tokens per row; token-major fields of length S*(1+d); per-position KV
    slots from the carried ``lens`` — writes past the accepted length are
    overwritten by the next window, which is the whole on-device rollback
    story), run ``_ragged_forward`` with ``window_logits=True``, verify on
    device, append the emitted tokens to the ring, and advance ``lens`` by
    the per-row emit count. Dead (padding) rows scatter to the OOB drop
    slot, emit nothing, and never advance.

    ``sample=False`` verifies by exact argmax match — byte-identical to
    the host ``accept_drafts`` — with no sort/filter/PRNG work in the
    trace. ``sample=True`` runs ``ops/sampling.spec_verify_window``
    (rejection sampling against the point-mass drafts, one key split per
    row per WINDOW), the same function the host fallback applies
    row-at-a-time, so streams agree bit-for-bit under the same keys."""
    from ...ops import sampling as dsamp
    S, B = block_table.shape
    Np1 = 1 + d
    ar = jnp.arange(S, dtype=jnp.int32)
    jw = jnp.arange(Np1, dtype=jnp.int32)
    live_i = live.astype(jnp.int32)

    def body(carry, _):
        cache, toks, lens, hist, hlen, keys = carry
        drafts, dlen = dsamp.ngram_draft_ring(
            hist, hlen, ngrams, max_drafts, max_ngram=max_ngram, d=d)
        dlen = jnp.where(live_i > 0, dlen, 0)
        feed = jnp.concatenate([toks[:, None], drafts], axis=1)   # [S, 1+d]
        pos = lens[:, None] + jw[None, :]
        slot = (block_table[ar[:, None], pos // block_size] * block_size
                + pos % block_size)
        slot = jnp.where(live_i[:, None] > 0, slot, total_slots)
        batch = RaggedBatch(
            tokens=feed.reshape(-1), token_seq=jnp.repeat(ar, Np1),
            token_pos=pos.reshape(-1), token_slot=slot.reshape(-1),
            seq_start=ar * Np1, seq_n_new=live_i * Np1, seq_seen=lens,
            block_table=block_table, last_token_idx=ar * Np1,
            q_tok_idx=(ar * Np1)[:, None] + jw[None, :])
        logits, cache = _ragged_forward(
            params, cache, batch, adapter_bank, adapter_slots,
            config=config, block_size=block_size,
            attn_backend=attn_backend, tp_size=tp_size, kv_pad=kv_pad,
            mesh=mesh, tp_wire=tp_wire, wire_block=wire_block,
            window_logits=True)                          # [S, 1+d, V]
        if sample:
            out, n_emit, keys = dsamp.spec_verify_window(
                logits, drafts, dlen, keys, temps, top_ks, top_ps, d=d)
        else:
            g_tok = jnp.argmax(logits.astype(jnp.float32),
                               axis=-1).astype(jnp.int32)         # [S, 1+d]
            acc = (drafts == g_tok[:, :d]) & (jw[None, :d] < dlen[:, None])
            m = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                        axis=1).astype(jnp.int32)
            corr = g_tok[ar, m]
            drafts_pad = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)
            out = jnp.where(jw[None, :] < m[:, None], drafts_pad,
                            corr[:, None])
            n_emit = m + 1
        n_emit = jnp.where(live_i > 0, n_emit, 0)
        last = out[ar, jnp.maximum(n_emit - 1, 0)]
        toks = jnp.where(live_i > 0, last, toks)
        hist, hlen = dsamp.ring_append(hist, hlen, out, n_emit)
        lens = lens + n_emit
        return (cache, toks, lens, hist, hlen, keys), (out, n_emit, dlen)

    if not sample:
        keys = jnp.zeros((S, 2), jnp.uint32)
    carry0 = (cache, tokens, seq_lens, hist, hist_len, keys)
    (cache, _, _, _, _, keys), (out, n_emit, dlen) = jax.lax.scan(
        body, carry0, jnp.arange(n_steps, dtype=jnp.int32))
    if not sample:
        return out, n_emit, dlen, cache
    return out, n_emit, dlen, keys, cache
