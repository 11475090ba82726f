"""Environment / op-compatibility report.

Reference: ``deepspeed/env_report.py`` (the ``ds_report`` CLI): prints the
op-builder compatibility matrix + torch/cuda versions. TPU version reports
the jax stack, device inventory, mesh capability, and the op registry
(pallas kernels, native AIO) status.
"""

import importlib
import os
import sys


GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"
NO = f"{RED}[NO]{END}"


def _version(mod_name):
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except ImportError:
        return None


def op_report():
    """Op registry status lines (reference op_report: compatible/installed)."""
    from .ops.registry import registry
    # probe ops so their registration side effects run
    from .ops import aio as _aio  # noqa: F401
    _aio.aio_available()
    from .ops import cpu_optim as _cpu_optim  # noqa: F401
    _cpu_optim.cpu_optim_available()
    for mod in ("attention", "normalization", "quantizer", "fused_optimizer",
                "rope",
                "evoformer_attn", "spatial", "cpu_optim", "paged_attention",
                "grouped_matmul", "sampling",
                "sparse_attention.sparse_self_attention"):
        try:
            importlib.import_module(f".ops.{mod}", package=__package__)
        except ImportError:
            pass
    lines = ["-" * 64, "op name " + "." * 40 + " backend  status", "-" * 64]
    for name, info in sorted(registry.report().items()):
        status = OKAY if info.compatible else NO
        lines.append(f"{name} {'.' * max(1, 48 - len(name))} "
                     f"[{info.backend}] {status}")
    return "\n".join(lines)


def debug_report():
    import jax
    lines = []
    lines.append("-" * 64)
    lines.append("DeepSpeed-TPU general environment info:")
    lines.append("-" * 64)
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        v = _version(mod)
        lines.append(f"{mod} version {'.' * max(1, 40 - len(mod))} "
                     f"{v if v else NO}")
    lines.append(f"python version {'.' * 34} {sys.version.split()[0]}")
    try:
        # what a TPU runs at the 0.4B preset's shape, at each chip's call in
        # the benchmark's train-zero3-seq4k cell (Mistral-7B widths) and at
        # the Qwen3-Next cell's: kernels and blocks follow from the shape alone
        from .ops import kernel_dispatch
        lines.append(f"attn dispatch @ bench shape {'.' * 21} "
                     f"{kernel_dispatch.resolved_note()}")
        lines.append(f"attn dispatch @ [1,4096,32/8,128] w4096 {'.' * 9} "
                     + kernel_dispatch.resolved_note(
                         batch=1, seq=4096, heads=32, kv_heads=8,
                         head_dim=128, window=4096))
        # a looped stack's 16,384-token call (the Ouro cell's): the walk is
        # a table of the live tiles, about half of the rectangle's steps
        lines.append(f"attn dispatch @ [1,16384,16/16,128] {'.' * 13} "
                     + kernel_dispatch.resolved_note(
                         batch=1, seq=16384, heads=16, kv_heads=16,
                         head_dim=128))
        # past the fused backward's VMEM cap whole: walked by query ranges
        lines.append(f"attn dispatch @ [1,32768,16/2,256] {'.' * 14} "
                     + kernel_dispatch.resolved_note(
                         batch=1, seq=32768, heads=16, kv_heads=2,
                         head_dim=256))
    except Exception as e:  # pragma: no cover
        lines.append(f"attn dispatch @ bench shape {'.' * 21} {NO} ({e})")
    try:
        # speculative decoding: where drafts come from under the current
        # config — the fused program's on-device ring buffer, or the host
        # prompt-lookup fallback (gate off / per-token oracle path)
        from .inference.v2.config_v2 import SamplingConfig
        scfg = SamplingConfig()
        src = ("device ring-buffer (fused)" if scfg.fused_speculative_decode
               else "host prompt-lookup (per-token fallback)")
        lines.append(f"speculative draft source {'.' * 24} {src}")
    except Exception as e:  # pragma: no cover
        lines.append(f"speculative draft source {'.' * 24} {NO} ({e})")
    try:
        # continuous fused serving: whether the scheduler overlaps prefill
        # + admission with the in-flight fused K-step decode wave, or
        # falls back to the legacy exclusive modes (per-token decode
        # whenever any prefill/arrival work exists)
        from .inference.v2.config_v2 import ContinuousFusionConfig
        ccfg = ContinuousFusionConfig()
        mode = ("overlapped (prefill rides the in-flight wave)"
                if ccfg.enabled else "exclusive (legacy gate)")
        lines.append(f"continuous fused serving {'.' * 24} {mode}")
    except Exception as e:  # pragma: no cover
        lines.append(f"continuous fused serving {'.' * 24} {NO} ({e})")
    try:
        # quantized TP serving: the resolved collective wire dtype (with its
        # precedence source — explicit config > DS_TPU_TP_WIRE env >
        # default) and whether WoQ×TP sharded kernels are available
        from .parallel.tp import resolve_tp_wire
        wire, source = resolve_tp_wire()
        base = wire["attn_out"]
        note = "" if wire["lm_head"] == base else " (lm_head fp)"
        lines.append(f"tp collective wire dtype {'.' * 24} "
                     f"{base}{note} [source: {source}]")
        from .inference.v2.model import check_woq_tp_support  # noqa: F401
        lines.append(f"woq x tp sharded kernels {'.' * 24} "
                     f"available (int8/int4/fp6 shard-major)")
    except Exception as e:  # pragma: no cover
        lines.append(f"tp collective wire dtype {'.' * 24} {NO} ({e})")
    try:
        # radix prefix cache: whether the default engine config would run
        # with cross-request KV reuse (COW forking), and why not when
        # disabled — the sliding-window gate lives in the engine, so here
        # we report the config default + the model-dependent caveat
        from .inference.v2.config_v2 import RaggedInferenceEngineConfig
        ecfg = RaggedInferenceEngineConfig()
        if ecfg.enable_prefix_caching:
            state = ("enabled (radix + COW fork; disabled at runtime "
                     "for sliding-window models)")
        else:
            state = "disabled (state_manager.enable_prefix_caching)"
        lines.append(f"prefix cache {'.' * 36} {state}")
        nt = len(ecfg.tenants)
        lines.append(f"multi-tenant scheduling {'.' * 25} "
                     f"{f'{nt} tenant(s) configured' if nt else 'single lane (no tenants block)'}")
        # multi-LoRA serving: whether the default config builds an adapter
        # registry, the boot-scan dir (DS_ADAPTERS_DIR override), and the
        # bank geometry hot loads must fit inside
        ad = ecfg.adapters
        ad_dir = os.environ.get("DS_ADAPTERS_DIR") or ad.registry_dir
        if ad.enabled:
            state = (f"enabled ({ad.max_live_adapters} slots, "
                     f"rank pad {ad.slot_rank_pad}, "
                     f"targets {','.join(ad.targets)})")
        else:
            state = "disabled (adapters.enabled)"
        lines.append(f"multi-LoRA adapters {'.' * 29} {state}")
        lines.append(f"adapter registry dir {'.' * 28} "
                     f"{ad_dir if ad_dir else 'unset (load via POST /adapters/load)'}")
    except Exception as e:  # pragma: no cover
        lines.append(f"prefix cache {'.' * 36} {NO} ({e})")
    try:
        # durable serving: where the write-ahead request journal would land
        # (env/XDG resolution) and whether that directory is writable — the
        # first thing to check when warm restart isn't replaying anything
        from .inference.v2.journal import journal_dir
        jd = journal_dir()
        writable = os.access(jd if os.path.isdir(jd)
                             else os.path.dirname(jd) or ".", os.W_OK)
        lines.append(f"serving journal dir {'.' * 29} "
                     f"{jd} [{'writable' if writable else 'NOT writable'}]")
    except Exception as e:  # pragma: no cover
        lines.append(f"serving journal dir {'.' * 29} {NO} ({e})")
    try:
        # observability: registry/tracer defaults and where an on-demand
        # jax.profiler capture would land (and whether that dir is writable)
        from .inference.v2.config_v2 import ObservabilityConfig
        from .observability import profile_dir
        ocfg = ObservabilityConfig()
        pd = profile_dir(ocfg.profile_dir)
        writable = os.access(pd if os.path.isdir(pd)
                             else os.path.dirname(pd) or ".", os.W_OK)
        state = ("enabled" if ocfg.enabled else "disabled")
        lines.append(
            f"serving observability {'.' * 27} {state} "
            f"(trace rings {ocfg.trace_requests} req x "
            f"{ocfg.trace_spans_per_request} spans, {ocfg.trace_waves} waves)")
        lines.append(f"profiler capture dir {'.' * 28} "
                     f"{pd} [{'writable' if writable else 'NOT writable'}]")
    except Exception as e:  # pragma: no cover
        lines.append(f"serving observability {'.' * 27} {NO} ({e})")
    try:
        # training observability: which recorders ride the training loop
        # (compile watch / goodput ledger / MFU / memory gauges) and where
        # the Prometheus textfile would land — config > env > disabled
        from .config.feature_configs import TrainObservabilityConfig
        tcfg = TrainObservabilityConfig()
        if tcfg.enabled:
            parts = [n for n, on in (("goodput", tcfg.goodput),
                                     ("compile-watch", tcfg.compile_watch),
                                     ("mfu", tcfg.mfu),
                                     ("memory", tcfg.memory)) if on]
            state = "enabled (" + ", ".join(parts) + ")"
        else:
            state = "disabled"
        lines.append(f"training observability {'.' * 26} {state}")
        tf = tcfg.textfile or os.environ.get("DS_TPU_METRICS_TEXTFILE")
        if tf:
            d = os.path.dirname(os.path.abspath(tf)) or "."
            writable = os.access(d if os.path.isdir(d) else ".", os.W_OK)
            lines.append(f"metrics textfile {'.' * 32} "
                         f"{tf} [{'writable' if writable else 'NOT writable'}]")
        else:
            lines.append(f"metrics textfile {'.' * 32} "
                         f"disabled (set observability.textfile or "
                         f"DS_TPU_METRICS_TEXTFILE)")
    except Exception as e:  # pragma: no cover
        lines.append(f"training observability {'.' * 26} {NO} ({e})")
    try:
        # ZeRO defaults: configured stage and the wire dtype a scheduled
        # stage-3 param gather would move (int8 iff zero_quantized_weights)
        from .config.feature_configs import ZeroConfig
        zc = ZeroConfig()
        lines.append(f"zero stage (default) {'.' * 28} {zc.stage}")
        wire = "int8" if zc.zero_quantized_weights else "fp32"
        lines.append(f"zero3 gather wire dtype {'.' * 25} {wire} "
                     f"(persistence threshold "
                     f"{int(zc.param_persistence_threshold)} elems)")
    except Exception as e:  # pragma: no cover
        lines.append(f"zero defaults {'.' * 35} {NO} ({e})")
    try:
        # disaggregated serving: what the default-config planner would
        # carve THIS host's devices into — the group topology a
        # ``--disagg`` daemon would serve with, or the fallback reason
        from .inference.v2.config_v2 import DisaggregationConfig
        from .inference.v2.disagg import plan_groups
        dcfg = DisaggregationConfig(enabled=True)
        plan = plan_groups(dcfg)
        if plan is not None:
            lines.append(
                f"disagg group topology {'.' * 27} prefill "
                f"{[d.id for d in plan.prefill_devices]} "
                f"(tp={plan.prefill_tp}) | decode "
                f"{[d.id for d in plan.decode_devices]}")
        else:
            lines.append(
                f"disagg group topology {'.' * 27} single group "
                f"({len(jax.local_devices())} device(s) — continuous-"
                f"fusion fallback)")
    except Exception as e:  # pragma: no cover
        lines.append(f"disagg group topology {'.' * 27} {NO} ({e})")
    try:
        devs = jax.devices()
        lines.append(f"platform {'.' * 40} {devs[0].platform}")
        lines.append(f"device count {'.' * 36} {len(devs)}")
        lines.append(f"process count {'.' * 35} {jax.process_count()}")
    except Exception as e:
        lines.append(f"jax devices {'.' * 37} {NO} ({e})")
    return "\n".join(lines)


def main():
    print(op_report())
    print(debug_report())
    return 0


def cli_main():
    sys.exit(main())


if __name__ == "__main__":
    main()
