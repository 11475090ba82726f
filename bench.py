"""Benchmark: flagship-model training throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: training tokens/sec/chip for a ~0.4B-param Llama-class model
(bf16 compute, fp32 master weights, full fused train step). ``vs_baseline``
reports model FLOPs utilization (MFU, 6*N*T/peak) relative to the reference's
best published sustained utilization (54% of peak on A100,
blogs/deepspeed-ulysses/README.md:82-83) — i.e. vs_baseline = our_MFU / 0.54.

Structure: the measurement runs in a *child* process, so the parent never
holds the chip and can salvage the best rung a timed-out ladder printed.
It measures the chip or it fails: no TPU, or a child that fails, is a
non-zero exit — never a host-CPU number and never an older result.
"""

import json
import os
import subprocess
import sys
import time

def env_flag(name: str) -> bool:
    """Conventional env bool: unset/empty/'0' are off (raw truthiness would
    read DS_BENCH_FAST=0 as ON)."""
    return os.environ.get(name, "") not in ("", "0")


def _folded_attn_resolved() -> bool:
    """Whether the folded flash kernels will ACTUALLY run — the env override
    as ops.attention._use_folded resolves it, not the raw env var: the
    unit tag keys A/B comparisons, so it must describe the resolved
    variant."""
    try:
        from deepspeed_tpu.ops.attention import _use_folded
        return _use_folded()
    except Exception:
        return env_flag("DS_TPU_FLASH_FOLDED")


def _attn_dispatch_note(cfg, batch, seq) -> str:
    """Resolved per-leg kernel choices at THIS rung's shape
    (ops/kernel_dispatch: measured cache > heuristic table > legacy env)
    — e.g. ``attn[fwd=xla:heuristic,bwd=pallas@256x512:measured]``.
    Banked in every artifact so a number can never be replayed against
    different kernels than the ones that earned it."""
    try:
        from deepspeed_tpu.ops import kernel_dispatch
        return kernel_dispatch.resolved_note(
            batch=batch, seq=seq, heads=cfg.num_attention_heads,
            kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim_,
            dtype="bfloat16", causal=True)
    except Exception:
        return "attn[folded]" if _folded_attn_resolved() else "attn[?]"


# the ladder compiles up to four footprints inside ONE child — the timeout
# is a backstop, not the budget: the child prints each improvement as it
# lands and the parent salvages the last line on timeout, so a mid-ladder
# kill still records the best completed rung
ATTEMPT_TIMEOUT = 1800


def bench_config(remat=False, heads=None, **overrides):
    """THE bench model: ~0.4B params, sized to fit one v5e chip (16 GB HBM)
    with Adam fp32 states. ce_chunk_size: streamed unembed+CE
    (ops/chunked_ce.py) — the [tokens, 32k] logits tensor (2.1 GB fp32 at
    bs16) never materializes, which is what lets the bigger MXU footprints
    fit. Single source of truth for measure() and breakdown() so their
    labels can't drift."""
    from deepspeed_tpu.models import LlamaConfig

    policy = remat if isinstance(remat, str) else None
    kw = dict(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
              num_hidden_layers=24, num_attention_heads=16,
              num_key_value_heads=16, max_position_embeddings=2048,
              remat=bool(remat), remat_policy=policy, ce_chunk_size=8000)
    if heads is not None:
        # head-count override at the SAME hidden size: 8h x hd128 keeps
        # params and FLOPs identical to 16h x hd64 (d_attn = 1024 either
        # way) but contracts the flash q.kT matmul over the MXU's full
        # 128-deep K dim. One mapping here so the ladder rung and the
        # mem_triage probe can't compile different HLO.
        kw.update(num_attention_heads=heads, num_key_value_heads=heads)
    # scan_layers accepts the ladder's scan value directly: False/True, or an
    # int chunk size N>1 = scan over chunks of N unrolled layers — the
    # compile-time/perf middle ground between per-layer scan (~10x less HLO,
    # least scheduling freedom) and fully unrolled (the >=25-min compile)
    scan = overrides.pop("scan_layers", False)
    if isinstance(scan, int) and not isinstance(scan, bool) and scan > 1:
        kw.update(scan_layers=True, scan_chunk_size=scan)
    else:
        kw.update(scan_layers=bool(scan))
    kw.update(overrides)
    return LlamaConfig(**kw)


def large_bench_config(remat=True, **overrides):
    """The LARGE rung (~1.36B params): the MFU claim shouldn't rest on the
    0.4B proxy. hidden 2048 / 24 layers / intermediate 5632 / 16h x hd128 —
    resident fp32 Adam states alone are ~21 GB, past a 16 GB v5e chip, so
    the rung structurally REQUIRES remat plus CPU-offloaded master/optimizer
    states (the ZeRO-Offload configuration this repo exists to exercise);
    it is not a tuned-down version of the small model that happens to fit."""
    from deepspeed_tpu.models import LlamaConfig

    policy = remat if isinstance(remat, str) else None
    kw = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
              num_hidden_layers=24, num_attention_heads=16,
              num_key_value_heads=16, max_position_embeddings=2048,
              remat=bool(remat), remat_policy=policy, ce_chunk_size=8000)
    scan = overrides.pop("scan_layers", True)
    if isinstance(scan, int) and not isinstance(scan, bool) and scan > 1:
        kw.update(scan_layers=True, scan_chunk_size=scan)
    else:
        kw.update(scan_layers=bool(scan))
    kw.update(overrides)
    return LlamaConfig(**kw)


def large_bench_engine_config(batch):
    """Engine config for the large rung: the bench base plus ZeRO-2 with
    CPU-offloaded optimizer states — on one chip the sharding is degenerate
    but the offload path (host master weights, device _offload_prep) is the
    point of the measurement."""
    cfg = bench_engine_config(batch)
    cfg["zero_optimization"] = {"stage": 2,
                                "offload_optimizer": {"device": "cpu"}}
    return cfg


def bench_engine_config(batch):
    """Single source of truth for the bench engine's DS config: every
    rung and probe lowers byte-identical HLO, which is what makes a
    persistent-cache pre-warm real."""
    return {"train_batch_size": batch,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            # llama threads dtype through every use site, so the fp32->bf16
            # cast happens per scan chunk inside the model — kills the
            # whole-model-sized convert_element_type temps that OOMed a
            # 16 GB chip in round 4
            "param_cast": "model",
            # async step pipeline: loss/overflow stay device scalars between
            # sync windows — no per-step float(loss)/effects_barrier stall in
            # the timed loop (host-side only: the compiled HLO is unchanged,
            # preserving the mem_triage byte-identity contract)
            "async_pipeline": {"enabled": True, "sync_interval": 16},
            # persistent XLA compile cache: runtime/compiler.py decides
            # ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
            "compile": {},
            "steps_per_print": 0}


def _measure_config(batch, seq, iters, remat, scan=False, heads=None,
                    large=False):
    """One measurement at a given batch/remat setting; raises on OOM so the
    caller can fall back to a smaller footprint. ``remat`` is False, True
    (full recompute) or a jax.checkpoint_policies name (selective remat —
    bigger batches without full-remat's recompute tax). ``scan`` compiles
    the 24 layers as one nn.scan body (numerics-identical, tested) — ~10x
    less HLO to compile, which matters when a chip call's time limit is
    shorter than the unrolled compile. ``heads`` overrides the head count at the
    SAME hidden size: 8 heads x hd128 has identical params and FLOPs to
    the default 16 x hd64 (d_attn = 1024 either way) but contracts the
    flash q.kT matmul over 128 elements — the MXU's full K depth — where
    hd64 wastes half of it. Apples-to-apples on MFU, friendlier silicon."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, init_llama

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py measures the chip: JAX found no TPU "
                 f"(platform {platform!r})")
    if large:
        # ~1.36B rung: remat + offloaded master states are structural (the
        # fp32 Adam states alone exceed a 16 GB chip), not a fallback
        cfg = large_bench_config(remat, scan_layers=scan,
                                 max_position_embeddings=max(2048, seq))
    else:
        cfg = bench_config(remat, heads=heads, scan_layers=scan,
                           max_position_embeddings=max(2048, seq))

    model, params = init_llama(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=(large_bench_engine_config(batch) if large
                else bench_engine_config(batch)))

    rng = np.random.default_rng(0)
    # pre-stage batches on device: host->device transfers inside the timed
    # loop serialize against the device and skew the measurement
    pool = [jax.device_put(jnp.asarray(rng.integers(0, cfg.vocab_size, size=(batch, seq)),
                                       dtype=jnp.int32)) for _ in range(4)]

    # DS_BENCH_MULTISTEP=K: K optimizer steps per DISPATCH (one lax.scan
    # program, engine.fused_train_steps) — isolates per-dispatch host
    # latency from on-chip step time. If tok/s rises with K, the
    # single-step number was dispatch-bound, not compute-bound.
    ksteps = int(os.environ.get("DS_BENCH_MULTISTEP", "0"))
    if ksteps > 1:
        stacked = jnp.stack([pool[i % len(pool)] for i in range(ksteps)])

        def step(i):
            return engine.fused_train_steps(stacked, labels=stacked)
        n_dispatch = max(iters // ksteps, 2)
        iters = n_dispatch * ksteps
    else:
        def step(i):
            # ONE XLA program per step: fwd+bwd+optimizer fused (gas=1 fast path)
            return engine.fused_train_step(pool[i % len(pool)], labels=pool[i % len(pool)])
        n_dispatch = iters

    step(0)  # compile + warmup
    step(1)
    jax.block_until_ready(engine.params)
    float(jax.tree_util.tree_leaves(engine.params)[0].ravel()[0])

    t0 = time.time()
    for i in range(n_dispatch):
        step(i)
    # barrier on the full step (params carry the optimizer update), not just
    # the forward loss — XLA dispatch is async; the host read ends the
    # timing region
    jax.block_until_ready(engine.params)
    float(jax.tree_util.tree_leaves(engine.params)[0].ravel()[0])
    dt = time.time() - t0

    tokens_per_sec = iters * batch * seq / dt
    # honest model-FLOPs accounting: 6N matmul fwd+bwd + causal attention
    # (6 * s * d_attn per layer-token); remat recompute is NOT credited
    d_attn = cfg.num_attention_heads * cfg.head_dim_
    flops_per_token = 6 * n_params + 6 * cfg.num_hidden_layers * seq * d_attn
    achieved = tokens_per_sec * flops_per_token
    from deepspeed_tpu.accelerator import get_accelerator
    peak = get_accelerator().peak_bf16_flops()  # device_kind-aware
    mfu = achieved / peak
    mfu_ratio = round(mfu / 0.54, 4)
    scan_tag = (f", scan_layers/chunk{cfg.scan_chunk_size}"
                if cfg.scan_chunk_size > 1 else
                (", scan_layers" if scan else ""))
    unit = (f"tokens/s ({n_params / 1e9:.1f}B llama, bf16, fused step, "
            f"{'cpu-offload opt, ' if large else ''}"
            f"bs{batch}xseq{seq}"
            f"{', remat=' + str(remat) if remat else ''}"
            f"{scan_tag}"
            f"{f', {heads}h x hd{cfg.head_dim_}' if heads else ''}"
            f"{f', {ksteps}-step dispatch' if ksteps > 1 else ''}"
            f", {_attn_dispatch_note(cfg, batch, seq)})")
    out = {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": unit,
        "vs_baseline": mfu_ratio,
    }
    return out


def _git_rev():
    """Short HEAD hash, or None outside a repo — journal records are scoped
    to the code revision that produced them."""
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.TimeoutExpired):
        return None


def _journal_append(path, rec):
    """Append one journal record, stamped with UTC time and git revision
    (shared by the chip-result and mem-triage journals — one writer).
    Self-healing: if the file ends in a torn line (a writer killed
    mid-append leaves no trailing newline), start on a fresh line so the
    new record isn't concatenated into the torn one and lost with it."""
    try:
        rec = dict(rec, utc=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                   ts=time.time(), rev=_git_rev())
        os.makedirs(os.path.dirname(path), exist_ok=True)
        needs_nl = False
        try:
            with open(path, "rb") as f:
                f.seek(-1, os.SEEK_END)
                needs_nl = f.read(1) != b"\n"
        except (OSError, ValueError):  # missing or empty file
            pass
        with open(path, "a") as f:
            f.write(("\n" if needs_nl else "") + json.dumps(rec) + "\n")
    except OSError:
        pass


def _journal_records(path):
    """All parseable dict records in a journal. A torn tail write (killed
    mid-append) must never void the good lines before it."""
    recs = []
    try:
        with open(path) as f:
            for ln in f:
                if not ln.strip():
                    continue
                try:
                    r = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(r, dict):
                    recs.append(r)
    except OSError:
        pass
    return recs


def _history_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_HISTORY.jsonl")


def _history_rung(unit: str = "") -> str:
    """Stable rung tag for the regression history: the env flags that pick
    the ladder (each selects a different model/footprint, so their numbers
    must never be diffed against each other)."""
    rung = "train"
    for flag, tag in (("DS_BENCH_LONGSEQ", "longseq"),
                      ("DS_BENCH_LARGE", "large"),
                      ("DS_BENCH_SCAN", "scan"),
                      ("DS_BENCH_FAST", "fast")):
        if env_flag(flag):
            rung += f"-{tag}"
    if int(os.environ.get("DS_BENCH_MULTISTEP", "0") or 0) > 1:
        rung += "-multistep"
    return rung


def _append_history(rec, rung=None):
    """One line per completed bench run in ``BENCH_HISTORY.jsonl`` — the
    regression ledger ``bin/ds_benchdiff`` diffs. ``_journal_append`` stamps
    git revision and UTC date; records are compared latest-vs-previous
    within a rung, higher ``value`` better."""
    _journal_append(_history_path(),
                    {"rung": rung or _history_rung(rec.get("unit", "")),
                     **{k: rec[k] for k in
                        ("metric", "value", "unit", "vs_baseline",
                         "paged_vs_dense") if k in rec}})


def _triage_journal_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chiprun_out", "mem_triage.jsonl")


def _device_kind():
    try:
        import jax
        return getattr(jax.devices()[0], "device_kind", None)
    except Exception:  # noqa: BLE001 — no backend
        return None


def journal_triage_record(batch, seq, remat, scan, heads, status, nbytes=None):
    """Append one mem-triage probe verdict (fit/oom/err) so the bench ladder
    can act on it: compile-only probes call this — one journal format, one
    writer. Records are scoped to git revision and device kind: a verdict
    earned by other code or another chip must never skip a rung."""
    _journal_append(_triage_journal_path(),
                    {"batch": batch, "seq": seq, "remat": remat,
                     "scan": scan, "heads": heads, "status": status,
                     "bytes": nbytes, "device_kind": _device_kind()})


def _triage_verdicts(max_age_h=24.0):
    """Latest fresh fit/oom verdict per rung, keyed
    ``(batch, seq, remat, scan, heads)``. Only records whose git revision
    AND device kind match the present ones are trusted (memory layout
    moves with code; HBM size with the chip). Computed once per ladder —
    not per rung — so git/jax/the journal are consulted once."""
    kind = _device_kind()
    rev = _git_rev()
    if kind is None or rev is None:
        return {}
    now = time.time()
    best = {}
    for r in _journal_records(_triage_journal_path()):
        if not (r.get("rev") == rev and r.get("device_kind") == kind
                and isinstance(r.get("ts"), (int, float))
                and now - r["ts"] < max_age_h * 3600
                and r.get("status") in ("fit", "oom")):
            continue
        # scan is kept RAW in the key: a chunk-size rung (scan=6) compiles a
        # different program than per-layer scan (scan=True) — one's verdict
        # must never suppress the other
        k = (r.get("batch"), r.get("seq"), r.get("remat"),
             r.get("scan"), r.get("heads"))
        if k not in best or r["ts"] > best[k]["ts"]:
            best[k] = r
    return {k: r["status"] for k, r in best.items()}


def _triage_verdict(batch, seq, remat, scan, heads, max_age_h=24.0):
    """Single-rung lookup over ``_triage_verdicts``. The ladder uses 'oom'
    to skip a rung without re-paying its doomed compile (failed compiles
    are never cached, so re-proving an OOM costs the full compile time out
    of a chip call)."""
    return _triage_verdicts(max_age_h).get((batch, seq, remat, scan, heads))


def breakdown(batch=8, seq=1024, iters=10):
    """Where-the-time-goes report: fused step vs forward-only
    vs optimizer-only, plus flash-vs-XLA attention and XLA cost analysis.
    Prints one JSON object (not the driver metric line)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, init_llama

    # same config object as measure() (incl. chunked CE) so the breakdown
    # explains the bench's fused step, not a different program;
    # DS_BENCH_SCAN=1 matches the scanned fast-mode program when the
    # unrolled 24-layer compile won't fit a chip call. Footprints form a
    # mini-ladder: bs8/no-remat has OOMed a 16G chip, so a deterministic
    # OOM must fall through to a fitting footprint.
    on_cpu = jax.devices()[0].platform == "cpu"
    footprints = [(batch, False), (batch, "dots_saveable"),
                  (max(batch // 2, 1), "dots_saveable")]
    if on_cpu:  # smoke-test sizing
        footprints = [(2, False)]
        seq, iters = 128, 2
    rng = np.random.default_rng(0)
    engine = None
    scan_val = env_flag("DS_BENCH_SCAN")
    verdicts = _triage_verdicts()
    skipped = 0
    for batch, remat in footprints:
        if verdicts.get((batch, seq, remat, scan_val, None)) == "oom":
            # compile-only triage already proved this footprint exceeds HBM
            # at this revision on this chip — don't re-pay the doomed compile
            print(f"breakdown: skipping bs{batch} remat={remat} "
                  f"(triage: proven OOM)", file=sys.stderr)
            skipped += 1
            continue
        cfg = bench_config(remat=remat, scan_layers=scan_val)
        if on_cpu:
            cfg = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                              num_hidden_layers=2, num_attention_heads=4,
                              num_key_value_heads=4, max_position_embeddings=512)
        model, params = init_llama(cfg)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree_util.tree_leaves(params))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config=bench_engine_config(batch))
        ids = jax.device_put(jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, seq)), dtype=jnp.int32))
        try:
            engine.fused_train_step(ids, labels=ids)  # compile + fit check
            break
        except Exception as e:  # noqa: BLE001
            if "RESOURCE_EXHAUSTED" not in str(e) and "memory" not in str(e).lower():
                raise
            print(f"breakdown: bs{batch} remat={remat} OOMed, trying next",
                  file=sys.stderr)
            # free the failed attempt's device buffers BEFORE the next
            # init_llama — the fp32 master tree (~1.6G) would otherwise
            # stay live into the fallback's compile and shrink exactly the
            # headroom the fallback is searching for
            engine = None
            del model, params, ids
            import gc
            gc.collect()
            jax.clear_caches()
    if engine is None:
        raise RuntimeError(
            "breakdown: every footprint OOMed"
            + (" (all skipped by triage verdicts — nothing compiled this "
               "session)" if skipped == len(footprints) else ""))
    remat_used = remat

    def _sync():
        jax.block_until_ready(engine.params)
        float(jax.tree_util.tree_leaves(engine.params)[0].ravel()[0])

    def timeit(fn, sync=None, n=iters):
        fn()  # compile
        fn()
        (sync or _sync)()
        t0 = time.time()
        for _ in range(n):
            out = fn()
        (sync or _sync)()
        return (time.time() - t0) / n, out

    def timed(build, n=iters):
        """Time `build()` (returns a device pytree): compile+warm, then n
        timed calls ended by a host readback (see _measure_config)."""
        box = [None]
        def sync():
            jax.block_until_ready(box[0])
            float(np.asarray(jax.tree_util.tree_leaves(box[0])[0]).ravel()[0])
        def run():
            box[0] = build()
            return box[0]
        return timeit(run, sync=sync, n=n)

    report = {}
    # dispatch sanity: make the fast-path decision visible in the artifact
    from deepspeed_tpu.ops.registry import on_tpu, use_pallas
    report["on_tpu"] = bool(on_tpu())
    report["use_pallas"] = bool(use_pallas())
    report["scan_layers"] = bool(cfg.scan_layers)
    report["batch"] = batch
    report["remat"] = str(remat_used)
    t_step, _ = timeit(lambda: engine.fused_train_step(ids, labels=ids))
    report["fused_step_ms"] = round(t_step * 1e3, 2)

    # forward-only (loss program, no bwd/opt) via the engine's compiled fn
    try:
        t_fwd, _ = timed(lambda: engine._fwd_only(
            engine.params, (ids, ), {"labels": ids}, ()))
        report["forward_ms"] = round(t_fwd * 1e3, 2)
    except Exception as e:  # noqa: BLE001
        report["forward_ms"] = f"n/a ({str(e)[:80]})"

    # attention kernel micro-bench: flash vs XLA at bench shape
    from deepspeed_tpu.ops.attention import flash_attention, _xla_attention
    hd = cfg.head_dim_
    q = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, seq, cfg.num_attention_heads, hd)), jnp.bfloat16))
    fl = jax.jit(lambda q: flash_attention(q, q, q, causal=True))
    xl = jax.jit(lambda q: _xla_attention(q, q, q, 1.0 / np.sqrt(hd), True))
    for name, fn in (("flash_attn_ms", fl), ("xla_attn_ms", xl)):
        try:
            t, _ = timed(lambda fn=fn: fn(q), n=20)
            report[name] = round(t * 1e3, 3)
        except Exception as e:  # noqa: BLE001
            report[name] = f"n/a ({str(e)[:80]})"

    # MXU peak calibration: what TFLOP/s can THIS chip actually sustain on
    # a pure big-matmul chain? The fused-step gap attribution needs this
    # anchor — if the probe itself lands well under 197 TF/s, the ceiling
    # is the chip, not our program.
    try:
        # the probe must be LONG enough that per-dispatch host latency is
        # noise: 512 links x 2*M*K^2 = 18 TFLOP per call (~150ms+ of pure
        # MXU work).
        M, K = (16384, 1024) if report["on_tpu"] else (256, 128)
        w = jax.device_put(jnp.asarray(
            rng.standard_normal((K, K)) / np.sqrt(K), jnp.bfloat16))
        y0 = jax.device_put(jnp.asarray(
            rng.standard_normal((M, K)), jnp.bfloat16))
        CHAIN = 512 if report["on_tpu"] else 16

        @jax.jit
        def matmul_chain(y, w):
            return jax.lax.scan(lambda c, _: (c @ w, None), y,
                                None, length=CHAIN)[0]
        t, _ = timed(lambda: matmul_chain(y0, w), n=4)
        report["mxu_peak_probe_tflops"] = round(
            2 * M * K * K * CHAIN / t / 1e12, 1)
    except Exception as e:  # noqa: BLE001
        report["mxu_peak_probe_tflops"] = f"n/a ({str(e)[:80]})"

    # FFN fwd+bwd micro-bench: the non-attention half of the layer under
    # XLA fusion alone (no Pallas). If this sustains near-probe TFLOP/s the
    # reference's fused-training-block kernel has nothing left to win here
    # and the remaining fused-step gap lives in scheduling/attention.
    try:
        T, H, I = batch * seq, cfg.hidden_size, cfg.intermediate_size
        xf = jax.device_put(jnp.asarray(
            rng.standard_normal((T, H)), jnp.bfloat16))
        w1 = jax.device_put(jnp.asarray(
            rng.standard_normal((H, I)) / np.sqrt(H), jnp.bfloat16))
        w3 = jax.device_put(jnp.asarray(
            rng.standard_normal((H, I)) / np.sqrt(H), jnp.bfloat16))
        w2 = jax.device_put(jnp.asarray(
            rng.standard_normal((I, H)) / np.sqrt(I), jnp.bfloat16))

        def ffn_loss(x, w1, w3, w2):
            h = jax.nn.silu(x @ w1) * (x @ w3)
            return ((h @ w2).astype(jnp.float32) ** 2).mean()
        # grad wrt x AND weights so the executed FLOPs are the full
        # 18*T*H*I backward (weight-only grads would let XLA drop the two
        # dx matmuls and overstate TFLOP/s by ~29%)
        ffn_grad = jax.jit(jax.grad(ffn_loss, argnums=(0, 1, 2, 3)))
        t, _ = timed(lambda: ffn_grad(xf, w1, w3, w2), n=10)
        report["ffn_fwdbwd_ms"] = round(t * 1e3, 3)
        report["ffn_fwdbwd_tflops"] = round(18 * T * H * I / t / 1e12, 1)
    except Exception as e:  # noqa: BLE001
        report["ffn_fwdbwd_tflops"] = f"n/a ({str(e)[:80]})"

    # flash fwd+bwd (the in-step reality is grad-of-attention, not fwd-only)
    try:
        def attn_loss(q):
            return (flash_attention(q, q, q, causal=True)
                    .astype(jnp.float32) ** 2).mean()
        fb = jax.jit(jax.grad(attn_loss))
        t, _ = timed(lambda: fb(q), n=10)
        report["flash_fwdbwd_ms"] = round(t * 1e3, 3)
    except Exception as e:  # noqa: BLE001
        report["flash_fwdbwd_ms"] = f"n/a ({str(e)[:80]})"

    # XLA attention fwd+bwd at the same shape: the 0801T1906 trace showed
    # the flash kernels at 70% of step time for ~6% of model FLOPs — if
    # XLA's materialized-scores attention backward beats the Pallas pair
    # at seq<=2k, the right per-shape dispatch is XLA, and this number
    # decides it
    try:
        def xattn_loss(q):
            return (_xla_attention(q, q, q, 1.0 / np.sqrt(hd), True)
                    .astype(jnp.float32) ** 2).mean()
        xb = jax.jit(jax.grad(xattn_loss))
        t, _ = timed(lambda: xb(q), n=10)
        report["xla_fwdbwd_ms"] = round(t * 1e3, 3)
    except Exception as e:  # noqa: BLE001
        report["xla_fwdbwd_ms"] = f"n/a ({str(e)[:80]})"

    # isolated optimizer step: Adam over a model-sized flat param vector —
    # bandwidth-bound floor ~13 ms at 0.4B params (26 B/param over ~800
    # GB/s); a number far above that indicts the fused-optimizer kernel's
    # blocking, not the model program
    try:
        from deepspeed_tpu.ops.fused_optimizer import fused_adam_step
        nflat = int(n_params)
        pf = jax.device_put(jnp.zeros((nflat, ), jnp.float32))
        gf = jax.device_put(jnp.ones((nflat, ), jnp.float32) * 1e-3)
        mf = jax.device_put(jnp.zeros((nflat, ), jnp.float32))
        vf = jax.device_put(jnp.zeros((nflat, ), jnp.float32))
        st = jax.jit(lambda p, g, m, v: fused_adam_step(
            p, g, m, v, lr=1e-3, step=1))
        t, _ = timed(lambda: st(pf, gf, mf, vf), n=10)
        report["adam_step_ms"] = round(t * 1e3, 3)
    except Exception as e:  # noqa: BLE001
        report["adam_step_ms"] = f"n/a ({str(e)[:80]})"

    # exact compiled FLOPs of the fused step (XLA cost analysis)
    try:
        lowered = engine._train_step_fused.lower(
            engine.params, engine.opt_state, engine.scale_state,
            (ids, ), {"labels": ids}, ())
        ca = lowered.compile().cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        report["xla_flops_per_step"] = float(ca.get("flops", -1.0))
    except Exception as e:  # noqa: BLE001
        report["xla_flops_per_step"] = f"n/a ({str(e)[:80]})"

    # optional xprof capture (DS_BENCH_TRACE=dir): 3 fused steps under
    # jax.profiler.trace — host dispatch and device timelines
    trace_dir = os.environ.get("DS_BENCH_TRACE")
    if trace_dir:
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    engine.fused_train_step(ids, labels=ids)
                jax.block_until_ready(engine.params)
            report["trace_dir"] = trace_dir
        except Exception as e:  # noqa: BLE001
            report["trace_dir"] = f"n/a ({str(e)[:80]})"

    toks = batch * seq
    report["tokens_per_step"] = toks
    report["model_flops_per_step"] = 6 * n_params * toks \
        + 6 * cfg.num_hidden_layers * seq * cfg.num_attention_heads * hd * toks
    from deepspeed_tpu.accelerator import get_accelerator
    peak = get_accelerator().peak_bf16_flops()
    report["peak_tflops_assumed"] = round(peak / 1e12, 1)
    if isinstance(report.get("xla_flops_per_step"), float) and t_step > 0:
        report["hw_flops_utilization"] = round(
            report["xla_flops_per_step"] / t_step / peak, 4)
        report["mfu"] = round(report["model_flops_per_step"] / t_step / peak, 4)
    print(json.dumps(report), flush=True)


def _measure_obs_ab():
    """``DS_BENCH_OBS_AB=1``: training-observability overhead A/B — the
    same fused-step loop on two engines, one with the ``observability``
    config block force-disabled, one with the default-on instrumentation
    (compile watch + goodput ledger + step histogram). Timed reps ALTERNATE
    between the arms so clock/thermal drift lands on both equally.
    Acceptance: the enabled arm costs <2% tok/s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, init_llama

    platform = jax.devices()[0].platform
    if platform == "cpu":
        # fp32 model dtype: the bf16 default would route fp32 masters
        # through the use-site cast barrier, which has no grad rule on host
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=704, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=512, remat=True,
                          dtype=jnp.float32)
        batch, seq, iters, reps = 2, 256, 4, 4
    else:
        cfg = bench_config("dots_saveable", scan_layers=True)
        batch, seq, iters, reps = 8, 1024, 8, 3

    rng = np.random.default_rng(0)
    pool = [jnp.asarray(rng.integers(0, cfg.vocab_size, size=(batch, seq)),
                        dtype=jnp.int32) for _ in range(4)]
    engines = {}
    for obs_on in (False, True):
        model, params = init_llama(cfg)
        ecfg = bench_engine_config(batch)
        if platform == "cpu":
            # the chip config's bf16+use-site-cast combo can't differentiate
            # on host CPU (optimization_barrier grad, chip-only path) — the
            # diagnostic arm measures instrumentation overhead, not dtype
            ecfg.pop("bf16", None)
            ecfg.pop("param_cast", None)
        ecfg["observability"] = {"enabled": obs_on}
        engines[obs_on], _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ecfg)

    def rep(eng):
        t0 = time.time()
        for i in range(iters):
            eng.fused_train_step(pool[i % len(pool)],
                                 labels=pool[i % len(pool)])
        jax.block_until_ready(eng.params)
        float(jax.tree_util.tree_leaves(eng.params)[0].ravel()[0])
        return time.time() - t0

    for eng in engines.values():  # compile + warmup, outside the clock
        rep(eng)
    wall = {False: 0.0, True: 0.0}
    for _ in range(reps):
        for obs_on in (False, True):
            wall[obs_on] += rep(engines[obs_on])
    tokens = reps * iters * batch * seq
    tok_off, tok_on = tokens / wall[False], tokens / wall[True]
    overhead = round(100.0 * (1.0 - tok_on / tok_off), 2)
    _journal_append(_history_path(), {
        "rung": "train-obs-ab" + ("-cpu" if platform == "cpu" else ""),
        "metric": "train_tokens_per_sec_observability_on",
        "value": round(tok_on, 1), "unit": "tokens/s",
        "vs_baseline": 0.0, "observability_overhead_pct": overhead})
    return {"metric": "train_observability_overhead_pct",
            "value": overhead,
            "unit": (f"pct tok/s lost with training observability on "
                     f"(off {tok_off:.0f} vs on {tok_on:.0f} tok/s"
                     f"{', DIAGNOSTIC cpu fallback' if platform == 'cpu' else ''})"),
            "vs_baseline": 0.0,
            "tok_s_observability_off": round(tok_off, 1),
            "tok_s_observability_on": round(tok_on, 1),
            "observability_ab": True}


def _measure_zero3_ab():
    """``DS_BENCH_ZERO3=1``: scheduled ZeRO-3 vs ZeRO-2 A/B — the same
    bucketed-gradient-comm training loop on two engines, stage 2 (replicated
    params, scattered grads) vs stage 3 (the compiler-scheduled param store:
    1/dp bucket shards, traced gather prefetch inside the microbatch scan).
    Records step-time ratio, per-chip param bytes, and the schedule's gather
    wire bytes. Needs dp>=2: on a single-device session the measurement
    re-execs itself under 2 forced host CPU devices (diagnostic sizing, the
    same topology the dp=2 acceptance test uses)."""
    import jax

    if jax.device_count() < 2:
        from deepspeed_tpu.utils.hostdev import force_host_devices_env
        env = force_host_devices_env(2, extra={"DS_BENCH_ZERO3": "1"})
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            env=env, capture_output=True, text=True, timeout=1700)
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.lstrip().startswith("{")]
        if out.returncode != 0 or not lines:
            raise RuntimeError("zero3 A/B dp=2 subprocess failed: "
                               + (out.stderr or out.stdout)[-800:])
        rec = json.loads(lines[-1])
        rec["forced_host_dp2"] = True
        return rec

    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from deepspeed_tpu.models import LlamaConfig, init_llama

    platform = jax.devices()[0].platform
    w = jax.device_count()  # pure-DP over every device
    # fp32 model dtype: the scheduled program's fp32 gather wire is the
    # bitwise-parity arm; small llama sizing keeps the CPU diagnostic snappy
    cfg = LlamaConfig(vocab_size=2048, hidden_size=256, intermediate_size=704,
                      num_hidden_layers=4, num_attention_heads=8,
                      num_key_value_heads=8, max_position_embeddings=512,
                      remat=True, dtype=jnp.float32)
    rows, seq, gas = 2 * w, 128, 2
    iters, reps = 2, 3

    def mk(zero_cfg):
        reset_mesh_context()
        model, params = init_llama(cfg)
        ecfg = {"train_batch_size": rows * gas,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                # 4MB buckets: ~5 buckets over the 17MB model, so the
                # stage-3 arm runs a real multi-epoch prefetch pipeline
                # (25MB default = one bucket = one degenerate gather)
                "gradient_comm": {"enabled": True, "overlap_comm": True,
                                  "bucket_size_mb": 4.0},
                "zero_optimization": zero_cfg,
                "steps_per_print": 0}
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ecfg)
        return eng

    engines = {
        2: mk({"stage": 2}),
        3: mk({"stage": 3, "stage3_param_persistence_threshold": 0}),
    }
    assert engines[3]._zero3_store is not None, \
        "stage-3 engine fell back — the A/B would measure nothing"

    rng = np.random.default_rng(0)
    pool = [(jnp.asarray(rng.integers(0, cfg.vocab_size, size=(rows, seq)),
                         jnp.int32), ) * 2 for _ in range(gas)]

    def rep(eng):
        t0 = time.time()
        for _ in range(iters):
            loss = eng.train_batch(iter(pool))
        jax.block_until_ready(eng.params)
        float(loss)
        return time.time() - t0

    for eng in engines.values():  # compile + warmup, outside the clock
        rep(eng)
    wall = {2: 0.0, 3: 0.0}
    for _ in range(reps):  # timed reps alternate so drift lands on both arms
        for stage in (2, 3):
            wall[stage] += rep(engines[stage])
    step2 = wall[2] / (reps * iters)
    step3 = wall[3] / (reps * iters)
    ratio = step2 / step3  # >1: scheduled stage 3 is faster

    def per_chip(tree):
        return sum(l.addressable_shards[0].data.nbytes
                   for l in jax.tree_util.tree_leaves(tree))

    p2, p3 = per_chip(engines[2].params), per_chip(engines[3].params)
    sched = engines[3]._zero3_schedule
    wire = sched.gather_wire_bytes * gas  # per optimizer step, per chip
    rung = "zero3-ab" + ("-cpu" if platform == "cpu" else "")
    _journal_append(_history_path(), {
        "rung": rung, "metric": "zero3_vs_zero2_step_time_ratio",
        "value": round(ratio, 4),
        "unit": "x (zero2_step/zero3_step, higher = faster zero3)",
        "vs_baseline": 0.0, "dp_world": w,
        "zero2_step_ms": round(step2 * 1e3, 1),
        "zero3_step_ms": round(step3 * 1e3, 1),
        "per_chip_param_bytes_zero2": p2, "per_chip_param_bytes_zero3": p3,
        "zero3_gather_wire_bytes_per_step": wire,
        "zero3_gather_epochs": len(sched.epochs),
        "zero3_prefetched_epochs": sched.prefetch_count})
    return {"metric": "zero3_vs_zero2_step_time_ratio",
            "value": round(ratio, 4),
            "unit": (f"x zero2/zero3 step time at dp={w} (z2 "
                     f"{step2 * 1e3:.0f}ms vs z3 {step3 * 1e3:.0f}ms; "
                     f"params/chip {p2} -> {p3} B; gather "
                     f"{wire} B/step/chip"
                     f"{', DIAGNOSTIC cpu' if platform == 'cpu' else ''})"),
            "vs_baseline": 0.0,
            "per_chip_param_bytes_zero2": p2,
            "per_chip_param_bytes_zero3": p3,
            "zero3_gather_wire_bytes_per_step": wire,
            "zero3_ab": True}


def measure():
    if env_flag("DS_BENCH_OBS_AB"):
        # overhead A/B replaces the ladder for this run — its number is a
        # regression gate, not a throughput headline
        print(json.dumps(_measure_obs_ab()), flush=True)
        return
    if env_flag("DS_BENCH_ZERO3"):
        # scheduled-ZeRO-3 A/B replaces the ladder likewise: the ratio is a
        # parity gate (step time within 10% of stage 2 at ~1/dp the param
        # bytes), not a throughput headline
        print(json.dumps(_measure_zero3_ab()), flush=True)
        return
    # ANYTIME ladder: a footprint that RELIABLY lands runs FIRST so a short
    # chip call still records a real number, then the ambitious configs
    # try to beat it. Every improvement prints a fresh JSON line; the
    # parent (and the driver) take the LAST line, so the recorded result is
    # the best achieved before the timeout closed.
    # Rung = (batch, seq, iters, remat, scan). Scanned rungs lead: the
    # unrolled 24-layer program has had a >=25-min cold compile (amortized
    # only once the persistent cache holds it), and bs8/no-remat can OOM —
    # so the ladder interleaves memory fallbacks instead of assuming a
    # landing spot.
    scan_only = env_flag("DS_BENCH_SCAN")
    # optional 6th element: head-count override at the same hidden size
    # (8h x hd128 = identical params/FLOPs to 16h x hd64, but the flash
    # q.kT contraction uses the MXU's full 128-deep K dim instead of half)
    attempts = [(8, 1024, 20, False, True),             # scanned safe start
                (8, 1024, 20, "dots_saveable", True),   # memory fallback
                (8, 1024, 20, False, False),            # unrolled bs8/no-remat:
                # the best program of the 2026-08-01 breakdown (269ms/step =
                # 30.4k tok/s, 0.68x bar, vs 340ms scanned) — its compile sits
                # in the persistent cache, so it goes right after the scanned
                # safety rungs
                (4, 1024, 20, False, True),             # second fallback
                (16, 1024, 20, "dots_saveable", True),  # bigger MXU footprint
                (4, 1024, 10, True, True),              # full-remat floor
                (8, 1024, 20, False, True, 8),          # hd128 head shape
                (8, 1024, 20, "dots_saveable", True, 8),  # hd128 + dots: the
                # no-remat hd128 OOMed in triage; dots freed 4.9G at hd64
                (8, 1024, 20, False, 6),                # chunked scan (4 steps
                # x 6 unrolled layers): most of unrolled's scheduling freedom
                # at ~1/6 the HLO
                (16, 1024, 20, "dots_saveable", False)]
    if env_flag("DS_BENCH_LONGSEQ"):
        # the Ulysses bar (blogs/deepspeed-ulysses/README.md:82-83) is a
        # LONG-SEQUENCE sustained-utilization number — measure the flash
        # kernel's long-context regime: same model, 16k/32k tokens in one
        # sequence, selective remat (full activations at 32k don't fit)
        attempts = [(1, 16384, 8, "dots_saveable", True),
                    (1, 32768, 6, "dots_saveable", True),
                    (1, 16384, 8, True, True)]
    large = env_flag("DS_BENCH_LARGE")
    if large:
        # ~1.36B-param rung (remat + CPU-offloaded master states): the MFU
        # claim shouldn't rest on the 0.4B proxy. Full
        # remat leads: the 4x-larger activations have no no-remat landing
        # spot on 16 GB, and every rung pays the host-offload step.
        attempts = [(4, 1024, 8, True, True),
                    (2, 1024, 8, True, True),
                    (4, 1024, 8, "dots_saveable", True),
                    (1, 1024, 6, True, True)]
    if env_flag("DS_BENCH_FAST"):
        # short chip call: scanned-only ladder, fewer iters. bs16/dots
        # comes right after the first landing rung
        attempts = [(8, 1024, 12, False, True),
                    (8, 1024, 12, "dots_saveable", True),
                    (8, 1024, 12, False, False),  # unrolled winner (cache-warm)
                    (8, 1024, 12, "dots_saveable", True, 8),  # hd128 + dots
                    (16, 1024, 12, "dots_saveable", True),
                    (4, 1024, 12, False, True),
                    (4, 1024, 10, True, True)]
    best = None
    last_err = None
    verdicts = _triage_verdicts()  # one git/jax/journal consult per ladder
    for batch, seq, iters, remat, scan, *rest in attempts:
        heads = rest[0] if rest else None
        if scan_only and scan is not True:
            # DS_BENCH_SCAN=1: per-layer-scan programs ONLY — the mode exists
            # for calls too short for big compiles, and a chunked rung's
            # compile (~6x the per-layer HLO) is exactly that class
            continue
        if best is not None and remat is True:
            continue  # the full-remat floor can't beat a no-remat success
        if not large and verdicts.get((batch, seq, remat, scan, heads)) == "oom":
            # (triage verdicts are keyed for the 0.4B model — a proven-OOM
            # there says nothing about the large rung, and vice versa)
            # the compile-only triage already PROVED this rung exceeds HBM
            # at this revision on this chip — re-proving it would burn a
            # full (uncacheable, failed) compile out of the chip call
            print(f"ladder: skipping bs{batch} remat={remat} scan={scan}"
                  f"{f' heads={heads}' if heads else ''} (triage: proven OOM)",
                  file=sys.stderr)
            continue
        print(f"ladder: trying bs{batch} seq{seq} remat={remat} scan={scan}"
              f"{f' heads={heads}' if heads else ''}", file=sys.stderr)
        try:
            # `large` forwarded only when set: the default ladder keeps the
            # historical _measure_config call shape (test fakes rely on it)
            out = _measure_config(batch, seq, iters, remat, scan=scan,
                                  heads=heads,
                                  **({"large": True} if large else {}))
        except Exception as e:  # noqa: BLE001 — RESOURCE_EXHAUSTED etc.
            msg = str(e)
            if "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg or "OOM" in msg:
                print(f"ladder: bs{batch} remat={remat} OOMed", file=sys.stderr)
                last_err = msg
                continue
            if best is not None:
                _append_history(best)
                return  # keep the number already printed; don't die improving it
            raise
        finally:
            # a completed rung's engine/params/compiled programs must not
            # eat into the next rung's HBM headroom (the safe rung now runs
            # FIRST; residue could make bs16 OOM where a fresh process fit)
            import gc
            import jax
            gc.collect()
            jax.clear_caches()
        # rank rungs by MFU first (fair across different seq lengths — a
        # 32k rung has more attention FLOPs per token, so raw tok/s would
        # always pick the short sequence), tok/s as the CPU-mode tiebreak
        if best is None or ((out["vs_baseline"], out["value"])
                            > (best["vs_baseline"], best["value"])):
            best = out
            print(json.dumps(out), flush=True)
    if best is None:
        raise RuntimeError("all bench footprints OOMed: "
                           + (last_err or "every rung skipped by triage "
                              "verdicts")[-500:])
    _append_history(best)


def supervise():
    """Run the measurement in one child and print its last JSON line. A
    child that fails, or prints no result, is a non-zero exit."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            capture_output=True, text=True, timeout=ATTEMPT_TIMEOUT,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        stdout, stderr, rc = proc.stdout, proc.stderr, proc.returncode
    except subprocess.TimeoutExpired as e:
        # anytime ladder: the child prints each improvement as it lands, so
        # a timeout mid-upgrade still leaves a real measurement
        stdout, stderr = (
            x.decode(errors="replace") if isinstance(x, bytes) else (x or "")
            for x in (e.stdout, e.stderr))
        rc = 0 if any(ln.startswith("{") for ln in stdout.splitlines()) \
            else 124
    out_lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    if rc == 0 and out_lines:
        print(out_lines[-1])
        return 0
    print(f"bench child rc={rc}:\n{(stderr or stdout)[-2000:]}",
          file=sys.stderr)
    return rc or 1


if __name__ == "__main__":
    if "--breakdown" in sys.argv:
        breakdown()
    elif "--child" in sys.argv:
        measure()
    else:
        sys.exit(supervise())
