"""Runner: a fixed number of clients in a closed loop over HTTP, streaming.

What ``bin/ds_serve`` does (``build_llama_engine`` -> ``ServingScheduler`` ->
``create_http_server``), copied from ``chip_smoke.serving_phase`` with every
engine default left a default, then ``traffic.clients`` threads that each
send their next request when the last one ended. The loop is already running
when the window opens. Warm-up is set-up time and goes through the program's
public entry points only: ``engine.warmup(**traffic.engine_warmup)`` where the
cell gives it, then the loop itself until the program has published no new
serving compile (``ds_compiles_total{key="serve:..."}``, what ``GET /metrics``
renders) for ``traffic.warmup_quiet_seconds``, and at most
``traffic.warmup_max_seconds``. Which programs the arrivals fall into cannot
be listed ahead; one compiled inside the window is counted and reported.
"""

import http.client
import json
import threading
import time

import numpy as np

from benchmark import stats, traffic as gen
from benchmark.reference import mistral as reference

HF_KEYS = ("architectures", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "max_position_embeddings", "rms_norm_eps", "rope_theta",
           "sliding_window", "tie_word_embeddings", "vocab_size")

# Engine in bf16 (weights and activations, fp32 logits) against the float32
# reference on the same bf16 weights: every matmul output and residual add
# rounds to 8 bits of mantissa (2^-8 = 3.9e-3) and the stream carries those
# roundings through 2 x depth additions to the head. PR 21 measured 1.6e-2
# of the largest logit against the bf16 flax model at depth 24. 5e-2 leaves
# room for that and fails an 8-bit weight or cache path (>= 1e-1 on random
# weights) and any missing window, rotary or norm term (order 1).
LOGIT_TOL = 5e-2


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check_against_reference(engine, model_cfg: dict, seed: int, log) -> bool:
    """The engine's ``put`` logits for a seeded prompt, then four further
    tokens decoded through the KV cache, against the reference's one full
    forward pass over the same tokens."""
    import jax.numpy as jnp
    rng = np.random.default_rng([seed, 7])
    n_prompt, n_decode = model_cfg.get("check_prompt_tokens", 200), 4
    ids = rng.integers(0, model_cfg["vocab_size"], size=n_prompt + n_decode)
    uid = (1 << 27) + 1
    got = [np.asarray(engine.put([uid], [ids[:n_prompt]])[0], np.float32)]
    for t in ids[n_prompt:n_prompt + n_decode]:
        got.append(np.asarray(engine.put([uid], [[int(t)]])[0], np.float32))
    engine.flush(uid)
    want = np.asarray(reference.logits(
        engine.model().params, jnp.asarray(ids)[None], model_cfg,
        last=n_decode + 1)[0], np.float32)
    errs = [rel_err(g, w) for g, w in zip(got, want)]
    ok = all(np.isfinite(g).all() for g in got) and max(errs) < LOGIT_TOL
    log(f"correctness: put({n_prompt}) then {n_decode} decoded tokens against "
        f"the float32 reference, rel err {[f'{e:.2e}' for e in errs]} "
        f"(tolerance {LOGIT_TOL:g}): {'ok' if ok else 'FAILED'}")
    return ok


class ClosedLoop:
    """``clients`` threads; each sends the next request of the seeded
    sequence when its last one ended, until :meth:`stop`."""

    def __init__(self, port: int, traffic: dict, seed: int, vocab_size: int):
        self.port, self.seed, self.vocab = port, seed, vocab_size
        self.sizes = gen.request_sizes(traffic, seed)
        self.records, self._next = [], 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._client, daemon=True)
                         for _ in range(int(traffic["clients"]))]

    def start(self):
        for t in self._threads:
            t.start()

    def stop(self):
        """No client sends another request; :meth:`join` after the server is
        down, which ends the streams still open."""
        self._stop.set()

    def join(self):
        for t in self._threads:
            t.join(timeout=120)
        if any(t.is_alive() for t in self._threads):
            raise RuntimeError("a client thread did not end")

    def completed(self) -> int:
        with self._lock:
            return len(self.records)

    def snapshot(self) -> list:
        with self._lock:
            return list(self.records)

    def _client(self):
        while not self._stop.is_set():
            with self._lock:
                index = self._next
                self._next += 1
            n_prompt, n_out = self.sizes[index % len(self.sizes)]
            prompt = gen.prompt_tokens(self.seed, index, n_prompt, self.vocab)
            rec = self._request(prompt, n_out)
            with self._lock:
                self.records.append(rec)

    def _request(self, prompt, n_out: int) -> dict:
        """One streamed ``POST /generate`` (after ``chip_smoke._post_generate``)
        timed on this client's clock."""
        body = json.dumps({"prompt": [int(t) for t in prompt],
                           "max_new_tokens": n_out, "stream": True})
        rec = {"n_prompt": len(prompt), "n_out": n_out, "ok": False,
               "t_first": None, "t_last": None, "n_tokens": 0, "uid": None}
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=900)
        rec["t_send"] = time.monotonic()
        try:
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            uid = resp.getheader("X-DS-Request-Id")
            rec["uid"] = int(uid) if uid is not None else None
            tokens = []
            for line in resp:           # http.client undoes the chunking
                if line.strip():
                    tokens.append(json.loads(line)["token"])
                    rec["t_last"] = time.monotonic()
                    rec["t_first"] = rec["t_first"] or rec["t_last"]
            rec["n_tokens"] = len(tokens)
            rec["ok"] = (resp.status == 200 and len(tokens) == n_out
                         and all(0 <= t < self.vocab for t in tokens))
        except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
            rec["error"] = repr(e)
        finally:
            conn.close()
        rec["t_end"] = time.monotonic()
        return rec


def serving_compiles() -> dict:
    """Compiles per serving program as the program publishes them: the
    ``ds_compiles_total`` counters of the process registry whose ``key``
    label starts with ``serve:``."""
    from deepspeed_tpu.observability.metrics import get_registry
    return {c.labels["key"]: int(c.value)
            for c in get_registry().series("ds_compiles_total")
            if c.labels and c.labels.get("key", "").startswith("serve:")}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ServingScheduler
    from deepspeed_tpu.inference.v2.engine_v2 import build_llama_engine
    from deepspeed_tpu.inference.v2.server import create_http_server
    from deepspeed_tpu.models.llama import init_llama
    from deepspeed_tpu.module_inject.replace_policy import MistralPolicy

    tr = cell["traffic"]
    cfg = MistralPolicy().config_from_hf({k: config[k] for k in HF_KEYS})
    dtype = jnp.dtype(config["dtype"])
    t0 = time.monotonic()
    _, params = init_llama(cfg, seed=seed % (2**31 - 1), dtype=dtype)
    engine = build_llama_engine(cfg, params=params, dtype=dtype,
                                **config.get("engine_kwargs", {}))
    del params
    model = engine.model()
    kv_blocks = engine.free_blocks      # no sequence yet: the whole pool
    t_engine = time.monotonic() - t0
    log(f"serving: depth {cfg.num_hidden_layers}, attn_backend="
        f"{model.attn_backend}, kv_blocks={kv_blocks} x "
        f"{model.kv_block_size} tokens, engine built in {t_engine:.1f} s")

    t0 = time.monotonic()
    correct = check_against_reference(engine, config, seed, log)
    t_check = time.monotonic() - t0

    t0 = time.monotonic()
    if tr.get("engine_warmup"):
        n = engine.warmup(**tr["engine_warmup"])
        log(f"engine.warmup({tr['engine_warmup']}): {n} programs")
    t_engine_warmup = time.monotonic() - t0

    sched = ServingScheduler(engine).start()
    httpd = create_http_server(sched, "127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    loop = ClosedLoop(httpd.server_address[1], tr, seed, cfg.vocab_size)
    t_loop = time.monotonic()
    loop.start()
    seen, t_new, ended_by = serving_compiles(), t_loop, "cap"
    while time.monotonic() - t_loop < float(tr["warmup_max_seconds"]):
        time.sleep(0.25)
        now, cur = time.monotonic(), serving_compiles()
        if cur != seen:
            log(f"  warm-up t={now - t_loop:6.1f} s, {loop.completed()} "
                f"requests done, {len(cur)} programs: "
                f"+{sorted(set(cur) - set(seen))}")
            seen, t_new = cur, now
        if (loop.completed() >= int(tr["warmup_requests"])
                and now - t_new >= float(tr["warmup_quiet_seconds"])):
            ended_by = "quiet"
            break
    t_warm = time.monotonic() - t_loop

    # ---- the measured window ----
    t_open = time.monotonic()
    setup = compiles.snapshot()
    compiles_open = serving_compiles()
    counters = {"open": sched.trace}
    free_min = engine.free_blocks
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        counters["trace_open"], t_tr0 = sched.trace, time.monotonic()
    t_trace_end = t_open + (min(float(tr["trace_seconds"]), seconds) if trace else 0)
    while time.monotonic() < t_open + seconds:
        time.sleep(min(1.0, max(0.0, t_open + seconds - time.monotonic())))
        free_min = min(free_min, engine.free_blocks)
        if trace and time.monotonic() >= t_trace_end:
            counters["trace_close"] = sched.trace
            counters["trace_host"] = (t_tr0, time.monotonic())
            jax.profiler.stop_trace()
            trace = False
    t_close = time.monotonic()
    compiles_close = serving_compiles()
    in_window = sum(compiles_close.values()) - sum(compiles_open.values())
    counters["close"] = sched.trace

    # the window is closed: requests in flight are dropped, not drained (a
    # drain walks down through batch sizes the traffic never has, and
    # compiles a program for each)
    loop.stop()
    records = loop.snapshot()
    sched.stop(drain=False)
    httpd.shutdown()
    httpd.server_close()
    loop.join()
    e2e = stats.serving_metrics(records, t_open, t_close)
    spans_all = {}
    for r in records:
        if r["t_end"] >= t_open and r["uid"] is not None:
            tl = sched.trace_timeline(r["uid"])
            if tl is not None:
                spans_all[r["uid"]] = tl["spans"]
    spans = {r["uid"]: spans_all[r["uid"]]
             for r in stats.in_window(records, t_open, t_close)
             if r["uid"] in spans_all}

    e2e["setup_s"] = t_open - t_start
    notes = {"setup": setup, "engine_build_s": t_engine, "check_s": t_check,
             "engine_warmup_s": t_engine_warmup, "warmup_s": t_warm,
             "warmup_ended_by": ended_by,
             "warmup_requests": len(stats.in_window(records, t_loop, t_open)),
             "programs_before_window": len(compiles_open),
             "compiles_before_window": sum(compiles_open.values()),
             "compiles_in_window": in_window,
             "programs_met_in_window": sorted(set(compiles_close) - set(compiles_open)),
             "kv_blocks": kv_blocks, "window": e2e,
             "weight_bytes": sum(x.nbytes for x in jax.tree_util.tree_leaves(model.params))}
    return {"correct": correct and e2e["failed"] == 0,
            "attempted": e2e["attempted"], "failed": e2e["failed"],
            "end_to_end": e2e, "notes": notes, "setup": setup, "spans": spans,
            "spans_all": spans_all, "records": records,
            "counters": counters, "kv_blocks": kv_blocks,
            "free_blocks_min": free_min,
            "compiles": {"in_window": in_window},
            "trace_window_s": (counters["trace_host"][1] - counters["trace_host"][0]
                               if "trace_host" in counters else None),
            "page_size": model.kv_block_size}
