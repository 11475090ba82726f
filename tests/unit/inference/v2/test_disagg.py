"""Disaggregated prefill/decode serving (``inference/v2/disagg.py``).

The invariants under test, per the module contract:

- token streams are BIT-IDENTICAL with disaggregation on vs off — greedy,
  sampled (top-k/top-p on the per-sequence key chains) and fused
  speculative alike, because the per-request PRNG chains are
  engine-independent and the first output token samples from the prefill
  group's final-chunk logits row;
- durable-journal replay routes back through the handoff queue (a crash
  with transfers in flight replays byte-identically on the next boot);
- bisect quarantine isolates a poisoned request WITHIN its group — the
  other group never stalls and healthy requests finish exactly;
- a wedged handoff transfer (``disagg.transfer_stall``) degrades the
  request to in-group prefill instead of stalling admission;
- when the split cannot form (single device, or ``prefill_fraction``
  rounding to an empty group) the planner returns None and serving falls
  back to time-overlap continuous fusion.

Group scenarios need >= 2 devices, so they run in a SUBPROCESS with 4
forced virtual host devices (the ``force_host_devices`` conftest fixture);
planner arithmetic and the fallback path run in-process at any device
count.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.config_v2 import DisaggregationConfig
from deepspeed_tpu.inference.v2.disagg import plan_groups

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))


class _FakeDev:
    def __init__(self, i):
        self.id = i

    def __repr__(self):  # pragma: no cover - error messages only
        return f"dev({self.id})"


def _devs(n):
    return [_FakeDev(i) for i in range(n)]


# ---------------------------------------------------------------------------
# planner arithmetic (no engines, no real devices)
# ---------------------------------------------------------------------------


def test_plan_fraction_splits_tail_to_prefill():
    plan = plan_groups(DisaggregationConfig(enabled=True), devices=_devs(4))
    assert [d.id for d in plan.decode_devices] == [0, 1]
    assert [d.id for d in plan.prefill_devices] == [2, 3]
    # the decode group keeps the front of the device list — it must hold
    # the process default device so the decode engine's default placement
    # stays inside its own group
    assert plan.decode_devices[0].id == 0


def test_plan_disabled_or_single_device_is_none():
    assert plan_groups(DisaggregationConfig(), devices=_devs(8)) is None
    assert plan_groups(DisaggregationConfig(enabled=True),
                       devices=_devs(1)) is None


def test_plan_fraction_rounding_to_zero_falls_back():
    cfg = DisaggregationConfig(enabled=True, prefill_fraction=0.05)
    assert plan_groups(cfg, devices=_devs(4)) is None


def test_plan_fraction_never_consumes_every_device():
    # 0.9 of 4 rounds to 4 -> clamped to 3 so the decode group survives
    cfg = DisaggregationConfig(enabled=True, prefill_fraction=0.9)
    plan = plan_groups(cfg, devices=_devs(4))
    assert [d.id for d in plan.decode_devices] == [0]
    assert [d.id for d in plan.prefill_devices] == [1, 2, 3]


def test_plan_explicit_device_lists():
    cfg = DisaggregationConfig(enabled=True, prefill_devices=(1, 3),
                               decode_devices=(0, 2))
    plan = plan_groups(cfg, devices=_devs(4))
    assert [d.id for d in plan.prefill_devices] == [1, 3]
    assert [d.id for d in plan.decode_devices] == [0, 2]


def test_plan_explicit_unknown_id_raises():
    cfg = DisaggregationConfig(enabled=True, prefill_devices=(7, ),
                               decode_devices=(0, ))
    with pytest.raises(ValueError, match="not in the local set"):
        plan_groups(cfg, devices=_devs(4))


def test_plan_prefill_tp_must_divide_group():
    cfg = DisaggregationConfig(enabled=True, prefill_tp_size=3)
    with pytest.raises(ValueError, match="divide"):
        plan_groups(cfg, devices=_devs(8))  # prefill group has 4 devices


def test_config_validation():
    with pytest.raises(ValueError):
        DisaggregationConfig(prefill_fraction=1.0)
    with pytest.raises(ValueError):
        DisaggregationConfig(max_inflight_transfers=0)
    with pytest.raises(ValueError):
        DisaggregationConfig(stall_timeout_s=0.0)
    with pytest.raises(ValueError):
        DisaggregationConfig(prefill_devices=(0, 1), decode_devices=(1, 2))


# ---------------------------------------------------------------------------
# fallback: the split cannot form -> plain continuous-fusion serving
# ---------------------------------------------------------------------------


def test_fraction_rounds_to_zero_serves_via_fallback():
    """build_disagg_llama with a fraction that rounds to an empty prefill
    group returns (engine, None) and the scheduler serves normally —
    bit-identical to a plain engine."""
    import jax.numpy as jnp
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            ServingScheduler,
                                            build_llama_engine)
    from deepspeed_tpu.inference.v2.disagg import build_disagg_llama
    from deepspeed_tpu.models import LlamaConfig, init_llama

    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    _, params = init_llama(cfg, seed=5)
    prompt = [1, 5, 9, 2, 11, 7]

    reset_mesh_context()
    ref_eng = build_llama_engine(cfg, params=params, dtype=jnp.float32,
                                 kv_block_size=16)
    ref = ref_eng.generate([prompt], max_new_tokens=6)[0]

    reset_mesh_context()
    ec = RaggedInferenceEngineConfig(
        disaggregation={"enabled": True, "prefill_fraction": 0.01})
    engine, disagg = build_disagg_llama(cfg, params=params,
                                        engine_config=ec,
                                        dtype=jnp.float32, kv_block_size=16)
    assert disagg is None  # fraction rounded to an empty prefill group
    sched = ServingScheduler(engine, idle_wait=0.005,
                             disagg=disagg).start()
    try:
        h = sched.submit(prompt, max_new_tokens=6)
        assert h.result(timeout=300) == ref
        assert sched.stats["disagg"] is None
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# group scenarios: subprocess over 4 forced virtual host devices
# ---------------------------------------------------------------------------

_CHILD = r'''
import os, sys, time
import numpy as np
import jax.numpy as jnp
from deepspeed_tpu.comm.mesh import reset_mesh_context
from deepspeed_tpu.inference.v2 import (ServingScheduler,
                                        build_llama_engine,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.disagg import build_disagg_llama
from deepspeed_tpu.models import LlamaConfig, init_llama
from deepspeed_tpu.utils.fault_injection import (InjectedFault,
                                                 get_fault_injector)
from deepspeed_tpu.inference.v2 import disagg as dmod

BS = 16
CFG = LlamaConfig.tiny(num_key_value_heads=4)
_, PARAMS = init_llama(CFG, seed=5)

def prompts(n, lo=3, hi=4 * BS + 5, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 200, size=rng.integers(lo, hi)).tolist()
            for _ in range(n)]

# mixed request shapes: greedy, top-k sampled, top-p sampled, speculative
# (sampled + greedy), and a long multi-block document — every stream
# family the bit-identity contract covers. token_budget 24 makes the long
# prompts prefill across several ticks so handoffs ship chunk by chunk.
PS = prompts(6, seed=11)
PS[4] = (PS[4] * 3)[:3 * BS + 7]          # repetitive -> drafts accept
SUBMITS = [
    dict(prompt=PS[0], max_new_tokens=10),
    dict(prompt=PS[1], max_new_tokens=10, temperature=0.8, top_k=20,
         seed=7),
    dict(prompt=PS[2], max_new_tokens=10, temperature=1.1, top_p=0.9,
         seed=42),
    dict(prompt=PS[3], max_new_tokens=10, temperature=0.7, top_k=16,
         seed=3, speculative="prompt_lookup", num_draft_tokens=3,
         draft_ngram=2),
    dict(prompt=PS[4], max_new_tokens=10, speculative="prompt_lookup",
         num_draft_tokens=3, draft_ngram=2),
    dict(prompt=PS[5], max_new_tokens=10),
]

def build(disagg_on, durable=False, **dis_kw):
    reset_mesh_context()
    ec = RaggedInferenceEngineConfig(
        num_kv_blocks=96,
        durable_serving={"enabled": durable},
        serving_resilience={"tick_retries": 1,
                            "tick_retry_backoff_s": 0.01})
    if not disagg_on:
        return build_llama_engine(CFG, params=PARAMS, dtype=jnp.float32,
                                  kv_block_size=BS, engine_config=ec), None
    ec.disaggregation.enabled = True
    for k, v in dis_kw.items():
        setattr(ec.disaggregation, k, v)
    return build_disagg_llama(CFG, params=PARAMS, engine_config=ec,
                              dtype=jnp.float32, kv_block_size=BS)

def sched_for(engine, disagg, window=4):
    return ServingScheduler(engine, idle_wait=0.005, token_budget=24,
                            fused_decode_window=window,
                            disagg=disagg).start()

def run_all(engine, disagg, submits=SUBMITS):
    s = sched_for(engine, disagg)
    try:
        hs = [s.submit(**kw) for kw in submits]
        outs = [h.result(timeout=300) for h in hs]
        stats = s.stats
    finally:
        s.stop()
    return outs, stats

def wait_stopped(s, timeout=120):
    t0 = time.monotonic()
    while not s.stats["stopped"]:
        assert time.monotonic() - t0 < timeout, "loop never died"
        time.sleep(0.02)

def scenario_parity():
    ref, _ = run_all(*build(False))
    h0 = int(dmod._handoffs_total.value)
    d0 = int(dmod._degraded_total.value)
    outs, stats = run_all(*build(True))
    for i, (r, o) in enumerate(zip(ref, outs)):
        assert o == r, f"req {i + 1} diverged: {r} != {o}"
    d = stats["disagg"]
    assert d["handoffs_total"] - h0 >= len(SUBMITS), d
    assert d["degraded_total"] - d0 == 0, d
    print("PARITY-OK", d["handoffs_total"] - h0)

def scenario_crash():
    ref, _ = run_all(*build(False))
    # crash the loop EARLY (nth tick) so long prompts are mid-prefill and
    # the handoff queue is half-drained when the process dies
    get_fault_injector().configure({"faults": [{
        "site": "serve.crash", "nth": 4}]})
    eng, dis = build(True, durable=True)
    s1 = sched_for(eng, dis)
    hs = [s1.submit(**kw) for kw in SUBMITS]
    wait_stopped(s1)
    pre = [list(h._req.outputs) for h in hs]
    assert not all(len(p) >= 10 for p in pre), "crash fired after finish"
    get_fault_injector().reset()

    h0 = int(dmod._handoffs_total.value)
    eng2, dis2 = build(True, durable=True)
    s2 = sched_for(eng2, dis2)
    try:
        outs = []
        for uid in range(1, len(SUBMITS) + 1):
            h = s2.lookup(uid)
            outs.append(None if h is None else h.result(timeout=300))
        stats = s2.stats
    finally:
        s2.stop()
    for i, (r, p, o) in enumerate(zip(ref, pre, outs)):
        assert o is not None, f"req {i + 1} lost across the crash"
        assert o[:len(p)] == p, f"req {i + 1} rewrote pre-crash tokens"
        assert o == r, f"req {i + 1} not bit-identical: {r} != {o}"
    # the replay itself routed through the handoff queue
    assert stats["disagg"]["handoffs_total"] > h0, stats["disagg"]
    print("CRASH-OK", stats["disagg"]["handoffs_total"] - h0)

def scenario_quarantine():
    eng, dis = build(True)
    sub3 = SUBMITS[:3]
    ref, _ = run_all(eng, dis, sub3)
    # uid 2 poisons every dispatch that contains it (retries + bisect
    # probes included) on EITHER engine — the scheduler must quarantine
    # exactly it; the other requests and the other group keep going
    get_fault_injector().configure({"faults": [{
        "site": "serve.request_poison", "nth": 1, "times": 100000,
        "args": {"uid": 2}}]})
    pre_free = (dis.prefill_engine.free_blocks, eng.free_blocks)
    s = sched_for(eng, dis)
    try:
        hs = [s.submit(**kw) for kw in sub3]
        err = None
        try:
            hs[1].result(timeout=300)
        except InjectedFault as e:
            err = e
        assert err is not None, "poisoned request did not error"
        assert hs[0].result(timeout=300) == ref[0]
        assert hs[2].result(timeout=300) == ref[2]
        assert s.trace["quarantined"] == [2]
        assert not s.stats["stopped"]
        get_fault_injector().reset()
        # both groups still serve fresh traffic afterwards
        h4 = s.submit(sub3[0]["prompt"], max_new_tokens=10)
        assert h4.result(timeout=300) == ref[0]
    finally:
        s.stop()
    assert dis.prefill_engine.free_blocks == pre_free[0]
    assert eng.free_blocks == pre_free[1]
    print("QUARANTINE-OK")

def scenario_stall():
    eng, dis = build(True, stall_timeout_s=0.3)
    ref, _ = run_all(eng, dis)
    d0 = int(dmod._degraded_total.value)
    # wedge ONE transfer batch: the watchdog must degrade that request to
    # in-group prefill (eviction-style replay — stream unchanged) while
    # admission and every other stream keep moving
    get_fault_injector().configure({"faults": [{
        "site": "disagg.transfer_stall", "nth": 2}]})
    outs, stats = run_all(eng, dis)
    get_fault_injector().reset()
    for i, (r, o) in enumerate(zip(ref, outs)):
        assert o == r, f"req {i + 1} diverged across degrade: {r} != {o}"
    d = stats["disagg"]
    assert d["degraded_total"] - d0 >= 1, d
    assert not stats["stopped"]
    print("STALL-OK", d["degraded_total"] - d0)

if __name__ == "__main__":
    for name in sys.argv[1:]:
        globals()[f"scenario_{name}"]()
'''


def _run_child(tmp_path, force_host_devices, scenarios, timeout=1200):
    script = tmp_path / "disagg_child.py"
    script.write_text(_CHILD)
    env = force_host_devices(4, extra={
        "PYTHONPATH": REPO,
        "DS_TPU_JOURNAL_DIR": str(tmp_path / "journal"),
    })
    out = subprocess.run([sys.executable, str(script)] + list(scenarios),
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, \
        f"child failed:\n{out.stdout[-2000:]}\n{out.stderr[-4000:]}"
    return out.stdout


@pytest.mark.slow  # ~2 min subprocess engine builds; planner/fallback coverage stays tier-1
def test_stream_parity_and_crash_replay(tmp_path, force_host_devices):
    """Bit-identical streams disagg on vs off (greedy / sampled / fused
    speculative), then the durable crash-replay through a half-drained
    handoff queue — one child so the reference engines compile once."""
    out = _run_child(tmp_path, force_host_devices, ["parity", "crash"])
    assert "PARITY-OK" in out, out[-2000:]
    assert "CRASH-OK" in out, out[-2000:]


@pytest.mark.slow  # ~90 s subprocess engine builds; planner/fallback coverage stays tier-1
def test_quarantine_isolation_and_transfer_stall(tmp_path,
                                                 force_host_devices):
    """A poisoned request is quarantined within its group (everything else
    finishes exactly), and a wedged handoff transfer degrades to in-group
    prefill instead of stalling admission."""
    out = _run_child(tmp_path, force_host_devices, ["quarantine", "stall"])
    assert "QUARANTINE-OK" in out, out[-2000:]
    assert "STALL-OK" in out, out[-2000:]
