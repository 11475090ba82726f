"""End-to-end training observability acceptance (the PR's contract):

run a real engine N optimizer steps under the async window with the
telemetry on and assert, from the exported artifacts alone, that

- ``ds_train_step_seconds`` count == optimizer steps taken;
- the goodput categories sum to the elapsed wall clock (±5%);
- every watched compile key has nonzero compile samples and ZERO
  recompiles on the steady-state tail;
- MFU lands in (0, 1];
- the monitor registry bridge fires exactly once per window drain and
  survives its log dir being deleted mid-run;
- the Prometheus textfile is written atomically and ``ds_top --file``
  renders it.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from simple_model import simple_model_and_params  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.comm.mesh import reset_mesh_context  # noqa: E402
from deepspeed_tpu.observability import get_registry  # noqa: E402

REPO = os.path.join(os.path.dirname(__file__), "..", "..", "..")


def make_engine(tmp_path, **over):
    reset_mesh_context()
    get_registry().reset()
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 1000,
           "async_pipeline": {"enabled": True, "sync_interval": 4},
           "csv_monitor": {"enabled": True,
                           "output_path": str(tmp_path / "logs"),
                           "job_name": "obs"},
           "registry_events": True,
           "observability": {"enabled": True,
                             "textfile": str(tmp_path / "ds.prom")}}
    cfg.update(over)
    model, params = simple_model_and_params(seed=0)
    engine, *_ = deepspeed_tpu.initialize(model=model,
                                          model_parameters=params,
                                          config=cfg)
    return engine


def batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
             jnp.zeros((8, 16)))
            for _ in range(n)]


def test_training_observability_acceptance(tmp_path, monkeypatch):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    calls = []
    orig = MonitorMaster.write_registry

    def counting(self, step, registry=None, prefix="", window_len=None):
        calls.append((step, window_len))
        return orig(self, step, registry=registry, prefix=prefix,
                    window_len=window_len)

    monkeypatch.setattr(MonitorMaster, "write_registry", counting)

    e = make_engine(tmp_path)
    for x, y in batches(8):
        e.fused_train_step(x, y)
    e._drain_async_window()
    reg = get_registry()

    # 1. per-step histogram: exactly one sample per optimizer step
    assert e.global_steps == 8
    assert reg.get("ds_train_step_seconds").count == 8

    # 2. goodput: categories partition the wall clock (±5%)
    led = e._train_obs.ledger
    wall, attributed = led.wall_seconds(), led.attributed_seconds()
    assert attributed == pytest.approx(wall, rel=0.05)
    t = led.totals()
    assert t["useful_step"] > 0 and t["restart"] > 0
    assert reg.get("ds_goodput_fraction").value == pytest.approx(
        led.goodput_fraction())

    # 3. compile keys: the fused step compiled once, zero steady-state
    # recompiles, and later dispatches were cache hits
    compiled_keys = {m.labels["key"]: m.value
                     for m in reg.series("ds_compiles_total") if m.value}
    assert "train_step_fused" in compiled_keys
    for m in reg.series("ds_recompiles_total"):
        assert m.value == 0, m.labels
    hits = {m.labels["key"]: m.value
            for m in reg.series("ds_compile_cache_hits_total")}
    assert hits["train_step_fused"] == 7
    assert reg.get("ds_compile_seconds",
                   labels={"key": "train_step_fused"}).count == 1

    # 4. MFU
    mfu = reg.get("ds_train_mfu").value
    assert 0.0 < mfu <= 1.0

    # 5. monitor bridge: exactly one write_registry per window drain
    # (8 steps / sync_interval 4 = 2 drains), stamped at window START
    assert [c for c in calls] == [(0, 4), (4, 4)]

    # 6. textfile exists, is a complete scrape body, and survives the
    # monitor log dir being deleted mid-run
    prom = tmp_path / "ds.prom"
    body = prom.read_text()
    assert body.endswith("\n") and "ds_train_step_seconds_count 8" in body
    import shutil
    shutil.rmtree(tmp_path / "logs")
    for x, y in batches(4, seed=1):
        e.fused_train_step(x, y)
    e._drain_async_window()  # must not raise with the log dir gone
    assert e.global_steps == 12
    assert reg.get("ds_train_step_seconds").count == 12

    # 7. ds_top renders the textfile (human and json modes)
    top = os.path.join(REPO, "bin", "ds_top")
    r = subprocess.run([sys.executable, top, "--file", str(prom)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "goodput" in r.stdout and "train_step_fused" in r.stdout
    rj = subprocess.run([sys.executable, top, "--file", str(prom),
                         "--json"],
                        capture_output=True, text=True, timeout=60)
    assert rj.returncode == 0, rj.stderr
    import json
    doc = json.loads(rj.stdout)
    assert doc["goodput_seconds"]["useful_step"] > 0
    assert "train_step_fused" in doc["compiles"]
    # the program's one cost analysis (published with the MFU) beside its
    # compile seconds, and in the console's table
    assert doc["compiles"]["train_step_fused"]["cost_analysis_seconds"] > 0
    assert "cost an." in r.stdout
    # and what jax reported of that compiling call, piece by piece: the
    # trace, the lowering and the backend's compile within its wall; what no
    # watched call claimed (the model's init) under the key "-"
    step = doc["compiles"]["train_step_fused"]
    pieces = step["pieces"]
    assert all(pieces[k] > 0 for k in ("trace", "lower", "backend"))
    assert pieces["trace"] + pieces["lower"] + pieces["backend"] <= step["seconds"]
    assert pieces["load"] <= pieces["backend"]
    assert doc["compiles"]["-"]["pieces"]["backend"] > 0
    header, = [ln for ln in r.stdout.splitlines() if ln.startswith("compile key")]
    assert header.split()[-6:] == ["trace", "lower", "backend", "load",
                                   "p.hit", "p.miss"]


def test_observability_disabled_is_silent(tmp_path):
    """enabled: false removes every recording path — no step histogram,
    no goodput series motion, no textfile."""
    e = make_engine(tmp_path, observability={"enabled": False})
    for x, y in batches(4):
        e.fused_train_step(x, y)
    e._drain_async_window()
    reg = get_registry()
    assert e._train_obs is None and e._obs_textfile is None
    h = reg.get("ds_train_step_seconds")
    assert h is None or h.count == 0
    assert not (tmp_path / "ds.prom").exists()


def test_sync_mode_publishes_per_step(tmp_path):
    """Without the async window the publish cadence is per optimizer
    step; counts and goodput hold the same invariants."""
    e = make_engine(tmp_path, async_pipeline={"enabled": False})
    for x, y in batches(3):
        loss = e.forward(x, y)
        e.backward(loss)
        e.step()
    reg = get_registry()
    assert reg.get("ds_train_step_seconds").count == e.global_steps == 3
    led = e._train_obs.ledger
    assert led.attributed_seconds() == pytest.approx(
        led.wall_seconds(), rel=0.05)
    assert (tmp_path / "ds.prom").exists()


def test_a_publish_attributes_its_own_seconds(tmp_path, monkeypatch):
    """What follows the last step's mark, the drain's exports, is attributed
    as the publish ends: with an export that takes a third of the run the
    categories still partition the wall clock, and the gauge agrees."""
    import time
    from deepspeed_tpu.observability.metrics import MetricsRegistry
    orig = MetricsRegistry.write_textfile

    def slow(self, path):
        time.sleep(0.25)
        return orig(self, path)

    monkeypatch.setattr(MetricsRegistry, "write_textfile", slow)
    e = make_engine(tmp_path)
    for x, y in batches(4):
        e.fused_train_step(x, y)
    led = e._train_obs.ledger
    wall, attributed = led.wall_seconds(), led.attributed_seconds()
    assert attributed == pytest.approx(wall, rel=0.05)
    assert get_registry().get("ds_goodput_fraction").value == pytest.approx(
        led.goodput_fraction())
    assert (tmp_path / "ds.prom").exists()


def test_checkpoint_spans_land_in_goodput(tmp_path):
    e = make_engine(tmp_path)
    for x, y in batches(4):
        e.fused_train_step(x, y)
    e.save_checkpoint(str(tmp_path / "ckpt"), tag="t0")
    e.load_checkpoint(str(tmp_path / "ckpt"), tag="t0")
    t = e._train_obs.ledger.totals()
    assert t["checkpoint_save"] > 0 and t["checkpoint_load"] > 0
    reg = get_registry()
    assert reg.get("ds_checkpoint_save_seconds").count >= 1
    assert reg.get("ds_checkpoint_load_seconds").count >= 1
