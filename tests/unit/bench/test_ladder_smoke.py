"""Bench-ladder CPU smoke: compile + run each distinctive ladder-rung
PROGRAM CLASS at tiny dims so a ladder regression is caught in CI instead
of on the chip.

The real `_measure_config` refuses a CPU, so this smoke rebuilds the rung
engines the way the ladder does — same
`bench_engine_config` (including ``param_cast: model``), same LlamaConfig
knob mapping (scan True / chunked int / remat policy / head override) —
at CI-sized dims, and runs two fused steps each.
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))

import bench  # noqa: E402  (repo-root bench.py)
import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.comm.mesh import reset_mesh_context  # noqa: E402
from deepspeed_tpu.models import LlamaConfig, init_llama  # noqa: E402

# (remat, scan, heads) triples mirroring bench.measure()'s rung classes:
# scanned, selective-remat scanned, full-remat floor, head-shape override,
# chunked scan, unrolled
RUNG_CLASSES = [
    (False, True, None),
    ("dots_saveable", True, None),
    (True, True, None),
    (False, True, 8),
    (False, 2, None),     # chunked: scan_chunk_size=2 at 4 layers
    (False, False, None),
]


def tiny_rung_cfg(remat, scan, heads):
    """bench.bench_config's knob mapping at CI dims (mirrors bench.py:60)."""
    policy = remat if isinstance(remat, str) else None
    kw = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
              num_hidden_layers=4, num_attention_heads=16,
              num_key_value_heads=16, max_position_embeddings=128,
              remat=bool(remat), remat_policy=policy, ce_chunk_size=100)
    if heads is not None:
        kw.update(num_attention_heads=heads, num_key_value_heads=heads)
    if isinstance(scan, int) and not isinstance(scan, bool) and scan > 1:
        kw.update(scan_layers=True, scan_chunk_size=scan)
    else:
        kw.update(scan_layers=bool(scan))
    return LlamaConfig(**kw)


@pytest.mark.parametrize("remat,scan,heads", RUNG_CLASSES,
                         ids=lambda v: str(v))
def test_ladder_rung_class_compiles_and_steps(remat, scan, heads):
    reset_mesh_context()
    cfg = tiny_rung_cfg(remat, scan, heads)
    model, params = init_llama(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=bench.bench_engine_config(8))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, 64)), jnp.int32)
    l0 = float(engine.fused_train_step(ids, labels=ids))
    l1 = float(engine.fused_train_step(ids, labels=ids))
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0  # same batch twice: the step must actually learn


def test_bench_engine_config_parses():
    """Every key bench_engine_config emits must be consumed by the config
    system (an inert key here = silently different bench semantics)."""
    from deepspeed_tpu.config.config import DeepSpeedTpuConfig
    c = DeepSpeedTpuConfig(bench.bench_engine_config(8), world_size=8)
    assert c.train_batch_size == 8
    assert c.bf16_enabled
    assert c.param_cast == "model"


# (ladder ORDERING invariants are pinned behaviorally by
# tests/unit/bin/test_bench_ladder.py — this file guards the rung PROGRAM
# classes compile+step, which that test stubs out)


@pytest.mark.slow
def test_bench_serving_cpu_sweep_survives(tmp_path):
    """bench_serving.py must complete its CPU sweep end-to-end and write
    well-formed JSON — the same don't-discover-breakage-on-the-chip guard
    as the ladder rung smoke."""
    import json
    import subprocess
    out = tmp_path / "BS.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "..", "..")
    r = subprocess.run([sys.executable,
                        os.path.join(env["PYTHONPATH"], "bench_serving.py"),
                        "--out", str(out)],
                       capture_output=True, text=True, timeout=1500, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(out.read_text())
    assert doc["results"], doc
    assert not out.with_suffix(".json.partial").exists()
    for row in doc["results"]:
        assert np.isfinite(row.get("decode_tok_per_s", row.get("tok_per_s", 1.0)))
