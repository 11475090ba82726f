"""CPU rehearsal of ``chip_smoke.py``'s control flow.

The script has no switch that lets it pass without a chip. Its phases are
functions of a model config and sizes: these tests run them at toy size on
the CPU (paths, arguments, HTTP, the engine and trainer entry points), show
that what only a chip can show is refused on a CPU's facts, and that the
script itself exits non-zero here.
"""

import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
from deepspeed_tpu.models import LlamaConfig

REPO = os.path.dirname(os.path.abspath(chip_smoke.__file__))
TINY = LlamaConfig.tiny(num_key_value_heads=4, sliding_window=48)


def test_serving_phase_rehearsal():
    facts = chip_smoke.serving_phase(
        TINY, dtype=jnp.bfloat16, prompt_lens=(5, 20, 30, 70), new_tokens=6,
        in_flight=3, logit_prompts=(20, 30), logit_tol=5e-2)
    assert facts["programs"] >= 2 and facts["compiles"] >= 2
    assert set(facts["logit_rel_err"]) == {20, 30}
    # a CPU's facts (dense backend, the default pool, no fused window)
    # cannot pass for a chip's
    with pytest.raises(AssertionError):
        chip_smoke.check_serving_on_chip(facts, 16 << 30)


def test_training_phase_rehearsal():
    cfg = dataclasses.replace(TINY, ce_chunk_size=96)
    facts = chip_smoke.training_phase(cfg, seq=64, batch=2)
    assert len(facts["losses"]) == 4 and facts["step_programs"] == 1
    with pytest.raises(AssertionError):  # no Pallas backward on a CPU
        chip_smoke.check_training_on_chip(facts, cfg.num_hidden_layers)


def test_four_chip_phase_rehearsal_on_forced_host_devices(force_host_devices):
    code = (
        "import dataclasses, chip_smoke\n"
        "from deepspeed_tpu.models import LlamaConfig\n"
        "cfg = LlamaConfig.tiny(num_key_value_heads=4, sliding_window=48,\n"
        "                       num_hidden_layers=1, ce_chunk_size=96)\n"
        "f = chip_smoke.four_chip_phase(cfg, seq=64, deep_layers=2)\n"
        "assert f['zero3']['mesh']['fsdp'] == 4, f['zero3']['mesh']\n"
        "assert f['data_parallel']['mesh']['data'] == 4\n"
        "print('REHEARSED')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=force_host_devices(4, extra={"PYTHONPATH": REPO}))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "REHEARSED" in proc.stdout
    assert "losses agree" in proc.stdout


def test_script_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for argv in ([], ["--four-chips"]):
        proc = subprocess.run([sys.executable, "chip_smoke.py", *argv],
                              cwd=REPO, capture_output=True, text=True,
                              timeout=300, env=env)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout  # no result line
        assert "no TPU" in proc.stderr


def test_depth_rules_at_published_widths():
    """16.9 GB of device memory: serving keeps a third for KV, training
    keeps its optimizer state under 85%."""
    cfg = chip_smoke.mistral_config()
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.sliding_window) == (4096, 14336, 32000, 32, 8, 4096)
    assert chip_smoke.param_count(cfg, 32) == 7_241_732_096  # the model card's 7.24B
    limit = 16_909_336_064
    depth = chip_smoke.serving_depth(cfg, limit)
    assert 2 * chip_smoke.param_count(cfg, depth) <= 2 * limit // 3 \
        < 2 * chip_smoke.param_count(cfg, depth + 1)
    assert chip_smoke.training_depth(cfg, limit) == 2
