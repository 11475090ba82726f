"""The engine's checkpoint engine is built at its first use: a run that never
saves or loads never imports orbax; a run that saves on a signal, inside a
grace period, builds it in ``__init__``.

A pytest worker's ``sys.modules`` already holds orbax from other files, so
the cases run in ONE child process (a module-scoped fixture: one jax
start-up) that prints what it saw as JSON; each test reads its part."""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.utils.hostdev import force_host_devices_env

UNIT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(UNIT))
SPAN = "ds.checkpoint.engine_build"

CHILD = r'''
import json, os, signal, sys, tempfile

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import reset_mesh_context
from deepspeed_tpu.observability.tracing import get_tracer
from simple_model import simple_model_and_params

# what `import orbax.checkpoint` brings ("google.cloud" itself is a namespace
# package a bare interpreter already holds: its site's .pth files name it)
HEAVY = ("orbax", "tensorstore", "google.cloud.")


def heavy():
    return sorted(m for m in sys.modules if m.startswith(HEAVY))


def spans(prefix):
    return [{"name": s["name"], "sid": s["sid"], "parent": s["parent"]}
            for s in get_tracer().scopes(prefix)]


def engine(seed=0, resilience=None):
    reset_mesh_context()
    cfg = {"train_batch_size": 8, "steps_per_print": 1000,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "observability": {"enabled": True}}
    if resilience is not None:
        cfg["resilience"] = resilience
    model, params = simple_model_and_params(seed=seed)
    return deepspeed_tpu.initialize(model=model, model_parameters=params,
                                    config=cfg)[0]


def step(e):
    x = jnp.ones((8, 16))
    return e.train_batch(iter([(x, jnp.zeros_like(x))]))


def leaves(e):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(e.params)]


out = {}
tmp = tempfile.mkdtemp()

# a run that never saves
e1 = engine()
losses = [step(e1), step(e1)]
out["never_saved"] = {"finite": bool(np.all(np.isfinite(losses))),
                      "heavy_modules": heavy(),
                      "init": [s["name"] for s in spans("ds.init")],
                      "builds": spans("ds.checkpoint.")}

# periodic autosave alone has no deadline: still lazy after __init__
get_tracer().reset()
e = engine(resilience={"enabled": True, "preempt_save": False,
                       "save_dir": os.path.join(tmp, "periodic"),
                       "autosave_interval_steps": 100})
out["periodic"] = {"heavy_modules": heavy(),
                   "builds": spans("ds.checkpoint.")}
e.destroy()

# a save on a signal has a grace period: built in __init__, and the save
# that the signal asks for, the process's first, builds nothing and imports
# no module of any name (the step before it is e1's program again)
get_tracer().reset()
save_dir = os.path.join(tmp, "preempt")
e = engine(resilience={"enabled": True, "save_dir": save_dir})
init = {s["name"]: s for s in spans("ds.init")}
built = spans("ds.checkpoint.")
modules = set(sys.modules)
os.kill(os.getpid(), signal.SIGTERM)
step(e)
from deepspeed_tpu.checkpoint.engine import read_latest_tag, verify_checkpoint
tag = read_latest_tag(save_dir)
out["preempt"] = {
    "init_sid": init["ds.init"]["sid"], "built_in_init": built,
    "builds_after_the_save": spans("ds.checkpoint."),
    "preempted": bool(e.preempted), "tag": tag,
    "verified": list(verify_checkpoint(os.path.join(save_dir, tag))),
    "imported_by_the_save": sorted(set(sys.modules) - modules)}
e.destroy()

# save, then load into a second engine: one build each
get_tracer().reset()
ckpt = os.path.join(tmp, "roundtrip")
e1.save_checkpoint(ckpt, tag="t")
after_save = spans("ds.checkpoint.")
e1.save_checkpoint(ckpt, tag="t2")
after_second_save = spans("ds.checkpoint.")
e2 = engine(seed=1)
differ_before = any(not np.array_equal(a, b)
                    for a, b in zip(leaves(e1), leaves(e2)))
after_init = spans("ds.checkpoint.")
path, _ = e2.load_checkpoint(ckpt, tag="t")
out["roundtrip"] = {
    "after_save": after_save, "after_second_save": after_second_save,
    "after_second_init": after_init, "after_load": spans("ds.checkpoint."),
    "loaded": path is not None, "differ_before": differ_before,
    "equal_to_the_bit": all(
        a.dtype == b.dtype and a.tobytes() == b.tobytes()
        for a, b in zip(leaves(e1), leaves(e2))),
    "steps": [int(e1.global_steps), int(e2.global_steps)]}

# an engine the caller assigned is the one a save goes through
get_tracer().reset()
from deepspeed_tpu.checkpoint.engine import AsyncCheckpointEngine
calls = []


class Recording(AsyncCheckpointEngine):

    def save(self, *a, **kw):
        calls.append("save")
        return super().save(*a, **kw)

    def commit(self, tag):
        calls.append("commit")
        return super().commit(tag)


e4 = engine()
mine = Recording()
e4.checkpoint_engine = mine
ok = e4.save_checkpoint(os.path.join(tmp, "assigned"), tag="a")
out["assigned"] = {"calls": calls, "same": e4.checkpoint_engine is mine,
                   "saved": bool(ok), "builds": spans("ds.checkpoint."),
                   "verified": list(verify_checkpoint(
                       os.path.join(tmp, "assigned", "a")))}

print("LAZY_CKPT_JSON " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def seen():
    env = force_host_devices_env(
        1, extra={"PYTHONPATH": os.pathsep.join([REPO, UNIT])})
    proc = subprocess.run([sys.executable, "-c", CHILD], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("LAZY_CKPT_JSON ")][-1]
    return json.loads(line.split(" ", 1)[1])


def test_a_run_that_never_saves_never_imports_orbax(seen):
    s = seen["never_saved"]
    assert s["finite"]
    assert s["heavy_modules"] == []
    assert s["builds"] == []
    assert "ds.init" in s["init"]
    assert "ds.init.checkpoint_engine" not in s["init"]


def test_periodic_autosave_alone_stays_lazy(seen):
    assert seen["periodic"] == {"heavy_modules": [], "builds": []}


def test_preempt_save_builds_in_init_as_a_child_of_ds_init(seen):
    s = seen["preempt"]
    assert [b["name"] for b in s["built_in_init"]] == [SPAN]
    assert s["built_in_init"][0]["parent"] == s["init_sid"]


def test_the_save_a_signal_asks_for_builds_and_imports_nothing(seen):
    s = seen["preempt"]
    assert s["preempted"] and s["tag"] == "global_step1"
    assert s["verified"] == [True, "ok"]
    assert s["builds_after_the_save"] == s["built_in_init"]
    assert s["imported_by_the_save"] == []


def test_the_first_save_builds_once(seen):
    s = seen["roundtrip"]
    assert [b["name"] for b in s["after_save"]] == [SPAN]
    assert s["after_second_save"] == s["after_save"]
    assert s["after_second_init"] == s["after_save"]


def test_a_load_into_a_second_engine_builds_once_and_is_equal_to_the_bit(seen):
    s = seen["roundtrip"]
    assert [b["name"] for b in s["after_load"]] == [SPAN, SPAN]
    assert s["loaded"] and s["differ_before"] and s["equal_to_the_bit"]
    assert s["steps"] == [2, 2]


def test_an_assigned_engine_is_the_one_a_save_uses(seen):
    s = seen["assigned"]
    assert s["same"] and s["saved"]
    assert s["calls"] == ["save", "commit"]
    assert s["builds"] == []
    assert s["verified"] == [True, "ok"]
