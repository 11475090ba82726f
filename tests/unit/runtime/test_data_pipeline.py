"""Data pipeline tests (parity with reference
``tests/unit/runtime/test_data_efficiency.py`` + indexed dataset tests)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.data_pipeline import (CurriculumScheduler, DeepSpeedDataSampler,
                                                 MMapIndexedDataset, MMapIndexedDatasetBuilder,
                                                 RandomLayerTokenDrop, RandomLTDScheduler)
from deepspeed_tpu.runtime.data_pipeline.data_routing import (random_ltd_scatter,
                                                              random_ltd_select)


def test_curriculum_fixed_linear():
    sched = CurriculumScheduler({
        "min_difficulty": 8, "max_difficulty": 64, "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8},
    })
    assert sched.get_difficulty(0) == 8
    assert sched.get_difficulty(100) == 64
    mid = sched.get_difficulty(50)
    assert 8 <= mid <= 64 and mid % 8 == 0
    # monotone
    vals = [sched.update_difficulty(s) for s in range(0, 110, 10)]
    assert vals == sorted(vals)


def test_curriculum_fixed_root():
    sched = CurriculumScheduler({
        "min_difficulty": 8, "max_difficulty": 64, "schedule_type": "fixed_root",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8,
                            "root_degree": 2},
    })
    lin = CurriculumScheduler({
        "min_difficulty": 8, "max_difficulty": 64, "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8},
    })
    # sqrt schedule ramps faster early
    assert sched.get_difficulty(25) >= lin.get_difficulty(25)
    assert sched.get_difficulty(100) == 64


def test_curriculum_fixed_discrete():
    sched = CurriculumScheduler({
        "min_difficulty": 1, "max_difficulty": 3, "schedule_type": "fixed_discrete",
        "schedule_config": {"difficulty": [1, 2, 3], "max_step": [5, 10]},
    })
    assert sched.get_difficulty(3) == 1
    assert sched.get_difficulty(7) == 2
    assert sched.get_difficulty(100) == 3


def test_curriculum_custom():
    sched = CurriculumScheduler({
        "min_difficulty": 1, "max_difficulty": 10, "schedule_type": "custom",
    })
    sched.set_custom_get_difficulty(lambda step: min(1 + step, 10))
    assert sched.get_difficulty(3) == 4


def test_indexed_dataset_roundtrip(tmp_path):
    prefix = str(tmp_path / "ds")
    builder = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
    samples = [np.arange(n, dtype=np.int32) for n in (5, 17, 3, 256)]
    for s in samples[:2]:
        builder.add_item(s)
    builder.end_document()
    for s in samples[2:]:
        builder.add_item(s)
    builder.end_document()
    builder.finalize()

    assert MMapIndexedDataset.exists(prefix)
    ds = MMapIndexedDataset(prefix)
    assert len(ds) == 4
    for got, want in zip(ds[0:4], samples):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ds.sizes, [5, 17, 3, 256])
    np.testing.assert_array_equal(ds.doc_idx, [0, 2, 4])
    # partial reads
    np.testing.assert_array_equal(ds.get(3, offset=10, length=5), np.arange(10, 15))


def test_data_sampler_partitions_ranks():
    n, mbs, dp = 64, 4, 2
    seen = {r: [] for r in range(dp)}
    for r in range(dp):
        sampler = DeepSpeedDataSampler(total_samples=n, micro_batch_size=mbs,
                                       data_parallel_rank=r, data_parallel_size=dp,
                                       shuffle=True, seed=7)
        for mb in sampler:
            assert len(mb) == mbs
            seen[r].extend(mb.tolist())
    # disjoint + complete coverage
    assert not (set(seen[0]) & set(seen[1]))
    assert set(seen[0]) | set(seen[1]) == set(range(n))


def test_data_sampler_curriculum_filters():
    n = 128
    metrics = np.arange(n)  # difficulty = index
    sched = CurriculumScheduler({
        "min_difficulty": 16, "max_difficulty": n, "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 8},
    })
    sampler = DeepSpeedDataSampler(total_samples=n, micro_batch_size=8,
                                   curriculum_scheduler=sched, metric_values=metrics,
                                   shuffle=False, seed=0)
    first = next(iter(sampler))
    # first batch drawn while difficulty is low -> only easy samples
    assert first.max() <= 48


def test_random_ltd_select_scatter():
    rng = jax.random.PRNGKey(0)
    h = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
    idx, sub = random_ltd_select(rng, h, keep=4)
    assert sub.shape == (2, 4, 4)
    assert (np.diff(np.asarray(idx), axis=1) > 0).all()  # sorted order kept
    out = random_ltd_scatter(h, sub * 0, idx)
    # dropped tokens untouched, kept tokens zeroed
    kept_mask = np.zeros((2, 8), bool)
    for b in range(2):
        kept_mask[b, np.asarray(idx)[b]] = True
    np.testing.assert_array_equal(np.asarray(out)[~kept_mask], np.asarray(h)[~kept_mask])
    assert (np.asarray(out)[kept_mask] == 0).all()


def test_random_ltd_layer_and_scheduler():
    def layer_fn(params, x):
        return x * params

    wrapped = RandomLayerTokenDrop(layer_fn)
    h = jnp.ones((2, 16, 4))
    out = wrapped(2.0, h, keep=8, rng=jax.random.PRNGKey(1))
    assert float(out.sum()) == 2 * 16 * 4 + 2 * 8 * 4  # half doubled
    full = wrapped(2.0, h, keep=16, rng=jax.random.PRNGKey(1))
    assert float(full.sum()) == 2 * 2 * 16 * 4

    sched = RandomLTDScheduler({"random_ltd_schedule": {
        "start_value": 128, "max_value": 512, "step_size": 16, "schedule_steps": 100}})
    assert sched.update_seq(0) == 128
    assert sched.update_seq(100) == 512
    assert sched.update_seq(50) % 16 == 0


class TestEngineDataEfficiency:
    """The engine drives the schedulers (reference engine.py:349-356 init,
    :1877-1883 forward hooks) — not just standalone math."""

    def _seq_probe_model(self):
        import flax.linen as nn

        class SeqProbe(nn.Module):
            """Loss encodes the *static* seqlen the compiled step saw."""

            @nn.compact
            def __call__(self, ids, labels=None):
                h = nn.Dense(4)(jnp.ones((1, 4), jnp.float32))
                return jnp.float32(ids.shape[1]) + 0.0 * jnp.sum(h)

        model = SeqProbe()
        params = model.init(jax.random.PRNGKey(0), jnp.ones((2, 32), jnp.int32))["params"]
        return model, params

    def test_curriculum_seqlen_ramps_in_engine(self):
        import deepspeed_tpu
        model, params = self._seq_probe_model()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={
                "train_batch_size": jax.device_count() * 2,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "curriculum_learning": {
                    "enabled": True, "curriculum_type": "seqlen",
                    "min_difficulty": 8, "max_difficulty": 32,
                    "schedule_type": "fixed_linear",
                    "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 8},
                },
            })
        assert engine.curriculum_enabled_legacy()
        ids = jnp.ones((engine.train_batch_size(), 32), jnp.int32)
        seen = []
        for _ in range(6):
            loss = engine.forward(ids, labels=ids)
            engine.backward(loss)
            engine.step()
            seen.append(int(float(loss)))
        # seqlen actually ramps: starts at min difficulty, ends at full length
        assert seen[0] == 8
        assert seen[-1] == 32
        assert seen == sorted(seen)

    def test_random_ltd_keep_injected_and_annealed(self):
        import deepspeed_tpu
        import flax.linen as nn

        class LTDProbe(nn.Module):
            """Loss encodes the static keep-count injected by the engine."""

            @nn.compact
            def __call__(self, x, random_ltd_keep=None):
                h = nn.Dense(4)(x)
                if random_ltd_keep is not None:
                    h = h[:, :random_ltd_keep]  # static slice: needs keep static
                return 0.0 * jnp.mean(h**2) + jnp.float32(
                    -1 if random_ltd_keep is None else random_ltd_keep)

        model = LTDProbe()
        x = jnp.ones((2, 16, 4), jnp.float32)
        params = model.init(jax.random.PRNGKey(0), x)["params"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={
                "train_batch_size": jax.device_count() * 2,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "data_efficiency": {"data_routing": {
                    "enabled": True,
                    "random_ltd": {"enabled": True, "random_ltd_schedule": {
                        "start_value": 4, "max_value": 16, "step_size": 4,
                        "schedule_steps": 4}},
                }},
            })
        assert engine.random_ltd_enabled()
        xb = jnp.ones((engine.train_batch_size(), 16, 4), jnp.float32)
        seen = []
        for _ in range(6):
            loss = engine.forward(xb)
            engine.backward(loss)
            engine.step()
            seen.append(int(float(loss)))
        assert seen[0] == 4      # start_value at step 0
        assert seen[-1] == 16    # annealed to full length
        assert seen == sorted(seen)

    def test_scheduler_state_checkpoints(self, tmp_path):
        import deepspeed_tpu
        model, params = self._seq_probe_model()
        cfg = {
            "train_batch_size": jax.device_count() * 2,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "curriculum_learning": {
                "enabled": True, "curriculum_type": "seqlen",
                "min_difficulty": 8, "max_difficulty": 32,
                "schedule_type": "fixed_linear",
                "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 8},
            },
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=cfg)
        ids = jnp.ones((engine.train_batch_size(), 32), jnp.int32)
        for _ in range(3):
            loss = engine.forward(ids, labels=ids)
            engine.backward(loss)
            engine.step()
        diff = engine.curriculum_scheduler_legacy.get_current_difficulty()
        assert diff > 8
        engine.save_checkpoint(str(tmp_path), tag="t1")

        # the engine takes ownership of (and donates) its params — build
        # fresh ones for the resuming engine, as a real restart would
        model2, params2 = self._seq_probe_model()
        engine2, _, _, _ = deepspeed_tpu.initialize(
            model=model2, model_parameters=params2, config=cfg)
        engine2.load_checkpoint(str(tmp_path), tag="t1")
        assert engine2.curriculum_scheduler_legacy.get_current_difficulty() == diff
        assert engine2.global_steps == 3


class TestDataAnalyzer:

    def test_map_reduce_seqlen(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (DataAnalyzer,
                                                                       load_metric)
        rng = np.random.default_rng(0)
        dataset = [np.zeros(rng.integers(4, 64), dtype=np.int32) for _ in range(200)]
        an = DataAnalyzer(dataset, save_path=str(tmp_path))
        stats = an.run_map_reduce()
        assert stats["seqlen"]["num_samples"] == 200
        vals = load_metric(str(tmp_path), "seqlen")
        np.testing.assert_array_equal(vals, [len(s) for s in dataset])
        order = np.load(tmp_path / "seqlen_metric_to_sample.npy")
        assert (np.diff(vals[order]) >= 0).all()  # sorted by difficulty

    def test_feeds_curriculum_sampler(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (DataAnalyzer,
                                                                       load_metric)
        from deepspeed_tpu.runtime.data_pipeline.data_sampler import DeepSpeedDataSampler
        from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
        rng = np.random.default_rng(1)
        dataset = [np.zeros(rng.integers(4, 64), dtype=np.int32) for _ in range(128)]
        DataAnalyzer(dataset, save_path=str(tmp_path)).run_map_reduce()
        metric = load_metric(str(tmp_path), "seqlen")
        sched = CurriculumScheduler({"curriculum_type": "seqlen",
                                     "min_difficulty": 8, "max_difficulty": 64,
                                     "schedule_type": "fixed_linear",
                                     "schedule_config": {"total_curriculum_step": 10,
                                                         "difficulty_step": 1}})
        sampler = DeepSpeedDataSampler(total_samples=128, micro_batch_size=4,
                                       curriculum_scheduler=sched, metric_values=metric)
        batch = next(iter(sampler))
        # early curriculum: only short samples are eligible
        assert all(metric[i] <= 64 for i in batch)


class TestDistributedDataAnalyzer:
    """Worker-sharded file map-reduce + SPMD analyzer (reference
    data_analyzer.py:455 DistributedDataAnalyzer): every execution shape
    must produce bit-identical artifacts to the single-process run."""

    @staticmethod
    def _dataset(n=97):
        rng = np.random.default_rng(7)
        return [rng.integers(0, 50, size=rng.integers(4, 40)).astype(np.int32)
                for _ in range(n)]

    def test_worker_sharded_matches_single_process(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            DataAnalyzer, load_metric, load_accumulated, metric_seqlen,
            metric_vocab_freq, SINGLE, ACCUMULATE)
        ds = self._dataset()
        names = ["seqlen", "vocab_freq"]
        fns = [metric_seqlen, metric_vocab_freq(50)]
        types = [SINGLE, ACCUMULATE]

        single = tmp_path / "single"
        DataAnalyzer(ds, metric_names=names, metric_functions=fns,
                     metric_types=types, save_path=str(single)).run_map_reduce()

        sharded = tmp_path / "sharded"
        # workers 1 and 2 map first; worker 0 merges their published partials
        for k in (1, 2):
            DataAnalyzer(ds, num_workers=3, worker_id=k, metric_names=names,
                         metric_functions=fns, metric_types=types,
                         save_path=str(sharded)).run_map()
        stats = DataAnalyzer(ds, num_workers=3, worker_id=0, metric_names=names,
                             metric_functions=fns, metric_types=types,
                             save_path=str(sharded)).run_map_reduce()
        assert stats["seqlen"]["num_samples"] == len(ds)
        np.testing.assert_array_equal(load_metric(str(sharded), "seqlen"),
                                      load_metric(str(single), "seqlen"))
        np.testing.assert_array_equal(load_accumulated(str(sharded), "vocab_freq"),
                                      load_accumulated(str(single), "vocab_freq"))
        # token conservation: accumulated counts == total tokens
        assert load_accumulated(str(sharded), "vocab_freq").sum() == \
            sum(len(s) for s in ds)

    def test_nonzero_worker_waits_for_reduce(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import DataAnalyzer
        ds = self._dataset(20)
        # worker 1 with nothing published must time out, not hang forever
        an = DataAnalyzer(ds, num_workers=2, worker_id=1, save_path=str(tmp_path),
                          merge_timeout=1.0)
        an.run_map()
        with pytest.raises(TimeoutError):
            an.run_map_reduce()

    def test_merge_times_out_on_missing_partials(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import DataAnalyzer
        ds = self._dataset(20)
        an = DataAnalyzer(ds, num_workers=4, worker_id=0, save_path=str(tmp_path),
                          merge_timeout=1.0)
        an.run_map()  # only worker 0's partial exists
        with pytest.raises(TimeoutError, match="missing partial"):
            an.run_reduce()

    def test_spmd_two_process_matches_single(self, tmp_path):
        """2 real JAX processes: DistributedDataAnalyzer's allgather merge
        equals the single-process artifacts."""
        import os as _os
        import socket
        import subprocess
        import sys
        import textwrap
        from deepspeed_tpu.launcher.runner import build_commands
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            DataAnalyzer, load_metric)

        ds = self._dataset(61)
        single = tmp_path / "single"
        DataAnalyzer(ds, save_path=str(single)).run_map_reduce()

        child = textwrap.dedent("""
            import sys
            import numpy as np
            import deepspeed_tpu.comm as dist
            from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
                DistributedDataAnalyzer)
            dist.init_distributed()
            rng = np.random.default_rng(7)
            ds = [rng.integers(0, 50, size=rng.integers(4, 40)).astype(np.int32)
                  for _ in range(61)]
            DistributedDataAnalyzer(ds, save_path=sys.argv[1]).run_map_reduce()
            # ACCUMULATE with an EMPTY shard: 1 sample over 2 processes —
            # the padded allgather must not shape-mismatch (regression)
            from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
                metric_vocab_freq, ACCUMULATE)
            DistributedDataAnalyzer(
                ds[:1], metric_names=["vf"],
                metric_functions=[metric_vocab_freq(50)],
                metric_types=[ACCUMULATE],
                save_path=sys.argv[1] + "_acc").run_map_reduce()
            print("ANALYZER_OK", flush=True)
        """)
        script = tmp_path / "child.py"
        script.write_text(child)
        out_dir = tmp_path / "spmd"
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        repo = _os.path.abspath(_os.path.join(_os.path.dirname(__file__),
                                              "..", "..", ".."))
        cmds = build_commands(["localhost", "localhost"], "127.0.0.1", port,
                              str(script), [str(out_dir)],
                              {"JAX_PLATFORMS": "cpu", "PYTHONPATH": repo,
                               "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
        env = dict(_os.environ)
        procs = [subprocess.Popen(c, env=env, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
                 for c in cmds]
        outs = [p.communicate(timeout=300)[0] for p in procs]
        for p, o in zip(procs, outs):
            assert p.returncode == 0 and "ANALYZER_OK" in o, o[-2000:]
        np.testing.assert_array_equal(load_metric(str(out_dir), "seqlen"),
                                      load_metric(str(single), "seqlen"))
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            load_accumulated)
        acc = load_accumulated(str(out_dir) + "_acc", "vf")
        assert acc.sum() == len(ds[0])  # one sample's tokens, empty shard ok

    def test_rerun_with_new_run_id_ignores_stale_files(self, tmp_path):
        """A second analysis in the same save_path must not consume the
        first run's partials or done marker (regression: reruns silently
        merged stale data)."""
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            DataAnalyzer, load_metric)
        ds1 = self._dataset(30)
        for k in (1,):
            DataAnalyzer(ds1, num_workers=2, worker_id=k,
                         save_path=str(tmp_path), run_id="a").run_map()
        DataAnalyzer(ds1, num_workers=2, worker_id=0,
                     save_path=str(tmp_path), run_id="a").run_map_reduce()
        v1 = load_metric(str(tmp_path), "seqlen")

        ds2 = self._dataset(30)[::-1]  # different data, same length
        # worker 1 of run "b" must TIME OUT waiting for run b's reduce even
        # though run a's done marker sits in the directory
        an_b1 = DataAnalyzer(ds2, num_workers=2, worker_id=1,
                             save_path=str(tmp_path), run_id="b",
                             merge_timeout=1.0)
        with pytest.raises(TimeoutError, match="run_id=b"):
            an_b1.run_map_reduce()
        # and run b's reduce merges only run-b partials
        DataAnalyzer(ds2, num_workers=2, worker_id=0,
                     save_path=str(tmp_path), run_id="b").run_map_reduce()
        v2 = load_metric(str(tmp_path), "seqlen")
        np.testing.assert_array_equal(v2, [len(s) for s in ds2])
        assert not np.array_equal(v1, v2)


@pytest.mark.world_size(8)
def test_engine_wires_curriculum_data_sampling(tmp_path):
    """End-to-end data-efficiency pipeline (reference deepspeed_io →
    DeepSpeedDataSampler): analyzer artifacts + data_sampling config →
    engine.training_dataloader serves difficulty-gated batches."""
    import sys as _sys
    import os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    from simple_model import simple_model_and_params
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from deepspeed_tpu.runtime.data_pipeline.data_analyzer import DataAnalyzer

    rng = np.random.default_rng(3)
    lengths = rng.integers(4, 64, 256)
    dataset = [np.zeros(n, np.int32) for n in lengths]
    DataAnalyzer(dataset, save_path=str(tmp_path)).run_map_reduce()

    reset_mesh_context()
    model, params = simple_model_and_params(seed=0)
    eng, _, loader, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, training_data=dataset,
        collate_fn=lambda items: items,  # identity: we inspect raw samples
        config={"train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 1000,
                "data_efficiency": {"data_sampling": {
                    "enabled": True, "seed": 7,
                    "curriculum_learning": {
                        "enabled": True,
                        "curriculum_metrics": {"seqlen": {
                            "metric_path": str(tmp_path),
                            "min_difficulty": 8, "max_difficulty": 64,
                            "schedule_type": "fixed_linear",
                            "schedule_config": {"total_curriculum_step": 20,
                                                "difficulty_step": 1}}}}}}})
    assert loader is eng.training_dataloader and loader.sampler is not None
    it = iter(loader)
    first = next(it)
    # early curriculum: every drawn sample obeys the entry difficulty bound
    assert len(first) == 16
    assert max(len(s) for s in first) <= 8 + 64 * 2 // 20 + 3  # early ramp
    # later batches (difficulty ~47 by step 14 of the 20-step ramp) may
    # include long samples; 256 samples / 16 = 16 batches per epoch
    for _ in range(13):
        batch = next(it)
    assert max(len(s) for s in batch) > 32


@pytest.mark.world_size(8)
def test_engine_rejects_multi_metric_sampling(tmp_path):
    import sys as _sys
    import os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    from simple_model import simple_model_and_params
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context

    reset_mesh_context()
    model, params = simple_model_and_params(seed=0)
    with pytest.raises(ValueError, match="exactly one metric"):
        deepspeed_tpu.initialize(
            model=model, model_parameters=params, training_data=[1, 2, 3],
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "data_efficiency": {"data_sampling": {
                        "enabled": True,
                        "curriculum_learning": {
                            "enabled": True,
                            "curriculum_metrics": {"a": {}, "b": {}}}}}})


@pytest.mark.world_size(8)
def test_curriculum_sampler_state_survives_checkpoint(tmp_path):
    """Sampler consumed_samples + difficulty resume from the checkpoint:
    a restart must NOT replay easy/already-consumed batches."""
    import sys as _sys
    import os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    from simple_model import simple_model_and_params
    import deepspeed_tpu
    import jax.numpy as _jnp
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from deepspeed_tpu.runtime.data_pipeline.data_analyzer import DataAnalyzer

    rng = np.random.default_rng(4)
    dataset = [np.zeros(n, np.int32) for n in rng.integers(4, 64, 128)]
    DataAnalyzer(dataset, save_path=str(tmp_path / "an")).run_map_reduce()
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "steps_per_print": 1000,
           "data_efficiency": {"data_sampling": {
               "enabled": True, "seed": 7,
               "curriculum_learning": {
                   "enabled": True,
                   "curriculum_metrics": {"seqlen": {
                       "metric_path": str(tmp_path / "an"),
                       "min_difficulty": 8, "max_difficulty": 64,
                       "schedule_type": "fixed_linear",
                       "schedule_config": {"total_curriculum_step": 20,
                                           "difficulty_step": 1}}}}}}}

    def mk():
        reset_mesh_context()
        model, params = simple_model_and_params(seed=0)
        return deepspeed_tpu.initialize(model=model, model_parameters=params,
                                        training_data=dataset,
                                        collate_fn=lambda it: it, config=cfg)[0]

    e1 = mk()
    it = iter(e1.training_dataloader)
    for _ in range(5):
        next(it)
    # a real step so the engine has params/opt state to checkpoint
    x = _jnp.ones((16, 16), _jnp.float32)
    loss = e1.forward(x, _jnp.zeros_like(x))
    e1.backward(loss)
    e1.step()
    e1.save_checkpoint(tmp_path / "ck")
    consumed = e1.training_dataloader.sampler.consumed_samples
    # the generator pauses AT the 5th yield, before its commit — the
    # in-flight batch replays on resume (never skips data)
    assert consumed == 4 * 16

    e2 = mk()
    e2.load_checkpoint(str(tmp_path / "ck"))
    assert e2.training_dataloader.sampler.consumed_samples == consumed
    # and the next batch continues at the advanced difficulty, not step 0
    nxt = next(iter(e2.training_dataloader))
    assert max(len(s) for s in nxt) > 8  # past the entry difficulty
