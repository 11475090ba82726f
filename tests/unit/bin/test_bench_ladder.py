"""Bench ladder contract tests (no chip needed).

These pin the invariants a short chip call depends on:
- every rung parses (5-tuple or 6-tuple with a head-count override);
- the ladder OPENS with scanned safety rungs (a short call lands a
  number first), then the unrolled bs8 program — the remaining big-HLO
  unrolled rung stays behind the full-remat floor;
- the 8h x hd128 rung is the SAME model (param count) as 16h x hd64, so
  its MFU is apples-to-apples (bench.py ranks rungs by vs_baseline);
- no chip means a non-zero exit: no host-CPU number, no older result.
"""

import numpy as np
import pytest


def _ladder(monkeypatch, **env):
    import bench
    for k in ("DS_BENCH_FAST", "DS_BENCH_LONGSEQ", "DS_BENCH_SCAN"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    captured = {}

    def fake_measure(batch, seq, iters, remat, scan=False, heads=None):
        captured.setdefault("rungs", []).append((batch, seq, remat, scan, heads))
        # pretend every rung OOMs so the full ladder unrolls
        raise RuntimeError("RESOURCE_EXHAUSTED (test)")

    monkeypatch.setattr(bench, "_measure_config", fake_measure)
    with pytest.raises(RuntimeError, match="all bench footprints OOMed"):
        bench.measure()
    return captured["rungs"]


def test_default_ladder_orders_reliable_rungs_first(monkeypatch):
    rungs = _ladder(monkeypatch)
    # the ladder OPENS with scanned safety rungs — a short window must land
    # a number before any big-HLO program
    assert rungs[0][3] is True and rungs[1][3] is True
    # the proven-best unrolled bs8 program (8/1 breakdown: 269 ms/step =
    # 0.68x bar) is promoted right after them; its compile is cache-warm
    assert rungs[2] == (8, 1024, False, False, None)
    # the full-remat floor still precedes the remaining unrolled monster
    # (that one's compile has never been proven cheap)
    monster = rungs.index((16, 1024, "dots_saveable", False, None))
    assert rungs.index((4, 1024, True, True, None)) < monster
    # the hd128 head-shape rung is present and scanned
    assert (8, 1024, False, True, 8) in rungs
    # the chunked-scan rung sits before the trailing unrolled monster
    assert rungs.index((8, 1024, False, 6, None)) < monster


def test_fast_ladder_is_scanned_with_fallbacks(monkeypatch):
    rungs = _ladder(monkeypatch, DS_BENCH_FAST="1")
    assert len(rungs) >= 3, "FAST mode must be a ladder, not a single rung"
    # opens scanned; exactly ONE unrolled rung (the cache-warm winner) —
    # fast mode must never queue a second cold big-HLO compile
    assert rungs[0][3] is True and rungs[1][3] is True
    assert sum(1 for r in rungs if r[3] is False) == 1
    assert rungs[-1][2] is True, "FAST ladder needs the full-remat floor"


def test_scan_only_filter_drops_unrolled(monkeypatch):
    rungs = _ladder(monkeypatch, DS_BENCH_SCAN="1")
    # per-layer scan ONLY: unrolled (False) and chunked (int) rungs are both
    # multi-minute compiles the mode exists to exclude
    assert rungs and all(r[3] is True for r in rungs)


def test_head_override_is_param_identical():
    import jax
    from bench import bench_config
    from deepspeed_tpu.models import init_llama

    n = lambda cfg: sum(int(np.prod(p.shape))
                        for p in jax.tree_util.tree_leaves(init_llama(cfg)[1]))
    c16 = bench_config(False, num_hidden_layers=1)
    c8 = bench_config(False, heads=8, num_hidden_layers=1)
    assert c8.head_dim_ == 128 and c16.head_dim_ == 64
    assert n(c16) == n(c8)


def test_bench_config_scan_value_mapping():
    """The ladder's scan value maps onto the model config in one place:
    False/True toggle per-layer scan; an int N>1 is chunked scan (N
    unrolled layers per scan step). 24 % 6 == 0 so the chunk rung traces."""
    from bench import bench_config
    assert bench_config(False).scan_layers is False
    c = bench_config(False, scan_layers=True)
    assert c.scan_layers and c.scan_chunk_size == 1
    c6 = bench_config(False, scan_layers=6)
    assert c6.scan_layers and c6.scan_chunk_size == 6
    assert c6.num_hidden_layers % c6.scan_chunk_size == 0


def test_no_chip_is_a_nonzero_exit():
    """A measurement that finds no TPU fails: no host-CPU sizing, no
    DIAGNOSTIC number."""
    import bench
    with pytest.raises(SystemExit) as e:
        bench._measure_config(8, 1024, 2, False)
    assert "no TPU" in str(e.value.code)


def test_parent_prints_result_or_fails(monkeypatch, capsys):
    """The parent prints the child's LAST result line, and a child that
    fails or prints none is a non-zero exit — never an older result."""
    import subprocess
    import types
    import bench

    def child(rc, out):
        return lambda *a, **k: types.SimpleNamespace(
            returncode=rc, stdout=out, stderr="boom")

    monkeypatch.setattr(subprocess, "run",
                        child(0, '{"value": 1}\nnoise\n{"value": 2}\n'))
    assert bench.supervise() == 0
    assert capsys.readouterr().out.strip() == '{"value": 2}'
    monkeypatch.setattr(subprocess, "run", child(1, ""))
    assert bench.supervise() == 1
    assert capsys.readouterr().out == ""
    monkeypatch.setattr(subprocess, "run", child(0, "no result line\n"))
    assert bench.supervise() != 0
    assert capsys.readouterr().out == ""


def test_journal_survives_a_torn_tail(tmp_path):
    """A writer killed mid-append must not void the good lines before it,
    and the next append starts on a fresh line."""
    import bench
    path = str(tmp_path / "j.jsonl")
    bench._journal_append(path, {"a": 1})
    with open(path, "a") as f:
        f.write("{truncated")
    bench._journal_append(path, {"a": 2})
    assert [r["a"] for r in bench._journal_records(path)] == [1, 2]


def test_triage_verdict_skips_proven_oom_rungs(tmp_path, monkeypatch):
    """A mem-triage 'oom' verdict (same rev + device kind, fresh) makes the
    ladder SKIP that rung — re-proving a known OOM costs a full uncacheable
    compile out of a chip call. Verdicts from another revision,
    another chip, or beyond the freshness window never skip anything."""
    import json
    import time as _time
    import bench

    monkeypatch.setattr(bench, "_triage_journal_path",
                        lambda: str(tmp_path / "mem_triage.jsonl"))
    monkeypatch.setattr(bench, "_git_rev", lambda: "cafe123")
    monkeypatch.setattr(bench, "_device_kind", lambda: "TPU v5e")

    bench.journal_triage_record(8, 1024, False, True, None, "oom")
    bench.journal_triage_record(8, 1024, "dots_saveable", True, None, "fit",
                                nbytes=12_000_000_000)
    assert bench._triage_verdict(8, 1024, False, True, None) == "oom"
    assert bench._triage_verdict(8, 1024, "dots_saveable", True, None) == "fit"
    assert bench._triage_verdict(4, 1024, False, True, None) is None  # unprobed

    rungs = _ladder(monkeypatch)
    assert (8, 1024, False, True, None) not in rungs, \
        "proven-OOM rung must be skipped"
    assert (8, 1024, "dots_saveable", True, None) in rungs  # fit still runs

    # a LATER fit verdict supersedes the old oom (e.g. after an HBM fix
    # landed in the same revision's working tree was re-probed)
    bench.journal_triage_record(8, 1024, False, True, None, "fit")
    assert bench._triage_verdict(8, 1024, False, True, None) == "fit"
    assert (8, 1024, False, True, None) in _ladder(monkeypatch)

    # scoping: other revision / other chip / stale -> verdict is ignored
    monkeypatch.setattr(bench, "_git_rev", lambda: "newrev99")
    assert bench._triage_verdict(8, 1024, "dots_saveable", True, None) is None
    monkeypatch.setattr(bench, "_git_rev", lambda: "cafe123")
    monkeypatch.setattr(bench, "_device_kind", lambda: "TPU v4")
    assert bench._triage_verdict(8, 1024, "dots_saveable", True, None) is None
    monkeypatch.setattr(bench, "_device_kind", lambda: "TPU v5e")
    rec = {"batch": 16, "seq": 1024, "remat": "dots_saveable", "scan": True,
           "heads": None, "status": "oom", "rev": "cafe123",
           "device_kind": "TPU v5e", "ts": _time.time() - 90 * 3600}
    with open(tmp_path / "mem_triage.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n" + "{torn")
    assert bench._triage_verdict(16, 1024, "dots_saveable", True, None) is None
    # the torn tail line must not void earlier verdicts
    assert bench._triage_verdict(8, 1024, False, True, None) == "fit"

    # a per-layer-scan verdict must NEVER suppress the chunked-scan rung
    # (scan=True vs scan=6 compile different programs)
    assert bench._triage_verdict(8, 1024, False, 6, None) is None
    bench.journal_triage_record(8, 1024, False, 6, None, "oom")
    assert bench._triage_verdict(8, 1024, False, 6, None) == "oom"
    assert bench._triage_verdict(8, 1024, False, True, None) == "fit"
    assert (8, 1024, False, 6, None) not in _ladder(monkeypatch)

    # no device kind (no backend at lookup time) -> never skip
    monkeypatch.setattr(bench, "_device_kind", lambda: None)
    assert bench._triage_verdict(8, 1024, False, True, None) is None


def test_breakdown_consults_triage_verdicts(monkeypatch, capsys):
    """breakdown()'s OOM-retry mini-ladder must also skip footprints the
    compile-only triage proved exceed HBM — its chip-session stages run
    after the triage and must not re-pay doomed compiles."""
    import bench
    monkeypatch.setattr(
        bench, "_triage_verdicts",
        lambda max_age_h=24.0: {(2, 128, False, False, None): "oom"})
    monkeypatch.delenv("DS_BENCH_SCAN", raising=False)
    with pytest.raises(RuntimeError,
                       match="all skipped by triage verdicts"):
        bench.breakdown()  # CPU sizing: single (2, False) footprint @seq128
    assert "triage: proven OOM" in capsys.readouterr().err
