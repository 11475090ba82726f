"""The readings that the limits of ``runners/train_steps_ouro.py`` stand on,
at the cell's own sizes on the chip:

    chiprun -- python3 benchmark/calibrate_ouro.py --seeds 2147480901,41

One timed first step of the cell's program (``first_step``) against the
float32 reference as it is, and against the reference made wrong in each way
``correct`` has to tell from it (``reference/ouro.py``'s ``wrong``): the final
norm after the last pass alone, three passes, the pre-norms only, ``p_t``
without the survival product, ``beta`` 0, the gate without its bias, every
matmul's operands rounded to fp8's three mantissa bits (the nearest precision
below the bf16 the configuration states). ``bf16``, the operands rounded to the
configuration's OWN precision, is read too and required of nothing. The
distance of the sound program from a wrong reference is what a program wrong
in that way would read against the sound reference. The program's step runs
first and its engine is dropped before the references run, one at a time (the
chip holds the engine or a reference, never both). Every reading then goes
through the runner's own ``verdicts``: one JSON line a seed and variant with
the readings and the verdict of each limit, and a line of text that says
``ok`` or ``FAILED``. The sound reference has to pass every limit and each
wrong one has to fail one at least: the exit code is 1 where either does not
hold. ``--reverdict <file>`` (no chip) passes a kept file's readings through
the limits as they are now, after a limit moved. ``--keep <log>`` (no chip)
turns the ``notes:`` line of a benchmark run of the cell into the sound row
the calibration would have written for that seed, so that the seeds a limit's
lower side was set from stay in the tree.
``readings/ouro_calibration.jsonl`` is what the chip gave here and
``readings/ouro_cell_runs.jsonl`` what it gave the cell's own runs
(``tests/benchmark/test_ouro_cell.py`` holds the limits to both). Their rows
were read while the gate's bias was told over its own size
(``grad_gate_bias_over_itself``); ``grad_gate.bias`` is that reading restated
over the kernel's gradient a lane from ``readings/ouro_gate_raw.jsonl``, the
two leaves' raw values that the chip gave for each seed and reference (PR 58).
Nothing here is part of a benchmark run.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "train-ouro-1chip-loop4-seq16k"
PASSES = 4


def say(args, line: dict):
    text = json.dumps(line)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")


def verdicts_of(row: dict) -> dict:
    """The runner's limits AS THEY ARE NOW on one kept row of readings."""
    from benchmark.runners import train_steps_ouro as runner
    return runner.verdicts(row, PASSES)


def reverdict(path: str) -> None:
    """Rewrite ``path``'s ``verdicts`` and ``correct`` from its own readings
    after a limit moved: the readings are the chip's and stay as they are."""
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.startswith("{")]
    with open(path, "w") as f:
        for row in rows:
            row["verdicts"] = verdicts_of(row)
            row["correct"] = all(row["verdicts"].values())
            f.write(json.dumps(row) + "\n")


def kept_row(notes: dict, run: str) -> dict:
    """The runner's ``notes`` of one benchmark run of the cell as a sound row
    of readings (``readings`` as the runner computed them on the chip; the
    verdicts by the limits as they are now)."""
    from benchmark.runners import train_steps_ouro as runner
    stack = {n: e for n, e in notes["grad_rel_err"].items() if runner.GATE not in n}
    loss, want = notes["loss_first_two"], notes["loss_reference"]
    row = {"seed": notes["seed"], "against": "sound", "lr": runner.LR, "run": run,
           "logit_median": notes["logit_rel_err_median"],
           "logit_p90": notes["logit_rel_err_p90"],
           "logit_worst": notes["logit_rel_err_worst"],
           "grad_worst": list(max(stack.items(), key=lambda kv: kv[1])),
           "grad_gate": notes["grad_gate"],
           "update_worst": notes["update_rel_err_worst_leaf"],
           "update_with_rounding_worst": notes["update_rel_err_with_rounding_worst_leaf"],
           "loss_err": abs(loss[0] - want[0]) / abs(want[0]),
           "loss_after_err": abs(loss[1] - want[1]) / abs(want[1]),
           "descends": loss[1] < loss[0], **notes["loop_first_batch"],
           "loss": loss, "loss_reference": want}
    row["verdicts"] = verdicts_of(row)
    row["correct"] = all(row["verdicts"].values())
    return row


def keep(args) -> None:
    with open(args.keep) as f:
        notes = [json.loads(ln[7:]) for ln in f if ln.startswith("notes: ")]
    say(args, kept_row(notes[-1], os.path.basename(args.keep)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="2147480901")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default="",
                    help="the wrong references to read, by name (default: all)")
    ap.add_argument("--out", default="", help="append the JSON lines to this file too")
    ap.add_argument("--reverdict", default="",
                    help="no chip: rewrite this file's verdicts by the limits as they are")
    ap.add_argument("--keep", default="",
                    help="no chip: the sound row of this log of a run of the cell")
    args = ap.parse_args()
    if args.reverdict:
        return reverdict(args.reverdict)
    if args.keep:
        return keep(args)
    import jax
    from benchmark import traffic as gen
    from benchmark.run import load_json
    from benchmark.runners import train_steps_ouro as runner

    cell = load_json("workloads", CELL + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        cell["traffic"].update(cell.get("rehearse", {}))
    rows, seq = int(cell["traffic"]["global_batch"]), int(cell["traffic"]["seq_len"])
    slack = runner.REHEARSAL_SLACK if args.rehearse else 1.0
    own = runner.reference.OWN_PRECISION
    known = runner.reference.WRONG + (own, )
    wrong = args.only.split(",") if args.only else list(known)
    if set(wrong) - set(known) - {"none"}:
        ap.error(f"--only takes {', '.join(known)} or none")
    wrong = [w for w in wrong if w != "none"]
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        cfg, params, _ = runner.host_parameters(config, seed)
        ids = next(gen.token_batches(seed, rows, seq, cfg.vocab_size))
        at = runner.logit_positions(rows, seq)
        # nothing is timed here, so the program goes first and leaves the chip
        # to the references, one after another
        engine, cfg, _ = runner.build_engine(cell, config, params)
        got = runner.first_step(engine, jax.numpy.asarray(ids), at)
        del engine
        gc.collect()        # the engine's closures hold it in a cycle
        jax.clear_caches()
        print("after the engine: %.2f GB in use on the chip" % (
            (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0) / 1e9), flush=True)
        for name in ["sound"] + wrong:
            want = runner.reference_pass(params, ids, config, at,
                                         () if name == "sound" else {name})
            jax.clear_caches()
            r = runner.readings(got, want)
            kinds = {}
            for leaf, err in r.pop("grad_err").items():
                kind = leaf.split("']['")[-2 if leaf.endswith("['kernel']") or
                                          leaf.endswith("['weight']") else -1].strip("[]'")
                kinds[kind] = max(kinds.get(kind, 0.0), err)
            ok = runner.verdicts(r, cfg.total_ut_steps, slack)
            correct = all(ok.values())
            expected = correct == (name == "sound") or name == own
            as_expected &= expected
            say(args, {"seed": seed, "against": name, "lr": runner.LR, **r,
                       "loss": [got["loss"], got["loss_after"]],
                       "loss_reference": [want["ce"], want["ce_after"]],
                       "grad_err_worst_by_kind": kinds, "verdicts": ok, "correct": correct})
            failed = [k for k, good in ok.items() if not good]
            print(f"{seed} against {name}: correct {str(correct).lower()}"
                  + (f" (fails {', '.join(failed)})" if failed else "") + ": "
                  + ("ok" if expected else "FAILED: "
                     + ("the sound reference must pass" if name == "sound"
                        else "a wrong reference must fail a limit")), flush=True)
            del want
        del got, params
    sys.exit(0 if as_expected else 1)


if __name__ == "__main__":
    main()
