"""The readings that the limits of ``runners/train_steps_phi4_flash.py`` stand
on, at the cell's own sizes on the chip:

    chiprun -- python3 benchmark/calibrate_phi4_flash.py --seeds 2147480901,41

One timed first step of the cell's program (``first_step``) against the
float32 reference as it is, and against the reference made wrong in each way
``correct`` has to tell from it (``reference/phi4_flash.py``'s ``wrong``): one
decay a channel (the states' mean: Mamba-2's form), the step size without its
softplus, the ``D`` term dropped, the state carried in bf16, the memory taken
after the ``z`` gate, the GMU reading the first Mamba layer's scan output, the
cross layer attending keys and values projected from its own input, no window
in the windowed layer, the window also in the full layer, no subtraction
(``lambda = 0``), ``1 - lambda_init`` dropped, ``subln`` left out, every
matmul's operands rounded to fp8's three mantissa bits (the nearest precision
below the bf16 the configuration states). ``bf16``, the operands rounded to
the configuration's OWN precision, is read too and required of nothing. The
distance of the sound program from a wrong reference is what a program wrong
in that way would read against the sound reference. The program's step runs
first and its engine is dropped before the references run, one at a time (the
chip holds the engine or a reference, never both). Every reading then goes
through the runner's own ``verdicts``: one JSON line a seed and variant with
the readings and the verdict of each limit, and a line of text that says
``ok`` or ``FAILED``. The sound reference has to pass every limit and each
wrong one has to fail one at least: the exit code is 1 where either does not
hold. ``--only-after`` reads the wrong references on the FIRST seed alone (the
later seeds: sound and bf16), which is how the committed readings were taken.
``readings/phi4_flash_calibration.jsonl`` is what the chip gave
(``tests/benchmark/test_phi4_flash_cell.py`` holds the limits to it); nothing
here is part of a benchmark run.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "train-phi4flash-1chip-sambay-seq16k"


def say(args, line: dict):
    text = json.dumps(line)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="2147480901")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default="",
                    help="the wrong references to read, by name (default: all)")
    ap.add_argument("--only-after", default="",
                    help="the wrong references to read on the seeds after the first")
    ap.add_argument("--out", default="", help="append the JSON lines to this file too")
    args = ap.parse_args()
    import jax
    from benchmark import traffic as gen
    from benchmark.run import load_json
    from benchmark.runners import train_steps_phi4_flash as runner

    cell = load_json("workloads", CELL + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        cell["traffic"].update(cell.get("rehearse", {}))
    rows, seq = int(cell["traffic"]["global_batch"]), int(cell["traffic"]["seq_len"])
    slack = runner.REHEARSAL_SLACK if args.rehearse else 1.0
    own = runner.reference.OWN_PRECISION
    known = runner.reference.WRONG + (own, )

    def chosen(text):
        names = text.split(",") if text else list(known)
        if set(names) - set(known) - {"none"}:
            ap.error(f"--only and --only-after take {', '.join(known)} or none")
        return [n for n in names if n != "none"]

    wrong, wrong_after = chosen(args.only), chosen(args.only_after or args.only)
    as_expected = True
    for nth, seed in enumerate(int(s) for s in args.seeds.split(",")):
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
        for name in ["sound"] + (wrong_after if nth else wrong):
            want = runner.reference_pass(params, ids, config, at,
                                         () if name == "sound" else {name})
            jax.clear_caches()
            r = runner.readings(got, want)
            r.pop("grad_err")
            ok = runner.verdicts(r, slack)
            correct = all(ok.values())
            expected = correct == (name == "sound") or name == own
            as_expected &= expected
            say(args, {"seed": seed, "against": name, "lr": runner.LR, **r,
                       "loss": [got["loss"], got["loss_after"]],
                       "loss_reference": [want["ce"], want["ce_after"]],
                       "verdicts": ok, "correct": correct})
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
