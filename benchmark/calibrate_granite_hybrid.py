"""The readings that the limits of ``runners/train_steps_granite_hybrid.py``
stand on, at the cell's own sizes on the chip:

    chiprun -- python3 benchmark/calibrate_granite_hybrid.py --seeds 2147480801,37

One timed first step of the cell's program (``first_step``) against the
float32 reference as it is, and against the reference made wrong in each way
``correct`` has to tell from it (``reference/granite_hybrid.py``'s ``wrong``):
the scan's state rounded to bf16 after every token, its decay ``exp(dt A)``
rounded to bf16, ``dt`` without ``softplus``, ``residual_multiplier`` left
out, a rotary embedding applied, the state dropped where a chunk ends. The
distance of the sound program from a wrong reference is what a program wrong
in that way would read against the sound reference. The program's step runs
first and its engine is dropped before the references run, one at a time (the
chip holds the engine or a reference, never both: the runner's docstring says
why). Every reading then goes through the runner's own ``verdicts`` (the
limits ``correct`` is decided by): one JSON line a seed and variant with the
readings and the verdict of each limit, and a line of text that says ``ok``
or ``FAILED``. The sound reference has to pass every limit and each wrong one
has to fail one at least: the exit code is 1 where either does not hold.
``readings/granite_hybrid_calibration.jsonl`` is what the chip gave
(``tests/benchmark/test_granite_hybrid_cell.py`` holds the limits to it);
nothing here is part of a benchmark run.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "train-granite4hm-1chip-longseq"
WRONG = ("bf16_state", "bf16_decay", "no_softplus", "no_residual_multiplier", "rope",
         "no_carry")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="2147480801")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default=",".join(WRONG),
                    help="the wrong references to read, by name")
    ap.add_argument("--out", default="", help="append the JSON lines to this file too")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmark import traffic as gen
    from benchmark.run import load_json
    from benchmark.runners import train_steps_granite_hybrid as runner

    cell = load_json("workloads", CELL + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        cell["traffic"].update(cell.get("rehearse", {}))
    rows, seq = int(cell["traffic"]["global_batch"]), int(cell["traffic"]["seq_len"])
    positions = runner.logit_positions(seq)
    slack = runner.REHEARSAL_SLACK if args.rehearse else 1.0
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        cfg, params, _ = runner.host_parameters(config, seed)
        init = runner.reference.initialisation_readings(params, config)
        ids = jnp.asarray(next(gen.token_batches(seed, rows, seq, cfg.vocab_size)))
        # nothing is timed here, so the program goes first and leaves the chip
        # to the references, one after another: the host holds one reference's
        # gradients at a time beside the program's (3 GB each)
        engine, cfg, _ = runner.build_engine(cell, config, params)
        got = runner.first_step(engine, ids, positions)
        del engine
        jax.clear_caches()
        for name in ["sound"] + [w for w in args.only.split(",") if w]:
            want = runner.reference_pass(params, ids, config, positions,
                                         () if name == "sound" else {name})
            jax.clear_caches()
            r = runner.readings(got, want)
            kinds = {}
            for leaf, err in r.pop("grad_err").items():
                kind = leaf.split("']['")[-2 if leaf.endswith("['kernel']") or
                                          leaf.endswith("['weight']") else -1].strip("[]'")
                kinds[kind] = max(kinds.get(kind, 0.0), err)
            ok = runner.verdicts(r, init, slack)
            correct = all(ok.values())
            as_expected &= correct == (name == "sound")
            line = json.dumps({"seed": seed, "against": name, **r,
                               "grad_err_worst_by_kind": kinds, "initialisation": init,
                               "verdicts": ok, "correct": correct})
            print(line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
            failed = [k for k, good in ok.items() if not good]
            print(f"{seed} against {name}: correct {str(correct).lower()}"
                  + (f" (fails {', '.join(failed)})" if failed else "") + ": "
                  + ("ok" if correct == (name == "sound") else "FAILED: "
                     + ("the sound reference must pass" if name == "sound"
                        else "a wrong reference must fail a limit")), flush=True)
            del want
        del got, params
    sys.exit(0 if as_expected else 1)


if __name__ == "__main__":
    main()
