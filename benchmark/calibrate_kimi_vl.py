"""The readings that the limits of ``runners/train_steps_kimi_vl.py`` stand
on, at the cell's own sizes on the chip:

    chiprun -- python3 benchmark/calibrate_kimi_vl.py --seeds 2147480901,41

One timed first step of the cell's program (``first_step``) against the
float32 reference as it is, and against the reference made wrong in each way
``correct`` has to tell from it (``reference/kimi_vl.py``'s ``wrong``):
``kv_a_layernorm`` dropped, the rotary embedding on the wrong slice, scores
over ``sqrt(128)``, no shared expert, a gated one, the routed sum without its
2.446, the top-6 of the unbiased scores, every matmul's operands rounded to
fp8's three mantissa bits (the nearest precision below the bf16 the
configuration states). ``bf16``, the operands rounded to the configuration's
OWN precision, is read too and required of nothing. ``--routing`` reads what
the gradients' distance is MADE OF and no wrong reference (unless ``--only``
names some): the model's own ``jax.grad`` as one program whose routing is
known token by token (``gradients_alone``), the share of assignments on
which its router and the reference's differ (``routers_differ``; ``moved`` is
the net of the per-expert counts, in which opposite flips cancel), and its
gradients leaf by leaf against the reference as it is and against the
reference ROUTED ALIKE (each token sent to the experts that program chose,
weighted by the reference's own scores): line ``routed_as_program``, PERF.md
section 6. The distance of the sound program from a wrong reference is what a
program wrong in that way would read against the sound reference. The program's step runs first and its engine is
dropped before the references run, one at a time (the chip holds the engine
or a reference, never both). Every reading then goes through the runner's own
``verdicts``: one JSON line a seed and variant with the readings and the
verdict of each limit, and a line of text that says ``ok`` or ``FAILED``. The
sound reference has to pass every limit and each wrong one has to fail one at
least: the exit code is 1 where either does not hold.
``readings/kimi_vl_calibration.jsonl`` is what the chip gave
(``tests/benchmark/test_kimi_vl_cell.py`` holds the limits to it); nothing
here is part of a benchmark run.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "train-kimivl-1chip-seq8k"
AS_PROGRAM = "routed_as_program"


def gradients_alone(cell, config, params, ids) -> dict:
    """The model's own loss and ``jax.grad`` of it on the whole batch, as ONE
    program beside the engine's: the engine's model, dtype, kernels and
    recomputation, every router's logits handed out beside the loss and put
    through ``LlamaMoEBlock._route``'s own selection (the top-k of
    ``sigmoid(z) + bias``). So this program's routing is known token by token
    (``picks`` ``[rows, expert layers, seq, k]``), which the fused step's is
    not: that one returns counts. ``grads`` float32 on the host."""
    import jax
    import numpy as np
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from benchmark.runners.train_steps_kimi_vl import model_config
    cfg = model_config(config)
    reset_mesh_context()        # as ``build_engine``: the kernels run on one device
    set_mesh_context(MeshContext.create(devices=jax.devices()[:cell["chips"]]))
    model = LlamaForCausalLM(cfg)
    layers = [i for i, spec in enumerate(cfg.layer_specs) if spec.ffn == "moe"]

    def loss_and_logits(p):
        loss, sown = model.apply({"params": p}, ids, labels=ids, mutable=["intermediates"],
                                 capture_intermediates=lambda m, _: m.name == "gate")
        return loss, [sown["intermediates"]["model"][f"layers_{i}"]["block_sparse_moe"][
            "gate"]["__call__"][0] for i in layers]

    @jax.jit
    def both(p):
        (loss, logits), grads = jax.value_and_grad(loss_and_logits, has_aux=True)(p)
        picks = [jax.lax.top_k(jax.nn.sigmoid(z) + p["model"][f"layers_{i}"][
            "block_sparse_moe"]["expert_bias"], cfg.num_experts_per_tok)[1]
                 for i, z in zip(layers, logits)]
        return loss, grads, jax.numpy.stack(picks, axis=1)

    loss, grads, picks = both(jax.device_put(params, jax.devices()[0]))
    out = {"loss": float(loss), "picks": np.asarray(picks),
           "grads": jax.tree_util.tree_map(lambda g: np.asarray(g, np.float32), grads)}
    del grads, picks
    jax.clear_caches()
    return out


def gaps(mine, theirs) -> dict:
    """Relative L2 distance leaf by leaf of two gradient trees (the leaves
    neither side gives a gradient left out)."""
    import jax
    import numpy as np
    out = {}
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree_util.tree_leaves(theirs)):
        if np.any(w):
            d = (g - w).ravel()
            out[jax.tree_util.keystr(path)] = float(np.sqrt(np.vdot(d, d) / np.vdot(w, w)))
    return out


def routers_differ(mine, theirs, experts: int, held: int) -> dict:
    """Two routings ``[rows, expert layers, seq, k]`` token by token: the
    share of assignments one has and the other has not (``flipped``; by
    expert layer; among the rows of the experts held), of tokens with one such
    at least, and the NET per-expert difference that ``moved`` counts, in
    which a token lost to an expert and another won by it cancel."""
    import numpy as np

    def has(picks):
        out = np.zeros(picks.shape[:-1] + (experts, ), bool)
        np.put_along_axis(out, picks.astype(np.int64), True, axis=-1)
        return out

    a, b = has(mine), has(theirs)
    lost = a & ~b
    net = np.abs(a.sum(axis=(0, 2), dtype=np.int64)
                 - b.sum(axis=(0, 2), dtype=np.int64)).sum() // 2
    return {"flipped_share": float(lost.sum() / mine.size),
            "flipped_share_by_layer": (lost.sum(axis=(0, 2, 3))
                                       / (mine.size / mine.shape[1])).tolist(),
            "flipped_share_of_rows_held": float(
                (lost[..., :held].sum() + (b & ~a)[..., :held].sum()) / 2
                / max(b[..., :held].sum(), 1)),
            "tokens_with_a_flip_share": float(lost.any(-1).mean()),
            "net_moved_share": float(net / mine.size)}


def routing_line(seed, alone, got, want, alike, cfg) -> dict:
    """The ``routed_as_program`` line: how far the two routers differ, and
    every leaf's distance: the engine's ``step`` and the program ``alone``
    against the ``reference`` as it is, ``alone`` against the reference
    ``routed_alike``, and the two bf16 programs against each other."""
    import numpy as np
    differ = routers_differ(alone["picks"], want["chosen"], cfg.num_local_experts,
                            cfg.experts_held_)
    step = np.asarray(got["stats"]["expert_counts"], np.int64)
    differ["alone_against_step_net_moved"] = int(np.abs(
        np.bincount(alone["picks"].ravel(), minlength=step.size) - step).sum() // 2)
    columns = (gaps(got["grads"], want["grads"]), gaps(alone["grads"], want["grads"]),
               gaps(alone["grads"], alike["grads"]), gaps(got["grads"], alone["grads"]))
    return {"seed": seed, "against": AS_PROGRAM, "routers": differ,
            "loss": [alone["loss"], want["ce"], alike["ce"]],
            "gap_by_leaf_columns": ["step|reference", "alone|reference",
                                    "alone|reference_routed_alike", "step|alone"],
            "gap_by_leaf": {leaf: [c.get(leaf) for c in columns] for leaf in columns[0]}}


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
    ap.add_argument("--routing", action="store_true",
                    help="read what the gradients' distance is made of (the docstring)")
    ap.add_argument("--out", default="", help="append the JSON lines to this file too")
    args = ap.parse_args()
    import jax
    from benchmark import traffic as gen
    from benchmark.run import load_json
    from benchmark.runners import train_steps_kimi_vl as runner

    cell = load_json("workloads", CELL + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        cell["traffic"].update(cell.get("rehearse", {}))
    rows, seq = int(cell["traffic"]["global_batch"]), int(cell["traffic"]["seq_len"])
    slack = runner.REHEARSAL_SLACK if args.rehearse else 1.0
    own = runner.reference.OWN_PRECISION
    known = runner.reference.WRONG + (own, )
    wrong = args.only.split(",") if args.only else [] if args.routing else list(known)
    if set(wrong) - set(known):
        ap.error(f"--only takes {', '.join(known)}")
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        cfg, params, _ = runner.host_parameters(config, seed)
        ids = next(gen.token_batches(seed, rows, seq, cfg.vocab_size))
        at = runner.logit_positions(rows, seq)
        # nothing is timed here, so the program goes first and leaves the chip
        # to the references, one after another
        alone = (gradients_alone(cell, config, params, jax.numpy.asarray(ids))
                 if args.routing else None)
        engine, cfg, _ = runner.build_engine(cell, config, params)
        got = runner.first_step(engine, jax.numpy.asarray(ids), at)
        del engine
        gc.collect()        # the engine's closures hold it in a cycle
        jax.clear_caches()
        print("after the engine: %.2f GB in use on the chip" % (
            (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0) / 1e9), flush=True)
        moe_layers = sum(spec.ffn == "moe" for spec in cfg.layer_specs)
        assigned = rows * seq * cfg.num_experts_per_tok * moe_layers
        for name in ["sound"] + wrong:
            want = runner.reference_pass(params, ids, config, at,
                                         () if name == "sound" else {name})
            jax.clear_caches()
            r = runner.readings(got, want)
            if name == "sound" and alone is not None:
                say(args, routing_line(seed, alone, got, want, runner.reference_pass(
                    params, ids, config, at, choice=alone["picks"]), cfg))
                jax.clear_caches()
            kinds = {}
            for leaf, err in r.pop("grad_err").items():
                kind = leaf.split("']['")[-2 if leaf.endswith("['kernel']") or
                                          leaf.endswith("['weight']") else -1].strip("[]'")
                kinds[kind] = max(kinds.get(kind, 0.0), err)
            r.pop("counts")
            ok = runner.verdicts({**r, "counts": [got["stats"]["expert_counts"]]},
                                 assigned, cfg.num_local_experts, cfg.experts_held_, slack)
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
