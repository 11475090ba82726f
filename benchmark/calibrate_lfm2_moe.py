"""The readings that the limits of ``runners/train_steps_lfm2_moe.py`` stand
on, at the cell's own sizes on the chip:

    chiprun -- python3 benchmark/calibrate_lfm2_moe.py --seeds 2147480701,31

One timed first step of the cell's program (``first_step``) against the
float32 reference as it is, and against the reference made wrong in each way
``correct`` has to tell from it: the convolutions' projections and the held
experts' matrices rounded to fp8 e4m3 (per-tensor scaling to the format's
largest value, as fp8 training scales them), the held experts alone, a router
that weights by the biased score, a router that leaves out the
renormalisation. The distance of the sound program from a wrong reference is
what a program wrong in that way would read against the sound reference. One
JSON line a seed and variant; nothing here is part of a benchmark run.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "train-lfm2moe-1chip-seq8k"


def fp8(tree, names):
    """The tree with every leaf whose path holds one of ``names`` rounded to
    float8 e4m3 and back, scaled so that its largest value is the format's."""
    import jax
    import jax.numpy as jnp

    def rounded(path, leaf):
        if not any(name in jax.tree_util.keystr(path) for name in names):
            return leaf
        scale = 448.0 / jnp.max(jnp.abs(leaf))
        return (leaf * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return jax.tree_util.tree_map_with_path(rounded, tree)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="2147480701")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmark import traffic as gen
    from benchmark.reference import lfm2_moe as reference
    from benchmark.run import load_json
    from benchmark.runners import train_steps_lfm2_moe as runner

    cell = load_json("workloads", CELL + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        cell["traffic"].update(cell.get("rehearse", {}))
    rows, seq = int(cell["traffic"]["global_batch"]), int(cell["traffic"]["seq_len"])
    last = min(runner.LOGIT_POSITIONS, seq)
    experts, convs = ("['w1']", "['w3']", "['w2']"), ("in_proj", "out_proj")
    for seed in (int(s) for s in args.seeds.split(",")):
        engine, cfg, _, _ = runner.build_engine(cell, config, seed)
        ids = jnp.asarray(next(gen.token_batches(seed, rows, seq, cfg.vocab_size)))
        params = engine.params
        variants = {
            "sound": reference.step_parts(params, ids, config, last),
            "biased_weights": reference.step_parts(params, ids, config, last,
                                                   weigh_biased=True),
            "no_renormalisation": reference.step_parts(
                params, ids, {**config, "norm_topk_prob": False}, last),
            "fp8_convs_and_experts": reference.step_parts(
                fp8(params, experts + convs), ids, config, last),
            "fp8_experts": reference.step_parts(fp8(params, experts), ids, config, last)}
        del params
        got = runner.first_step(engine, ids, last)
        for name, want in variants.items():
            wrong = variants["sound" if name == "biased_weights" else "biased_weights"]
            r = runner.readings(got, want, wrong["logits"])
            kinds = {}
            for leaf, err in r.pop("grad_err").items():
                kind = leaf.split("']['")[-2 if leaf.endswith("['kernel']") or
                                          leaf.endswith("['weight']") else -1].strip("[]'")
                kinds[kind] = max(kinds.get(kind, 0.0), err)
            r["counts"] = None
            print(json.dumps({"seed": seed, "against": name, **r,
                              "grad_err_worst_by_kind": kinds}), flush=True)
        del engine, got, variants
        jax.clear_caches()


if __name__ == "__main__":
    main()
