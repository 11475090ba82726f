"""What share of the router's assignments went to the experts held here:
the program's own ``rows_held`` (``engine.moe_stats()``, summed over the
expert layers) of each traced step, as the runner sampled it, over ``tokens
* top_k * expert layers``, averaged, in percent. 12.5 when the router spreads
its load evenly over 64 experts of which 8 are held. It describes the traffic
the seeded router makes, not the program's speed: the manifest has to give
every metric a direction, so it says ``lower``, but the number is read beside
``train_tok_s`` and never judged."""


def read(run):
    rows = run.get("moe_rows_held_samples")
    if not rows:
        return None
    cfg = run["config"]
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assigned = run["tokens_per_step"] * cfg["num_experts_per_tok"] * layers
    return 100.0 * sum(rows) / len(rows) / assigned
