"""The grouped matmuls of an expert layer that holds a share of the experts,
against the work its rows need: nine grouped matmuls an expert layer and
step (gate, up, down; the rows' and the weights' gradient of each) of ``2 *
rows held * hidden * expert width`` FLOP, the rows held being the program's
own count for each traced step (``rows_held`` of ``engine.moe_stats()``,
summed over the expert layers, as the runner sampled it), over the published
bf16 peak, over the time the device trace gives the ``%ragged-dot*`` /
``%moe_gmm*`` calls ``benchmark/moe_cost.py`` matches. The rows array's
static length is not read: padding, a recomputed forward and the pass over
all assignments after a fallback add time and no work, so they lower the
share. Compute-bound."""

from benchmark import device, moe_cost


def read(run):
    gmm = moe_cost.traced_gmm(run)
    rows = run.get("moe_rows_held_samples")
    if gmm is None or not rows:
        return None
    cfg = run["config"]
    least = 9 * sum(moe_cost.gmm_flops(r, cfg["hidden_size"],
                                       moe_cost.expert_width(cfg)) for r in rows)
    peak = device.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * least / peak / gmm["seconds"]
