"""Chosen over causal (query, key) pairs of the sparse-attention layers, in
percent: the program's own ``engine.dsa_stats()["chosen_share"]`` of each
traced step, as the runner sampled it, averaged. 12.11 at 1 x 32,768 tokens
and ``topk`` 2,048 (``sum_t min(t + 1, 2048)`` over ``seq * (seq + 1) / 2``).
It describes the traffic, not the program's speed: the manifest has to give
every metric a direction, so it says ``lower``, but the number is read
beside ``train_tok_s`` and never judged."""


def read(run):
    shares = run.get("dsa_chosen_share_samples")
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
