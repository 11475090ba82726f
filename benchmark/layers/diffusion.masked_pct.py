"""Masked tokens over data tokens of the traced steps, in percent: the
program's own count (``masked_tokens`` of ``engine.diffusion_stats()``, what
``ds_diffusion_masked_tokens_total`` sums), as the runner sampled it after
each traced step. It describes the traffic the seed made (the mean noise
level its steps drew: the loss, and the head's live rows, follow it) and is
read beside ``train_tok_s``, never judged. A program that counts no masked
tokens reports nothing."""


def read(run):
    samples = run.get("diffusion_masked_samples")
    if not samples or not run.get("tokens_per_step"):
        return None
    return 100.0 * sum(samples) / (len(samples) * run["tokens_per_step"])
