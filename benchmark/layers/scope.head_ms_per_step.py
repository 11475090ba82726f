"""Device time of the embedding and the head per traced step and chip, every
phase, in milliseconds: ``embed_tokens``, ``lm_head`` and ``ds.head.loss``
(the chunked cross-entropy, where the head's three matmuls run)."""

from benchmark import scope_time


def read(run):
    return scope_time.part_ms(run, "head")
