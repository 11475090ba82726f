"""Device time of the head in the looped cell, per traced step and chip, every
phase, in milliseconds: ``scope_time``'s part ``head`` (the embedding, and
under ``ds.head.loss`` the chunked loss, which takes the four passes' streams
through the 49,152-row head in one sweep: forward, ``dx`` and ``dw`` a chunk).
A program without the step's scopes reports nothing."""

from benchmark import scope_time


def read(run):
    return scope_time.part_ms(run, "head")
