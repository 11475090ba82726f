"""Seconds ``import deepspeed_tpu`` took with whatever was imported before it
(jax, flax): the ``ds.import`` span, which the package records from its first
line to its last."""

from benchmark import compile_anatomy


def read(run):
    spans = compile_anatomy.spans("ds.import")
    return sum(s["dur_s"] for s in spans) if spans else None
