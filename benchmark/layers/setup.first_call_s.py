"""Seconds the watched programs' compiling calls took, host call to return:
the summed ``ds.compile.call`` spans (what ``ds_compile_seconds`` records, with
the cost analysis that follows inside the call)."""

from benchmark import compile_anatomy


def read(run):
    calls = compile_anatomy.spans(compile_anatomy.CALL)
    return sum(s["dur_s"] for s in calls) if calls else None
