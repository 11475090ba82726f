"""Share of the compiling calls' time that none of their child spans covers
(``ds.compile.trace``, ``.lower``, ``.backend``, ``.cost_analysis``): the
calls' self time over their length, by the ring's own parent links. How
complete the split of a first call is."""

from benchmark import compile_anatomy


def read(run):
    calls = compile_anatomy.spans(compile_anatomy.CALL)
    total = sum(s["dur_s"] for s in calls)
    if not total:
        return None
    return 100.0 * sum(max(0.0, s["self_s"]) for s in calls) / total
