"""From a profiler trace (xplane) to busy intervals, per-name device time,
idle gaps attributed to what the host was doing, and exposed collective time.

``load_xplane`` turns the file into plain Python: ``{plane name: {line name:
[(event name, start_ns, duration_ns)]}}``; everything else works on that, so
the arithmetic is tested on a hand-built trace. Shared code that no later
non-benchmark PR edits.
"""

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
PARENTS = ("%while", "%conditional", "%call")   # ops that only hold other ops
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def load_xplane(path: str) -> Dict[str, Dict[str, list]]:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def short(name: str) -> str:
    """A device event is named by its whole HLO instruction; the part before
    `` = `` is the instruction's name."""
    return name.split(" = ", 1)[0][:80]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def measure(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that no interval of ``b`` covers."""
    out, cover = [], union(b)
    for lo, hi in union(a):
        for c, d in cover:
            if d <= lo or c >= hi:
                continue
            if c > lo:
                out.append((lo, c))
            lo = max(lo, d)
            if lo >= hi:
                break
        if lo < hi:
            out.append((lo, hi))
    return out


class Cover:
    """A union of intervals that answers "how much of [lo, hi] is covered"
    in logarithmic time."""

    def __init__(self, intervals: Sequence[Interval]):
        self.ivs = union(intervals)
        self.starts = [a for a, _ in self.ivs]
        self.prefix = [0.0]
        for a, b in self.ivs:
            self.prefix.append(self.prefix[-1] + (b - a))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        a, b = self.ivs[i - 1]
        return self.prefix[i - 1] + min(t, b) - a

    def within(self, lo: float, hi: float) -> float:
        return self._upto(hi) - self._upto(lo)


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def device_planes(trace: dict, chips: int) -> List[str]:
    names = sorted(n for n, lines in trace.items()
                   if n.startswith("/device:TPU:") and OPS_LINE in lines)
    return names[:chips]


def _rehearsal_view(trace: dict) -> dict:
    """Tests only: a CPU trace has no device plane, so the XLA CPU client's
    host threads stand in for one, to drive the same arithmetic."""
    ops = [e for ln, evs in trace.get("/host:CPU", {}).items()
           if ln.startswith("tf_XLA") for e in evs]
    view = {n: l for n, l in trace.items() if n != "/host:CPU"}
    view["/device:TPU:0"] = {OPS_LINE: ops}
    return view


def reduce(trace: dict, chips: int) -> dict:
    """All times in seconds. ``window_s`` is the span from the first to the
    last device event over the chips used; ``busy_s`` the union of "XLA Ops"
    intervals, averaged over chips; ``exposed_collective_s`` the worst
    chip's collective time during which no other op ran on it."""
    planes = device_planes(trace, chips)
    if not planes:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in the "
                         f"trace (planes: {sorted(trace)})")
    ev = {p: [(n, s, s + d) for n, s, d in trace[p][OPS_LINE]] for p in planes}
    t_lo = min(s for p in planes for _, s, _ in ev[p])
    t_hi = max(e for p in planes for _, _, e in ev[p])
    busy, exposed, colls, by_name, modules, kernels = [], [], [], {}, {}, {}
    for p in planes:
        ivs = [(s, e) for _, s, e in ev[p]]
        busy.append(measure(ivs))
        # collectives run on either line; what hides them is any other op
        # that is not merely the holder of other ops
        coll = [(s, s + d) for ln in (OPS_LINE, ASYNC_LINE)
                for n, s, d in trace[p].get(ln, []) if is_collective(short(n))]
        comp = [(s, e) for n, s, e in ev[p]
                if not is_collective(short(n)) and not n.startswith(PARENTS)]
        exposed.append(measure(subtract(coll, comp)))
        colls.append(measure(coll))
        loops = Cover([(s, e) for n, s, e in ev[p] if n.startswith("%while")])
        for n, s, e in ev[p]:
            if n.startswith(PARENTS):
                continue
            by_name[short(n)] = by_name.get(short(n), 0.0) + (e - s) / len(planes)
            if "custom-call" in n:
                k = kernels.setdefault(short(n), {"count": 0, "seconds": 0.0,
                                                  "hlo": n[:160]})
                k["count"] += 1
                k["seconds"] += (e - s) * 1e-9
        cover = Cover(ivs)
        for n, s, d in trace[p].get(MODULES_LINE, []):
            n = re.sub(r"\(\d+\)$", "", n)   # the program's run id
            if loops.within(s, s + d) > 0:     # the program holds a scan
                n += "[while]"
            m = modules.setdefault(n, {"count": 0, "span_s": 0.0, "busy_s": 0.0})
            m["count"] += 1
            m["span_s"] += d * 1e-9 / len(planes)        # seconds a chip
            m["busy_s"] += cover.within(s, s + d) * 1e-9 / len(planes)
    # idle gaps on the first chip, named by the host event that covers most
    # of each gap
    first = union([(s, e) for _, s, e in ev[planes[0]]])
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(first, first[1:])),
                  reverse=True)[:10]
    host = [(short(n), s, s + d) for pn, lines in trace.items()
            if pn.startswith("/host:") for ln, evs in lines.items()
            for n, s, d in evs if d < 0.5 * (t_hi - t_lo)]
    idle_gaps = []
    for length, a, b in gaps:
        best = max(host, key=lambda h: overlap((h[1], h[2]), (a, b)),
                   default=None)
        name = best[0] if best and overlap((best[1], best[2]), (a, b)) > 0 \
            else "unattributed"
        idle_gaps.append([name, length * 1e-9])
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (t_hi - t_lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "busy_s_per_chip": [b * 1e-9 for b in busy],
        "exposed_collective_s": max(exposed) * 1e-9,
        "collective_s": max(colls) * 1e-9,
        "op_seconds": {n: t * 1e-9 for n, t in by_name.items()},
        "modules": modules,
        "kernels": kernels,
        "breakdown": {"device_ops": [[n, t * 1e-9] for n, t in top],
                      "idle_gaps": idle_gaps},
    }


def reduce_dir(out_dir: str, chips: int, log=print,
               rehearse: bool = False) -> dict:
    paths = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane file under {out_dir}, "
                                f"found {paths}")
    trace = load_xplane(paths[0])
    for name, lines in trace.items():
        log(f"trace plane {name}: " + ", ".join(
            f"{ln} ({len(ev)})" for ln, ev in lines.items() if ev)[:600])
    if rehearse and not device_planes(trace, chips):
        trace, chips = _rehearsal_view(trace), 1
    red = reduce(trace, chips)
    log("trace modules: " + "; ".join(
        f"{n} x{m['count']} busy {m['busy_s']:.3f} s"
        for n, m in sorted(red["modules"].items(),
                           key=lambda kv: -kv[1]["busy_s"])[:12]))
    log("trace top ops: " + "; ".join(
        f"{n} {t:.4f} s" for n, t in sorted(
            red["op_seconds"].items(), key=lambda kv: -kv[1])[:30]))
    return red
