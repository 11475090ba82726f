"""Device time of a traced training step by the program's own scopes.

Every op of a compiled step carries the name stack it was traced under
(``op_name``): flax's module names (``layers_3/self_attn/q_proj``), JAX's
transformation marks (``jvp(...)``, ``transpose(...)``,
``rematted_computation``) and the ``ds.*`` scopes the program gives what no
module names (``docs/observability.md``, "Device scopes"). This file reads
the run's one xplane file, gives every event of the chips' "XLA Ops" line
that path, a *phase* and a *part* from it, and sums: the table the nine
``scope.*`` readers under ``layers/`` read, cached on ``run``.

Where the path comes from, in this order: the event's ``tf_op`` stat (XProf's
name stack; a TPU plane keeps it on the event's *metadata*, which
``jax.profiler.ProfileData`` does not hand out, so the file is read as
protobuf wire format here: the few messages needed, by field number); an
``op_name="..."`` inside the event's own name; else the event's
``program_id`` and instruction name joined with the ``op_name`` of that
instruction in the module's "Hlo Proto", which the profiler stores in the
same file's ``/host:metadata`` plane (what a CPU trace needs; a program loaded
from the compile cache brings no proto). Nothing is
asked of the program and nothing compiles.

Time on the "XLA Ops" line is exclusive but for the ops that only hold others
(``reduce_trace.PARENTS``), which are left out, so phases and parts each
partition the chips' busy time. An op XLA made itself has no name stack: its
grouped-matmul kernel (``op_name`` "ragged-dot-none") is the MoE block's by its
instruction name, as ``benchmark/moe_cost.py`` matches it, with its pass
unknown (phase ``other``); a layout copy stays ``unnamed``. A program whose paths hold no ``ds.step.*``
scope (the commit before they existed, or a step loaded from a compile-cache
entry that commit wrote: the cache's key ignores metadata) has no update and
no split worth the name: ``table`` says so and every reader reports nothing.

By hand, on any traced run's file:

    python -m benchmark.scope_time <file.xplane.pb> --chips 1 --steps 4
"""

import argparse
import re
import struct
import sys
import time
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import host_spans, moe_cost, reduce_trace

PHASES = ("fwd", "bwd", "recompute", "update", "prep", "other")
PARTS = ("head", "mixer", "ffn", "moe", "norm", "layer", "update", "prep",
         "unnamed")
STEP = "ds.step."
# the module names the part of an op is read from: renaming one in
# models/llama.py moves a metric (PERF.md section 3 lists them as a contract)
MODULE_PART = {"embed_tokens": "head", "lm_head": "head", "ds.head.loss": "head",
               "self_attn": "mixer", "conv": "mixer", "mamba": "mixer",
               "ds.rope": "mixer",
               "mlp": "ffn", "shared_expert": "ffn",
               "block_sparse_moe": "moe", "ds.moe.route": "moe",
               "ds.moe.dispatch": "moe", "ds.moe.combine": "moe"}
STEP_PART = {"ds.step.grad_norm": "update", "ds.step.optimizer": "update",
             "ds.step.cast": "prep", "ds.step.gather": "prep"}
_LAYER = re.compile(r"^layers?(_\d+)?$")
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# --- paths -------------------------------------------------------------

def segments(path: str) -> List[str]:
    """The scopes of a name stack, outermost first, each out of the
    transformation marks JAX wrapped it in: ``transpose(jvp(M))`` is ``M``."""
    out = []
    for seg in path.split("/"):
        while (m := _WRAPPED.match(seg)):
            seg = m.group(1)
        out.append(seg)
    return out


def phase_of(path: str) -> str:
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "bwd"
    if "jvp(" in path:
        return "fwd"
    for seg in segments(path):
        if seg in STEP_PART:
            return STEP_PART[seg]
    return "other"


def part_of(path: str) -> str:
    """From the first module segment under the layer, or at the top level."""
    segs = segments(path)
    for seg in segs:
        if seg in MODULE_PART:
            return MODULE_PART[seg]
        if seg == "norm" or (seg.endswith(("_norm", "layernorm"))
                             and not seg.startswith("ds.")):
            return "norm"
    if any(_LAYER.match(seg) for seg in segs):
        return "layer"      # an op of the layer itself: the residual adds
    for seg in segs:
        if seg in STEP_PART:
            return STEP_PART[seg]
    return "unnamed"


def innermost_ds(path: str) -> Optional[str]:
    """The last ``ds.*`` scope of a path, ``ds.step.loss`` (which holds the
    whole model) left out."""
    mine = [s for s in segments(path)
            if s.startswith("ds.") and s != STEP + "loss"]
    return mine[-1] if mine else None


# --- the xplane file, as wire format -------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        val |= (b & 0x7f) << shift
        if b < 0x80:
            return val, pos
        shift += 7


def _fields(buf, pos: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one protobuf message: a varint
    as an int, a length-delimited field as ``(start, end)`` into ``buf``, a
    fixed-width one as its bytes."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
            yield num, wt, val
        elif wt == 2:
            n, pos = _varint(buf, pos)
            yield num, wt, (pos, pos + n)
            pos += n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            yield num, wt, bytes(buf[pos:pos + n])
            pos += n
        else:
            raise ValueError(f"wire type {wt} at byte {pos}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """An ``XStat`` as ``(name, value)``; a ``ref_value`` is the name of the
    stat metadata it points to."""
    name, value = "", None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            value = v
        elif num == 5:
            value = _text(buf, v)
        elif num == 6:
            value = v               # bytes, as a span
        elif num == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf, span, ops_line) -> dict:
    """One ``XPlane``: its event metadata ``{id: {"name", stats...}}`` and the
    events ``(metadata id, duration ps, {stat: value})`` of the lines
    ``ops_line(line name)`` accepts, by their start."""
    lines, emd_spans, stat_names = [], [], {}
    for num, _, v in _fields(buf, *span):
        if num == 3:
            lines.append(v)
        elif num == 4:
            emd_spans.append(v)
        elif num == 5:
            key, val = _map_entry(buf, v)
            for n2, _, v2 in _fields(buf, *val):
                if n2 == 2:
                    stat_names[key] = _text(buf, v2)
    meta = {}
    for sp in emd_spans:
        key, val = _map_entry(buf, sp)
        md = {"name": "", "display": ""}
        for n2, _, v2 in _fields(buf, *val):
            if n2 == 2:
                md["name"] = _text(buf, v2)
            elif n2 == 4:
                md["display"] = _text(buf, v2)
            elif n2 == 5:
                k, x = _stat(buf, v2, stat_names)
                md[k] = x
        meta[key] = md
    events = []
    for sp in lines:
        line_name, ev_spans = "", []
        for num, _, v in _fields(buf, *sp):
            if num == 2:
                line_name = _text(buf, v)
            elif num == 4:
                ev_spans.append(v)
        if not ops_line(line_name):
            continue
        for esp in ev_spans:
            mid = dur = off = 0
            stats = {}
            for num, _, v in _fields(buf, *esp):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
                elif num == 4:
                    k, x = _stat(buf, v, stat_names)
                    if k in ("hlo_op", "hlo_module", "program_id", "tf_op"):
                        stats[k] = x
            events.append((off, mid, dur, stats))
    events.sort(key=lambda e: e[0])     # one line a chip, or one clock: by start
    return {"meta": meta, "events": [e[1:] for e in events]}


def _hlo_op_names(buf, span) -> Dict[str, str]:
    """``{instruction name: op_name}`` of an ``HloProto``: hlo_module (1) >
    computations (3) > instructions (2) > name (1), metadata (7) > op_name
    (2)."""
    out = {}
    for n0, _, module in _fields(buf, *span):
        if n0 != 1:
            continue
        for n1, _, comp in _fields(buf, *module):
            if n1 != 3:
                continue
            for n2, _, ins in _fields(buf, *comp):
                if n2 != 2:
                    continue
                name = op_name = ""
                for n3, _, v in _fields(buf, *ins):
                    if n3 == 1:
                        name = _text(buf, v)
                    elif n3 == 7:
                        for n4, _, v4 in _fields(buf, *v):
                            if n4 == 2:
                                op_name = _text(buf, v4)
                if op_name:
                    out[name] = op_name
    return out


def read_events(path: str, chips: int) -> Tuple[Dict[str, list], str]:
    """``({plane: [(instruction text, duration ns, path)]}, source)`` of the
    first ``chips`` TPU planes' "XLA Ops" lines, or, in a CPU rehearsal, of
    the XLA CPU client's host threads as one stand-in plane. ``source`` says
    where the paths came from: ``tf_op``, ``op_name`` or ``hlo_proto``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes, protos = {}, {}
    for num, _, sp in _fields(buf):
        if num != 1:
            continue
        name = next((_text(buf, v) for n, _, v in _fields(buf, *sp) if n == 2), "")
        if name.startswith("/device:TPU:") or name == "/host:CPU":
            planes[name] = sp
        elif name == "/host:metadata":
            for md in _plane(buf, sp, lambda ln: False)["meta"].values():
                if isinstance(md.get("Hlo Proto"), tuple):
                    protos[md["name"]] = md["Hlo Proto"]
    tpu = sorted(n for n in planes if n.startswith("/device:TPU:"))[:chips]
    if tpu:
        use = {n: _plane(buf, planes[n], lambda ln: ln == reduce_trace.OPS_LINE)
               for n in tpu}
    elif "/host:CPU" in planes:
        use = {"/device:TPU:0": _plane(buf, planes["/host:CPU"],
                                       lambda ln: ln.startswith("tf_XLA"))}
    else:
        use = {}
    op_names: Dict[str, Dict[str, str]] = {}    # program id -> instruction map

    def by_proto(program, op) -> str:
        key = str(program)
        if key not in op_names:
            span = next((s for n, s in protos.items()
                         if n.endswith(f"({key})")), None)
            op_names[key] = _hlo_op_names(buf, span) if span else {}
        return op_names[key].get(op, "")

    out, sources = {}, set()
    for pname, plane in use.items():
        rows = []
        for mid, dur_ps, stats in plane["events"]:
            md = plane["meta"].get(mid, {})
            text = md.get("name", "")
            tf_op = stats.get("tf_op") or md.get("tf_op")
            if tf_op:
                scope, src = tf_op.rsplit(":", 1)[0], "tf_op"
            elif (m := _OP_NAME.search(text)):
                scope, src = m.group(1), "op_name"
            else:
                program = stats.get("program_id", md.get("program_id"))
                op = stats.get("hlo_op") or md.get("display") \
                    or reduce_trace.short(text).lstrip("%")
                scope, src = by_proto(program, op), "hlo_proto"
            if "/" in scope:
                sources.add(src)
            rows.append((text, dur_ps * 1e-3, scope))
        out[pname] = rows
    return out, "+".join(sorted(sources)) or "none"


# --- the table -----------------------------------------------------------

def build_table(planes: Dict[str, list], steps: int) -> Optional[dict]:
    """Milliseconds a traced step and chip (the mean over the planes), by
    ``(phase, part)``; the same for custom calls alone (the named kernels);
    by innermost ``ds.*`` scope and phase; the twenty longest ops with their
    paths, and the ten longest that stay ``unnamed``. ``None`` when there is
    no event to read."""
    if not planes or not steps or not any(planes.values()):
        return None
    per = 1e-6 / steps / len(planes)            # ns summed -> ms a step, chip
    ms, custom, ds, ops, unnamed, kinds = {}, {}, {}, {}, {}, {}
    step_scopes = False
    for rows in planes.values():
        for text, dur, path in rows:
            if text.startswith(reduce_trace.PARENTS):
                continue
            if path not in kinds:       # a path recurs every step, on every chip
                kinds[path] = ((phase_of(path), part_of(path)), innermost_ds(path))
            key = kinds[path][0]
            if "/" not in path and text.startswith(moe_cost.GMM_PREFIXES) \
                    and not text.startswith(moe_cost.NOT_GMM):
                # XLA's grouped-matmul call carries no name stack (its
                # ``op_name`` is "ragged-dot-none"): the MoE block's by its
                # name, as ``moe.gmm_ms_per_step`` reads it; its pass unknown
                key = ("other", "moe")
            name = reduce_trace.short(text) or path
            if key[1] == "unnamed":
                unnamed[name] = unnamed.get(name, 0.0) + dur * per
            ms[key] = ms.get(key, 0.0) + dur * per
            if " custom-call(" in text:      # the opcode, not an operand
                custom[key] = custom.get(key, 0.0) + dur * per
            step_scopes = step_scopes or STEP in path
            scope = kinds[path][1]
            if scope:
                ds[(scope, key[0])] = ds.get((scope, key[0]), 0.0) + dur * per
            op = ops.setdefault(name, [0.0, path])
            op[0] += dur * per
    top = sorted(((t, n, p) for n, (t, p) in ops.items()), reverse=True)[:20]
    return {"ms": ms, "custom_ms": custom, "ds_ms": ds, "top": top,
            "unnamed": sorted(((t, n, ops[n][1]) for n, t in unnamed.items()),
                              reverse=True)[:10],
            "busy_ms": sum(ms.values()),
            "step_scopes": step_scopes, "steps": steps, "chips": len(planes)}


def total(table: dict, phase: Optional[str] = None, part: Optional[str] = None,
          of: str = "ms") -> float:
    return sum(v for (ph, pt), v in table[of].items()
               if phase in (None, ph) and part in (None, pt))


def named_pct(table: dict) -> Optional[float]:
    """Share of the busy time whose part is not ``unnamed``."""
    if not table["busy_ms"]:
        return None
    return 100.0 * (1.0 - total(table, part="unnamed") / table["busy_ms"])


def render(table: dict, source: str) -> List[str]:
    """The whole table as lines for the log: phase x part, the ``ds.*``
    scopes, and the longest ops with their paths."""
    parts = [p for p in PARTS if total(table, part=p)]
    lines = [f"scope table (ms a step and chip; {table['steps']} steps, "
             f"{table['chips']} chip(s); paths from {source}; "
             f"ds.step scopes {'present' if table['step_scopes'] else 'ABSENT'})",
             "| phase | " + " | ".join(parts) + " | all | of it custom calls |",
             "| --- |" + " --- |" * (len(parts) + 2)]
    for ph in PHASES + (None, ):
        if ph is not None and not total(table, phase=ph):
            continue
        cells = [f"{total(table, ph, p):.2f}" for p in parts]
        lines.append(f"| {ph or 'all'} | " + " | ".join(cells)
                     + f" | {total(table, ph):.2f}"
                     + f" | {total(table, ph, of='custom_ms'):.2f} |")
    lines.append(f"named {named_pct(table) or 0.0:.2f}% of "
                 f"{table['busy_ms']:.2f} ms busy")
    for (scope, ph), v in sorted(table["ds_ms"].items()):
        lines.append(f"scope {scope} [{ph}] {v:.3f} ms")
    for t, name, path in table["top"]:
        lines.append(f"op {t:8.3f} ms {name} <- {path or '(no path)'}")
    for t, name, path in table["unnamed"]:
        lines.append(f"unnamed {t:8.3f} ms {name} <- {path or '(no path)'}")
    return lines


def report(path: str, chips: int, steps: int) -> Optional[dict]:
    """The table of one xplane file, printed whole with the seconds the
    reading took; ``None`` when the file holds no device event."""
    t0 = time.monotonic()
    planes, source = read_events(path, chips)
    table = build_table(planes, steps)
    if table is not None:
        for line in render(table, source):
            print(line, flush=True)
        print(f"scope table read in {time.monotonic() - t0:.2f} s", flush=True)
    return table


def load(run: dict) -> Optional[dict]:
    """The run's table, parsed once and cached on ``run``; logged whole the
    first time, so that a traced run's log is the evidence. ``None`` when the
    run left no one trace, nothing was traced, or no path holds a
    ``ds.step.*`` scope: the readers then report nothing."""
    if "_scope_table" not in run:
        table, path = None, host_spans._xplane_path()
        steps = run.get("trace_steps")
        if path is not None and steps:
            chips = run.get("chips") or run.get("device", {}).get("count", 1)
            table = report(path, chips, steps)
            if table is not None and not table["step_scopes"]:
                table = None
        run["_scope_table"] = table
    return run["_scope_table"]


def phase_ms(run: dict, phase: str) -> Optional[float]:
    table = load(run)
    return None if table is None else total(table, phase=phase)


def part_ms(run: dict, part: str, less_custom: bool = False) -> Optional[float]:
    """A part's time over every phase, ``less_custom`` without its custom
    calls; ``None`` where the program has no such part."""
    table = load(run)
    if table is None or not total(table, part=part):
        return None
    ms = total(table, part=part)
    return ms - total(table, part=part, of="custom_ms") if less_custom else ms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--steps", type=int, required=True,
                    help="optimizer steps inside the traced window")
    args = ap.parse_args(argv)
    if report(args.xplane, args.chips, args.steps) is None:
        print("no device event to read", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
