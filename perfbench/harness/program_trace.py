"""What the program's own instrumentation says in a profiler trace: device
self time by model scope within each program, and device idle time by the
innermost host span the program had open.

The program opens host spans named ``repro.<phase>`` (one
``repro.quantum`` per scheduling quantum, carrying ``step_num`` and its
page counts) and names parts of its model with ``jax.named_scope``; each
device op carries its scope in its HLO ``op_name`` (a fusion's is its
root's), which the trace keeps as the ``tf_op`` stat of the op's event
metadata, per program. ``jax.profiler.ProfileData`` does not show event
metadata, so ``op_names`` reads it from the ``.xplane.pb`` itself. An op
the compiler added without metadata (a layout copy or a dtype
conversion) takes the scope its operands agree on, read from the
instruction text that names the event. A trace of a program without
spans or scopes reads as all unscoped and without spans, and the readers
built on this return None.

The window is the one ``harness.trace.reduce`` uses (the benchmark's own
quantum spans), or, where those are missing, the program's quantum spans,
so the idle time here partitions the idle time ``idle_share`` reads.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from harness.trace import QUANTUM as BENCH_QUANTUM
from harness.trace import SMALL, SMALL_GAP_NS, Event, merge

SPAN_PREFIX = "repro."
QUANTUM = SPAN_PREFIX + "quantum"
# the model's named scopes; an op under none of them is UNSCOPED
SCOPES = ("attn_decode", "attn_prefill", "attn_proj", "mlp", "lm_head",
          "sample")
UNSCOPED = "unscoped"
OUTSIDE = "outside every repro span"
NO_PROGRAM = "no program"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float            # ns on the trace's clock
    dur: float              # ns
    scope: str

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float
    args: Tuple[Tuple[str, object], ...] = ()

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class ProgramTrace:
    ops: List[Op]               # device ops of chip 0, with their scope
    modules: List[Event]        # device program executions of chip 0
    spans: List[Span]           # the program's host spans
    bench_quanta: List[Event]   # the benchmark's quantum spans, if any


def scope_of(op_name: str) -> str:
    """The innermost model scope in an ``op_name`` path (the trace ends
    it with a colon), or UNSCOPED."""
    for part in reversed(op_name.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


# --- the protobuf wire format, enough to read a plane's event metadata:
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (map entries: key 1, value 2); XEventMetadata.name
# = 2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, values
# uint64 = 3, int64 = 4, str = 5, ref (a stat metadata id) = 7.

def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def op_names(path: str, plane: str) -> Dict[Tuple[str, str], str]:
    """(program id, instruction text) -> ``tf_op`` of every op event
    metadata of the trace's plane named ``plane``."""
    data = memoryview(Path(path).read_bytes())
    for field, body in _fields(data):
        if field != 1:
            continue
        parts = list(_fields(body))
        if not any(f == 2 and bytes(v).decode() == plane for f, v in parts):
            continue
        stat_names: Dict[int, str] = {}
        for f, entry in parts:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        out: Dict[Tuple[str, str], str] = {}
        for f, entry in parts:
            if f != 4:
                continue
            name, stats = "", {}
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                if g == 2:
                    name = bytes(v).decode(errors="replace")
                elif g == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = bytes(st[5]).decode(errors="replace")
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    else:
                        stats[key] = str(st.get(3, st.get(4, "")))
            if "tf_op" in stats:
                out[(stats.get("program_id", ""), name)] = stats["tf_op"]
        return out
    return {}


def _program_id(module: str) -> str:
    """``jit_fused_decode_steps(1517...)`` -> ``1517...``."""
    return module.rsplit("(", 1)[-1].rstrip(")") if "(" in module else ""


def read(logdir: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    out = ProgramTrace(ops=[], modules=[], spans=[], bench_quanta=[])
    devices = sorted(p.name for p in data.planes
                     if p.name.startswith("/device:TPU:"))
    raw: List[Event] = []
    for plane in data.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out.modules.extend(Event(e.name, e.start_ns,
                                             e.duration_ns)
                                       for e in line.events)
                elif line.name == "XLA Ops":
                    raw.extend(Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.spans.append(Span(e.name, e.start_ns,
                                              e.duration_ns,
                                              tuple(e.stats)))
                    elif e.name.startswith(BENCH_QUANTUM):
                        out.bench_quanta.append(Event(e.name, e.start_ns,
                                                      e.duration_ns))
    if raw:
        names = op_names(paths[-1], devices[0])
        program = program_of(out.modules)
        out.ops = [Op(e.name, e.start, e.dur, scope_of(names.get(
            (_program_id(program(e.start)), e.name), "")))
            for e in raw]
    return out


def program_of(modules: Sequence[Event]):
    """A function from a device time to the name of the program (module)
    executing then, or NO_PROGRAM."""
    modules = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in modules]

    def program(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < modules[i].end:
            return modules[i].name
        return NO_PROGRAM
    return program


def innermost(items: Sequence[Tuple[float, float, object]], lo: float,
              hi: float) -> List[Tuple[float, float, object]]:
    """Cut [lo, hi) into segments, each given to the innermost item open
    over it (of those still open, the one opened last) or to None where
    none is. ``items`` are (start, end, key); the segments' lengths per
    key are the keys' self times, and the None segments the gaps."""
    todo = sorted(((max(s, lo), min(e, hi), k) for s, e, k in items
                   if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
    segs: List[Tuple[float, float, object]] = []
    stack: List[Tuple[float, float, object]] = []
    t = lo

    def advance(to: float) -> None:
        nonlocal t
        while t < to:
            while stack and stack[-1][1] <= t:
                stack.pop()
            if not stack:
                segs.append((t, to, None))
                t = to
                return
            b = min(stack[-1][1], to)
            segs.append((t, b, stack[-1][2]))
            t = b

    for s, e, k in todo:
        advance(s)
        stack.append((s, e, k))
    advance(hi)
    return segs


@dataclasses.dataclass
class Reduction:
    window_ns: float
    busy_ns: float
    quanta: List[Span]                         # program quanta in the window
    # program (module name) -> scope -> device self ns (UNSCOPED included)
    scope_ns: Dict[str, Dict[str, float]]
    program_busy_ns: Dict[str, float]   # union of each program's op runs
    op_self_ns: Dict[Tuple[str, str, str], float]  # (program, op, scope)
    # innermost repro span (name) -> device idle ns in gaps >= 20 us, plus
    # OUTSIDE and the gaps under 20 us (SMALL)
    idle_ns: Dict[str, float]
    # the longest idle gaps >= 20 us: (ns from the window's start, ns, the
    # innermost span open at the gap's start)
    longest_gaps: List[Tuple[float, float, str]]


def _window(pt: ProgramTrace) -> Optional[Tuple[float, float]]:
    marks = pt.bench_quanta or [s for s in pt.spans if s.name == QUANTUM]
    if not marks:
        return None
    return min(m.start for m in marks), max(m.end for m in marks)


def _op_name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


def _operands(text: str) -> List[str]:
    rhs = text.split(" = ", 1)[1] if " = " in text else ""
    return re.findall(r"%([\w.\-]+)", rhs)


def _inherit(ops: List[Op], program) -> List[Op]:
    """Ops without a scope of their own take the one scope their operands
    (earlier ops of the same program) have, if they agree on one."""
    known: Dict[Tuple[str, str], str] = {}
    out = []
    for op in sorted(ops, key=lambda o: o.start):
        prog = program(op)
        if op.scope == UNSCOPED:
            got = {known.get((prog, n)) for n in _operands(op.name)}
            got.discard(None)
            if len(got) == 1:
                op = dataclasses.replace(op, scope=got.pop())
        if op.scope != UNSCOPED:
            known[(prog, _op_name(op.name))] = op.scope
        out.append(op)
    return out


def reduce(pt: ProgramTrace) -> Optional[Reduction]:
    """Self time of every device op in the window (the part of its run in
    which it is the innermost op open: a loop is charged only what its
    body's ops do not cover), summed by program and scope; and every idle
    gap cut by the innermost ``repro.*`` span open in it."""
    win = _window(pt)
    if win is None or not pt.ops:
        return None
    lo, hi = win
    at = program_of(pt.modules)

    def program(op: Op) -> str:
        return at(op.start)

    op_self: Dict[Tuple[str, str, str], float] = {}
    idle: Dict[str, float] = {}
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    ops = _inherit(pt.ops, program)
    for a, b, op in innermost([(o.start, o.end, o) for o in ops], lo, hi):
        if op is None:
            gaps.append((a, b))
            continue
        busy += b - a
        key = (program(op), _op_name(op.name), op.scope)
        op_self[key] = op_self.get(key, 0.0) + (b - a)
    scope_ns: Dict[str, Dict[str, float]] = {}
    for (prog, _, scope), ns in op_self.items():
        by = scope_ns.setdefault(prog, {})
        by[scope] = by.get(scope, 0.0) + ns
    spans_of: Dict[str, List[Tuple[float, float]]] = {}
    for op in ops:
        spans_of.setdefault(program(op), []).append((op.start, op.end))
    program_busy = {prog: sum(e - s for s, e in merge(iv, lo, hi))
                    for prog, iv in spans_of.items() if prog in scope_ns}
    segs = innermost([(s.start, s.end, s.name) for s in pt.spans], lo, hi)
    j = 0
    longest: List[Tuple[float, float, str]] = []
    for a, b in gaps:
        if b - a < SMALL_GAP_NS:
            idle[SMALL] = idle.get(SMALL, 0.0) + (b - a)
            continue
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        if j < len(segs):
            longest.append((a - lo, b - a, segs[j][2] or OUTSIDE))
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            ns = min(e, b) - max(s, a)
            if ns > 0:
                key = OUTSIDE if name is None else name
                idle[key] = idle.get(key, 0.0) + ns
            k += 1
    quanta = [s for s in pt.spans if s.name == QUANTUM and lo <= s.start < hi]
    return Reduction(window_ns=hi - lo, busy_ns=busy, quanta=quanta,
                     scope_ns=scope_ns, program_busy_ns=program_busy,
                     op_self_ns=op_self, idle_ns=idle,
                     longest_gaps=sorted(longest, key=lambda g: -g[1])[:10])


def scope_time(red: Reduction, marker: str, scope: str) -> float:
    """Device self seconds under ``scope`` in the programs whose name
    holds ``marker``."""
    return 1e-9 * sum(by.get(scope, 0.0) for prog, by in red.scope_ns.items()
                      if marker in prog)


def stall_ns(red: Reduction) -> float:
    """Device idle time in gaps of at least 20 us inside a repro span."""
    return sum(ns for name, ns in red.idle_ns.items()
               if name not in (OUTSIDE, SMALL))
