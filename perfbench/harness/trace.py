"""From a profiler trace to numbers: device busy time, idle gaps and what
the host was doing in each, and the device time of each program.

``read_xplane`` turns JAX's ``.xplane.pb`` into three plain lists of
``Event``: device ops, device program executions ("XLA Modules"), and the
benchmark's own host spans (TraceAnnotations named ``perfbench.*``). The
reduction below works on those lists only, so it is checked on a small
recorded trace in the tests.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "perfbench."
QUANTUM = SPAN_PREFIX + "quantum."
OUTSIDE = "between quanta (event loop, clients)"
IN_QUANTUM = "quantum, outside the wrapped phases"
SMALL = "between ops (gaps under 20 us)"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns on the trace's clock
    dur: float              # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[Event]        # device ops (busy time), chip 0
    modules: List[Event]    # device program executions, chip 0
    spans: List[Event]      # the benchmark's host spans


def read_xplane(logdir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    ops: List[Event] = []
    modules: List[Event] = []
    spans: List[Event] = []
    devices = sorted(p.name for p in data.planes
                     if p.name.startswith("/device:TPU:"))
    for plane in data.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                into = (modules if line.name == "XLA Modules" else
                        ops if line.name == "XLA Ops" else None)
                if into is not None:
                    into.extend(Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def merge(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def idle_gaps(busy: Sequence[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


SMALL_GAP_NS = 20_000


def split(s: float, e: float, spans: Sequence[Event],
          starts: Sequence[float]):
    """Cut [s, e) by disjoint ``spans`` sorted by start: the covered parts
    as (span name, ns), and the uncovered segments."""
    covered, free, t = [], [], s
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(spans) and spans[i].start < e:
        a, b = max(spans[i].start, t), min(spans[i].end, e)
        if b > a:
            if a > t:
                free.append((t, a))
            covered.append((spans[i].name[len(SPAN_PREFIX):], b - a))
            t = b
        i += 1
    if e > t:
        free.append((t, e))
    return covered, free


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float
    quanta: List[int]                    # quantum numbers fully traced
    program_ns: Dict[str, float]         # device time per program name
    program_runs: Dict[str, int]
    top_ops: List[Tuple[str, float]]     # (op, seconds)
    idle_by_span: List[Tuple[str, float]]  # (host span, idle seconds)


def reduce(trace: Trace, top: int = 10) -> Optional[Reduced]:
    """The window is the run of whole quanta the trace holds: from the
    first quantum span's start to the last one's end. Program executions
    and ops count when they start inside it. Idle gaps of at least
    SMALL_GAP_NS are cut by the host spans open in them; shorter ones,
    between ops of one program, are summed apart."""
    qspans = sorted((s for s in trace.spans if s.name.startswith(QUANTUM)),
                    key=lambda s: s.start)
    if not qspans or not trace.ops:
        return None
    lo, hi = qspans[0].start, qspans[-1].end
    busy = merge([(e.start, e.end) for e in trace.ops], lo, hi)
    program_ns: Dict[str, float] = {}
    program_runs: Dict[str, int] = {}
    for m in trace.modules:
        if lo <= m.start < hi:
            program_ns[m.name] = program_ns.get(m.name, 0.0) + m.dur
            program_runs[m.name] = program_runs.get(m.name, 0) + 1
    op_ns: Dict[str, float] = {}
    for e in trace.ops:
        if lo <= e.start < hi:
            name = op_name(e.name)
            op_ns[name] = op_ns.get(name, 0.0) + e.dur
    inner = sorted((s for s in trace.spans if not s.name.startswith(QUANTUM)),
                   key=lambda s: s.start)
    inner_starts = [s.start for s in inner]
    q_starts = [q.start for q in qspans]
    idle: Dict[str, float] = {}

    def add(name, ns):
        idle[name] = idle.get(name, 0.0) + ns
    for s, e in idle_gaps(busy, lo, hi):
        if e - s < SMALL_GAP_NS:
            add(SMALL, e - s)
            continue
        covered, free = split(s, e, inner, inner_starts)
        for name, ns in covered:
            add(name, ns)
        for a, b in free:
            in_q, outside = split(a, b, qspans, q_starts)
            for _, ns in in_q:
                add(IN_QUANTUM, ns)
            for x, y in outside:
                add(OUTSIDE, y - x)
    return Reduced(
        window_ns=hi - lo, busy_ns=sum(e - s for s, e in busy),
        quanta=[int(q.name[len(QUANTUM):]) for q in qspans],
        program_ns=program_ns, program_runs=program_runs,
        top_ops=[(n, t * 1e-9) for n, t in
                 sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        idle_by_span=[(n, t * 1e-9) for n, t in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]])


def op_name(text: str) -> str:
    """An op event's HLO instruction name: the trace names an op by its
    whole instruction text, ``%fusion.12 = bf16[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_time(reduced: Reduced, marker: str) -> Tuple[float, int]:
    """Device seconds and executions of the programs whose name holds
    ``marker``."""
    ns = sum(t for n, t in reduced.program_ns.items() if marker in n)
    runs = sum(c for n, c in reduced.program_runs.items() if marker in n)
    return ns * 1e-9, runs
