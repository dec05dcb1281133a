"""Per-layer readings of the dropless expert layer (``models/moe.py``) in
the decode program: device self time under its scopes ``moe_route``
(router, top-k, dispatch, combine) and ``moe_experts`` (the held experts'
products), and the least time of that work from the program's counters.

``harness.program_trace`` gives each op the innermost of the scopes it
knows; these scopes sit inside ``mlp``, so it files them there. This
module reads the same trace again and gives each op its innermost expert
scope instead, with the same inheritance for ops the compiler added
without metadata and the same self time (the part of an op's run in which
it is the innermost op open). The counters are arguments of the
program's ``repro.quantum`` spans (``moe_decode_pairs``,
``moe_decode_experts_hit``: that quantum's decode, summed over its
micro-steps and layers). Each reading is None where the program, or the
run, gives it nothing to read: a program without these scopes or
counters, or a run without a trace.
"""
from __future__ import annotations

import dataclasses
import glob
from typing import Dict, Optional

from harness import counts, program_readings, program_trace as PT
from harness.model import architecture
from harness.readings import DECODE_PROGRAMS, Context

SCOPES = ("moe_route", "moe_experts")
_MISSING = object()


def moe_scope(op_name: str) -> str:
    """The innermost expert scope in an ``op_name`` path, or UNSCOPED."""
    for part in reversed(op_name.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return PT.UNSCOPED


def _device_plane(path: str) -> Optional[str]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = sorted(p.name for p in data.planes
                     if p.name.startswith("/device:TPU:"))
    return devices[0] if devices else None


def decode_scope_s(trace_dir: str) -> Dict[str, float]:
    """Device self seconds under each expert scope in the decode programs
    of a traced run, inside the window ``program_trace.reduce`` uses."""
    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    plane = _device_plane(paths[-1]) if paths else None
    if plane is None:
        return {}
    return expert_self_s(PT.read(trace_dir), PT.op_names(paths[-1], plane))


def expert_self_s(pt: PT.ProgramTrace,
                  names: Dict[tuple, str]) -> Dict[str, float]:
    """``decode_scope_s`` of a read trace: ``names`` maps (program id,
    instruction text) to the op's ``tf_op`` path (``PT.op_names``)."""
    win = PT._window(pt)
    if win is None or not pt.ops:
        return {}
    at = PT.program_of(pt.modules)
    ops = [dataclasses.replace(op, scope=moe_scope(names.get(
        (PT._program_id(at(op.start)), op.name), ""))) for op in pt.ops]
    ops = PT._inherit(ops, lambda o: at(o.start))
    out: Dict[str, float] = {}
    for a, b, op in PT.innermost([(o.start, o.end, o) for o in ops], *win):
        if op is None or op.scope not in SCOPES:
            continue
        if any(m in at(op.start) for m in DECODE_PROGRAMS):
            out[op.scope] = out.get(op.scope, 0.0) + 1e-9 * (b - a)
    return out


def _moe_decode_s(ctx: Context) -> Optional[float]:
    secs = getattr(ctx, "_moe_decode_s", _MISSING)
    if secs is _MISSING:
        secs = None
        if program_readings.reduction(ctx) is not None:
            secs = sum(decode_scope_s(ctx.run.trace_dir).values()) or None
        ctx._moe_decode_s = secs
    return secs


def moe_decode_ms(ctx: Context) -> Optional[float]:
    """Device self time under the expert scopes in the decode program per
    traced decode micro-step."""
    secs, dev = _moe_decode_s(ctx), ctx.decode_device()
    return None if secs is None or dev is None else 1e3 * secs / dev[1]


def moe_decode_roofline(ctx: Context) -> Optional[float]:
    """Least time of the held experts' decode work (the architecture's
    ``moe_decode`` of the counted pairs and experts hit) over the device
    self time under the expert scopes."""
    secs, dev = _moe_decode_s(ctx), ctx.decode_device()
    red = program_readings.reduction(ctx)
    arch = architecture(ctx.cfg)
    if secs is None or dev is None or not hasattr(arch, "moe_decode"):
        return None
    pairs = hit = 0
    for q in red.quanta:
        args = dict(q.args)
        pairs += args.get("moe_decode_pairs", 0)
        hit += args.get("moe_decode_experts_hit", 0)
    if not pairs:
        return None
    c = arch.moe_decode(ctx.cfg, pairs, hit, dev[1])
    return 100.0 * counts.least_time(c["flops"], c["bytes"], ctx.peak) / secs
