"""Per-layer readings of the program's own instrumentation (host spans,
model scopes, the pool's page counts; ``harness.program_trace``), and the
least time of decode attention's own work. Each returns None where the
program, or the run, gives it nothing to read."""
from __future__ import annotations

from typing import Dict, Iterable, Optional

from harness import counts, program_trace as PT
from harness.readings import DECODE_PROGRAM, PREFILL_PROGRAM, Context

_MISSING = object()


def reduction(ctx: Context) -> Optional[PT.Reduction]:
    """The traced run's reduction, read once per run."""
    red = getattr(ctx, "_program_reduction", _MISSING)
    if red is _MISSING:
        red = None
        if ctx.reduced is not None and ctx.run.trace_dir:
            red = PT.reduce(PT.read(ctx.run.trace_dir))
        ctx._program_reduction = red
    return red


def decode_attention(cfg: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """Attention's own work in one decode micro-step over the live slots at
    ``positions``: scores and the value sum over p + 1 keys, p rows of K
    and V read and one written per slot."""
    positions = list(positions)
    kvb = counts.kv_bytes_per_token(cfg)
    return {"flops": float(sum(counts.attention_flops(cfg, p + 1)
                               for p in positions)),
            "bytes": float(sum((p + 1) * kvb for p in positions))}


def _attn_decode_s(ctx: Context) -> Optional[float]:
    red = reduction(ctx)
    secs = None if red is None else PT.scope_time(red, DECODE_PROGRAM,
                                                  "attn_decode")
    return secs or None


def attn_decode_ms(ctx: Context) -> Optional[float]:
    secs, dev = _attn_decode_s(ctx), ctx.decode_device()
    return None if secs is None or dev is None else 1e3 * secs / dev[1]


def attn_decode_roofline(ctx: Context) -> Optional[float]:
    secs = _attn_decode_s(ctx)
    if secs is None:
        return None
    least = 0.0
    for q in ctx.traced_quanta():
        for step in q.decode:
            c = decode_attention(ctx.cfg, step)
            least += counts.least_time(c["flops"], c["bytes"], ctx.peak)
    return 100.0 * least / secs if least > 0 else None


def attn_prefill_ms(ctx: Context) -> Optional[float]:
    red, dev = reduction(ctx), ctx.prefill_device()
    if red is None or dev is None:
        return None
    secs = PT.scope_time(red, PREFILL_PROGRAM, "attn_prefill")
    return 1e3 * secs / dev[1] if secs else None


def kv_mapped_share(ctx: Context) -> Optional[float]:
    """Pages mapped over pages reserved, summed over the traced quanta:
    the delta of ``mapped_page_quanta`` over that of
    ``reserved_page_quanta`` across them."""
    red = reduction(ctx)
    if red is None:
        return None
    reserved = mapped = 0
    for q in red.quanta:
        args = dict(q.args)
        reserved += args.get("reserved_pages", 0)
        mapped += args.get("mapped_pages", 0)
    return 100.0 * mapped / reserved if reserved > 0 else None


def host_stall_ms(ctx: Context) -> Optional[float]:
    """Device idle time in gaps of at least 20 us that fall inside a
    ``repro.*`` span, per traced quantum."""
    red = reduction(ctx)
    if red is None or not red.quanta:
        return None
    return 1e-6 * PT.stall_ns(red) / len(red.quanta)
