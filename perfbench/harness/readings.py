"""What a finished run offers the metric readers, and the arithmetic they
share. A reader returns None when its cell gives it nothing to read."""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

from harness import counts
from harness.serve import Quantum, Record, Run
from harness.trace import Reduced, program_time

# names the program gives its served programs (device trace modules): the
# single engine's decode scan, then the fleet's; the chunked prefill is
# ``_chunk_prefill_fn`` in the one and ``_chunk_prefill_fleet`` in the other
DECODE_PROGRAMS = ("fused_decode_steps", "_fused_steps_fleet")
PREFILL_PROGRAM = "chunk_prefill"


def p95(values: List[float]) -> Optional[float]:
    """Nearest-rank 95th percentile: the smallest value with at least 95%
    of the sample at or below it (an infinite value stays infinite)."""
    if not values:
        return None
    v = sorted(values)
    return v[max(math.ceil(0.95 * len(v)) - 1, 0)]


@dataclasses.dataclass
class Context:
    cfg: Dict                       # configuration file
    run: Run
    setup_s: float
    peak: Dict[str, float]          # chip peaks
    reduced: Optional[Reduced] = None

    @property
    def w0(self) -> float:
        return self.run.t_w0

    @property
    def w1(self) -> float:
        return self.run.t_w1

    def due_in_window(self) -> List[Record]:
        return [r for r in self.run.records if self.w0 <= r.due < self.w1]

    def traced_quanta(self) -> List[Quantum]:
        if self.reduced is None:
            return []
        keep = set(self.reduced.quanta)
        return [q for q in self.run.quanta if q.index in keep]

    def counter_delta(self, name: str) -> float:
        return self.run.snap["w1"][name] - self.run.snap["w0"][name]

    # ------------------------------------------------------ device side
    def decode_device(self) -> Optional[tuple]:
        """(device seconds of the decode program, micro-steps it ran)."""
        if self.reduced is None:
            return None
        secs = runs = 0
        for marker in DECODE_PROGRAMS:
            s, n = program_time(self.reduced, marker)
            secs, runs = secs + s, runs + n
        steps = sum(len(q.decode) for q in self.traced_quanta())
        return (secs, steps) if runs and steps else None

    def prefill_device(self) -> Optional[tuple]:
        """(device seconds of the chunked-prefill program, launches)."""
        if self.reduced is None:
            return None
        secs, runs = program_time(self.reduced, PREFILL_PROGRAM)
        return (secs, runs) if runs else None

    def decode_least_s(self) -> float:
        total = 0.0
        for q in self.traced_quanta():
            for step in q.decode:
                c = counts.decode_step(self.cfg, step)
                total += counts.least_time(c["flops"], c["bytes"], self.peak)
        return total

    def model_flops(self) -> float:
        total = 0.0
        for q in self.traced_quanta():
            for step in q.decode:
                total += counts.decode_step(self.cfg, step)["flops"]
            for start, stop in q.prefill:
                total += counts.prefill_chunk(self.cfg, start, stop)
        return total


def decode_step_ms(ctx: Context) -> Optional[float]:
    dev = ctx.decode_device()
    return None if dev is None else 1e3 * dev[0] / dev[1]


def prefill_chunk_ms(ctx: Context) -> Optional[float]:
    dev = ctx.prefill_device()
    return None if dev is None else 1e3 * dev[0] / dev[1]


def decode_roofline(ctx: Context) -> Optional[float]:
    dev = ctx.decode_device()
    return None if dev is None else 100.0 * ctx.decode_least_s() / dev[0]


def mfu(ctx: Context) -> Optional[float]:
    dec, pre = ctx.decode_device(), ctx.prefill_device()
    secs = (dec[0] if dec else 0.0) + (pre[0] if pre else 0.0)
    flops = ctx.model_flops()
    if secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (secs * ctx.peak["flops_bf16"])


def idle_share(ctx: Context) -> Optional[float]:
    r = ctx.reduced
    if r is None or r.window_ns <= 0:
        return None
    return 100.0 * (1.0 - r.busy_ns / r.window_ns)


def batch_occupancy(ctx: Context) -> Optional[float]:
    steps = ctx.counter_delta("steps")
    if steps <= 0:
        return None
    # a fleet's micro-step runs max_batch slots on each of its shards
    slots = ctx.cfg["engine"]["max_batch"] * ctx.run.snap["w1"]["shards"]
    return 100.0 * ctx.counter_delta("decode_tokens") / (steps * slots)


def prefix_hit_share(ctx: Context) -> Optional[float]:
    t0, t1 = ctx.run.snap["w0"]["t"], ctx.run.snap["w1"]["t"]
    admitted = sum(len(r.prompt) for r in ctx.run.records
                   if r.request.t_admit is not None
                   and t0 <= r.request.t_admit < t1)
    if admitted <= 0:
        return None
    return 100.0 * ctx.counter_delta("prefix_hit_tokens") / admitted


# ------------------------------------------------------------ end to end
def output_tok_s(ctx: Context) -> Optional[float]:
    n = sum(1 for r in ctx.run.records for t in r.times
            if ctx.w0 <= t < ctx.w1)
    return n / (ctx.w1 - ctx.w0)
