"""The program's own instrumentation in a traced run, as one breakdown:
device self time by model scope within each program (and the ops that
take most of it), device idle time by the innermost ``repro.*`` span, and
the sums that tie both to the benchmark's own reduction of the trace.

    python3 perfbench/tools/scopes.py .perfbench_cache/trace/<cell>

Reads the trace that a ``--trace 1`` run of ``perfbench/run.py`` leaves
behind, and prints one JSON object; it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]

from harness import program_trace as PT                         # noqa: E402
from harness.trace import read_xplane, reduce                   # noqa: E402


def breakdown(logdir: str, top: int = 12) -> dict:
    pt = PT.read(logdir)
    red = PT.reduce(pt)
    bench = reduce(read_xplane(logdir))
    if red is None or bench is None:
        return {"error": "no quanta or no device ops in the trace"}
    programs = {}
    for prog, by in sorted(red.scope_ns.items(),
                           key=lambda kv: -red.program_busy_ns[kv[0]]):
        ops = sorted(((op, scope, ns) for (p, op, scope), ns
                      in red.op_self_ns.items() if p == prog),
                     key=lambda x: -x[2])[:top]
        programs[prog] = {
            "busy_s": red.program_busy_ns[prog] * 1e-9,
            "scopes_s": {s: ns * 1e-9 for s, ns in
                         sorted(by.items(), key=lambda kv: -kv[1])},
            "top_ops_s": [[op, scope, ns * 1e-9] for op, scope, ns in ops]}
    bench_idle = bench.window_ns - bench.busy_ns
    idle = sum(red.idle_ns.values())
    worst = max(abs(sum(by.values()) - red.program_busy_ns[p])
                / red.program_busy_ns[p] for p, by in red.scope_ns.items())
    args = [dict(q.args) for q in red.quanta]
    reserved = sum(a.get("reserved_pages", 0) for a in args)
    return {
        "window_s": red.window_ns * 1e-9, "busy_s": red.busy_ns * 1e-9,
        "bench_busy_s": bench.busy_ns * 1e-9,
        "quanta": len(red.quanta),
        "device_scopes": programs,
        "idle_by_program": {k: ns * 1e-9 for k, ns in
                            sorted(red.idle_ns.items(),
                                   key=lambda kv: -kv[1])},
        "host_stall_s": PT.stall_ns(red) * 1e-9,
        "kv_mapped_share": (100.0 * sum(a.get("mapped_pages", 0)
                                        for a in args) / reserved
                            if reserved else None),
        "checks": {
            "self_over_busy": red.busy_ns / bench.busy_ns,
            "scopes_vs_program_busy_worst": worst,
            "idle_over_idle_share_idle": (idle / bench_idle
                                          if bench_idle else None)},
        "scoped_ops_share": (sum(1 for o in pt.ops if o.scope != PT.UNSCOPED)
                             / len(pt.ops)),
        "longest_gaps_s": [[t * 1e-9, ns * 1e-9, span]
                           for t, ns, span in red.longest_gaps],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    print(json.dumps(breakdown(args.trace_dir, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
