"""Readings for the limit of ``correct``: serve a cell on several seeds in
one process, with a short window at the cell's own load, and print for
each seed the widest logit gap of the program's served tokens and of the
float8 control's, on the same sample of requests, and the verdict that
each would get against the configuration's limit.

    python3 perfbench/tools/limits.py --workload minicpm-2b.chat-batch \
        --seeds 1,2,3 --seconds 15
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]

from harness import spec                                        # noqa: E402
from harness.device import (CompileLog, NoChip, check_devices,  # noqa: E402
                            enable_compile_cache)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    import run as R
    cell = spec.resolve(spec.load_benchmark(spec.ROOT), args.workload)
    try:
        device = check_devices(cell.chips)
    except NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 1
    enable_compile_cache(spec.ROOT)
    log = CompileLog()
    for seed in (int(x) for x in args.seeds.split(",")):
        res = R.run_cell(cell, seed, args.seconds, False, device, log,
                         time.perf_counter(), control=True)
        rep = res["_report"]
        limit = res["checks"]["widest_logit_gap"]["limit"]
        control = rep["control_widest_logit_gap"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program_gap": res["checks"]["widest_logit_gap"]["value"],
            "control_gap": control, "limit": limit,
            # the harness's own verdict with the control in the program's
            # place: its gap against the same limit
            "control_correct": (control is not None and limit is not None
                                and control <= limit),
            "failed": rep["requests_failed"],
            "compiles_in_window": rep["compiles_in_window"],
            "setup_s": rep["setup_s"], "peak_bytes": rep["peak_bytes_in_use"],
            "window_output_tok_s": rep["window_output_tok_s"],
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "sampled": rep["sampled_requests"],
            "reference_s": rep["setup_split_s"]["reference_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
