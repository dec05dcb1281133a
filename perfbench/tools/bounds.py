"""Spread of a cell's end-to-end metrics over sets of runs, and the bound
they suggest: for each metric, each set's spread (first to third
quartile, as ``statistics.quantiles(values, n=4)`` gives them, over the
median), and five times the widest, never under 1%.

    python3 perfbench/tools/bounds.py --set a1.out a2.out a3.out \
        --set b1.out b2.out b3.out

Each file is the standard output of one ``--trace 0`` run; its last line
is the result. The sets hold runs of one cell, with the same seeds in
each set.
"""
from __future__ import annotations

import argparse
import json
import statistics
from typing import Dict, List


def spread(values: List[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def read_set(paths: List[str]) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for p in paths:
        with open(p) as f:
            last = f.read().strip().splitlines()[-1]
        for name, m in json.loads(last)["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--set", nargs="+", action="append", required=True)
    args = ap.parse_args(argv)
    sets = [read_set(paths) for paths in args.set]
    for name in sorted(set().union(*sets)):
        rows = [s[name] for s in sets if len(s.get(name, [])) >= 2]
        spreads = [spread(v) for v in rows]
        medians = [statistics.median(v) for v in rows]
        print(json.dumps({
            "metric": name, "medians": medians, "spreads": spreads,
            "bound": max(0.01, 5 * max(spreads)) if spreads else None}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
