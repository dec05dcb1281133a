"""The bound arithmetic: a set's spread is its first to third quartile
over its median, as ``statistics.quantiles(values, n=4)`` gives them, and
the bound is five times the widest spread, never under 1%."""
from __future__ import annotations

import json

import pytest

import perfbench_tiny  # noqa: F401  (puts the benchmark on the path)
from tools import bounds


def test_spread_is_the_quartile_range_over_the_median():
    # quantiles(n=4) of 1..6 (exclusive method): 1.75 and 5.25
    assert bounds.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert bounds.spread([100.0] * 6) == 0.0


def test_bound_is_five_times_the_widest_spread_and_at_least_one_percent(
        tmp_path, capsys):
    def out(name, value):
        p = tmp_path / name
        p.write_text("noise\n" + json.dumps({"metrics": {
            "output_tok_s": {"value": value, "unit": "tokens/s"}}}) + "\n")
        return str(p)
    a = [out(f"a{i}", v) for i, v in enumerate([100, 100, 100, 100])]
    b = [out(f"b{i}", v) for i, v in enumerate([98, 100, 102, 100])]
    bounds.main(["--set", *a, "--set", *b])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["spreads"][0] == 0.0
    assert row["bound"] == pytest.approx(5 * bounds.spread([98, 100, 102,
                                                            100]))
    c = [out(f"c{i}", 100.0) for i in range(4)]
    bounds.main(["--set", *c, "--set", *c])
    assert json.loads(capsys.readouterr().out.strip())["bound"] == 0.01
