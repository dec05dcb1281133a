"""The traffic generator: deterministic under its seed, every seed the same
sizes in the same order, within each mix's clips and near its medians."""
from __future__ import annotations

import json
import math
import statistics

import pytest

import perfbench_tiny as tiny
from harness.traffic import Traffic

# an open-loop mix beside the committed ones, so the open loop stays
# covered while no cell uses it
OPEN = {
    "loop": "open", "rate_per_s": 0.5, "strata": 16,
    "turn_tokens": {"kind": "lognormal", "median": 600, "sigma": 0.9,
                    "min": 32, "max": 2048},
    "output_tokens": {"kind": "lognormal", "median": 192, "sigma": 0.8,
                      "min": 16, "max": 1024},
    "warmup_s": 10, "drain_s": 30,
}
MIXES = sorted(p.stem for p in (tiny.BENCH_DIR / "traffic").glob("*.json")
               ) + ["open"]


def _mix(name):
    if name == "open":
        return dict(OPEN)
    return json.loads((tiny.BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


def _requests(tr, n_sessions):
    out = []
    for i in range(n_sessions):
        s = tr.session(i)
        for t, turn in enumerate(s.turns):
            p = tr.prompt(s, t)
            out.append((len(p), turn.output, tuple(p[:4])))
    return out


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_under_seed(name):
    mix = _mix(name)
    a, b = Traffic(mix, 2**35 + 3, 1000), Traffic(mix, 2**35 + 3, 1000)
    c = Traffic(mix, 7, 1000)
    assert _requests(a, 40) == _requests(b, 40)
    assert _requests(a, 40) != _requests(c, 40)
    if mix["loop"] == "open":
        assert [a.start_s(i) for i in range(50)] == \
            [b.start_s(i) for i in range(50)]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_draws_the_same_sizes(name):
    """The same sizes in the same order for every seed; each block of
    ``strata`` draws takes every stratum once, shuffled."""
    mix = _mix(name)
    k = mix["strata"]
    for tag in ("turn_tokens", "output_tokens"):
        a = Traffic(mix, 1, 1000)
        b = Traffic(mix, 2**33 + 99, 1000)
        da = [a.draw(tag, i) for i in range(3 * k)]
        assert da == [b.draw(tag, i) for i in range(3 * k)]
        assert da[:k] != da[k:2 * k] or len(set(da)) == 1
        for blk in range(3):
            assert sorted(da[blk * k:(blk + 1) * k]) == sorted(a._base[tag])


@pytest.mark.parametrize("name", MIXES)
def test_within_clips_and_near_medians(name):
    mix = _mix(name)
    tr = Traffic(mix, 11, 1000)
    for tag in ("turn_tokens", "output_tokens", "document"):
        dist = mix.get(tag)
        if dist is None:
            continue
        vals = [tr.draw(tag, i) for i in range(4 * mix["strata"])]
        assert min(vals) >= dist["min"] and max(vals) <= dist["max"]
        if dist["kind"] == "lognormal":
            med = statistics.median(vals)
            assert abs(med / dist["median"] - 1) < 0.05, (tag, med)
        if dist["kind"] == "uniform":
            med = statistics.median(vals)
            assert abs(med - (dist["min"] + dist["max"]) / 2) <= 1


def test_open_loop_rate():
    mix = dict(_mix("open"), rate_per_s=2.0, strata=32)
    tr = Traffic(mix, 5, 1000)
    n = 32 * 20
    assert n / tr.start_s(n - 1) == pytest.approx(2.0, rel=0.01)
    gaps = [tr.start_s(i + 1) - tr.start_s(i) for i in range(n - 1)]
    # exponential gaps: the median is ln 2 of the mean
    assert statistics.median(gaps) * 2.0 == pytest.approx(math.log(2),
                                                          rel=0.1)


def test_open_loop_every_seed_draws_the_same_gaps():
    """Every seed sends at the same times; each block of ``strata``
    arrivals takes every stratum of the exponential once."""
    mix = _mix("open")
    k = mix["strata"]
    a, b = Traffic(mix, 3, 1000), Traffic(mix, 2**33 + 1, 1000)
    assert [a.start_s(i) for i in range(3 * k)] == \
        [b.start_s(i) for i in range(3 * k)]
    gaps = [a.draw("gap", i) for i in range(3 * k)]
    for blk in range(3):
        assert sorted(gaps[blk * k:(blk + 1) * k]) == sorted(a._base["gap"])


def test_docqa_turns_share_the_instruction_and_the_document():
    """Every docqa request starts with the run's instruction; the turns of
    one session repeat its document, and other sessions' documents differ."""
    mix = _mix("docqa")
    tr = Traffic(mix, 2**31 + 9, 1000)
    n_pre = mix["shared_prefix_tokens"]
    s0, s1 = tr.session(0), tr.session(1)
    p0 = [tr.prompt(s0, t) for t in range(len(s0.turns))]
    doc = n_pre + s0.document
    assert all(p[:doc] == p0[0][:doc] for p in p0)
    assert all(len(p) == doc + s0.turns[t].tokens for t, p in enumerate(p0))
    q = tr.prompt(s1, 0)
    assert q[:n_pre] == p0[0][:n_pre]
    assert q[n_pre:n_pre + 16] != p0[0][n_pre:n_pre + 16]
    assert tr.worst_case_tokens() == n_pre + sum(
        mix[k]["max"] for k in ("document", "turn_tokens", "output_tokens"))
