"""The reduction from a trace to busy time, idle gaps by host span, and
device time per program, on a small trace recorded here (ns)."""
from __future__ import annotations

import pytest

import perfbench_tiny  # noqa: F401  (puts the harness on the path)
from harness.trace import (IN_QUANTUM, OUTSIDE, SMALL, Event, Trace,
                           merge, program_time, reduce)

# two quanta on the host; in each, an admit span, a prefill-quantum span
# and a decode-chunk span; on the device, a prefill program and a decode
# program per quantum, each of a few ops, one pair of ops overlapping
TRACE = Trace(
    spans=[Event("perfbench.quantum.7", 1_000_000, 4_000_000),
           Event("perfbench.admit", 1_000_000, 500_000),
           Event("perfbench.prefill_quantum", 1_500_000, 500_000),
           Event("perfbench.decode_chunk", 2_000_000, 2_900_000),
           Event("perfbench.pump", 5_000_000, 400_000),
           Event("perfbench.quantum.8", 5_500_000, 2_500_000),
           Event("perfbench.decode_chunk", 5_600_000, 2_300_000)],
    modules=[Event("jit__chunk_prefill_fn(1)", 1_600_000, 300_000),
             Event("jit_fused_decode_steps(2)", 2_100_000, 2_000_000),
             Event("jit_fused_decode_steps(2)", 5_700_000, 2_000_000),
             Event("jit_fused_decode_steps(2)", 9_000_000, 1_000_000)],
    ops=[Event("fusion.1", 1_600_000, 300_000),
         Event("fusion.2", 2_100_000, 1_000_000),
         Event("dot.3", 2_600_000, 1_500_000),        # overlaps fusion.2
         Event("fusion.2", 5_700_000, 2_000_000),
         Event("fusion.2", 4_105_000, 10_000),        # a 5 us gap before it
         Event("fusion.9", 9_000_000, 1_000_000)])    # after the last quantum


def test_busy_union_and_window():
    r = reduce(TRACE)
    assert r.quanta == [7, 8]
    assert r.window_ns == 7_000_000                    # 1.0 ms .. 8.0 ms
    # 0.3 (prefill) + 2.0 (2.1..4.1 merged) + 0.01 + 2.0 ms
    assert r.busy_ns == 300_000 + 2_000_000 + 10_000 + 2_000_000


def test_idle_gaps_are_cut_by_the_host_spans_open_in_them():
    idle = dict(reduce(TRACE).idle_by_span)
    want = {"admit": 0.5e-3,                            # 1.0-1.5
            "prefill_quantum": 0.2e-3,                  # 1.5-1.6, 1.9-2.0
            # 2.0-2.1, 4.115-4.9, 5.6-5.7, 7.7-7.9
            "decode_chunk": (0.1 + 0.785 + 0.1 + 0.2) * 1e-3,
            "pump": 0.4e-3,                             # 5.0-5.4
            IN_QUANTUM: 0.3e-3,                         # 4.9-5.0, 5.5-5.6, 7.9-8.0
            OUTSIDE: 0.1e-3,                            # 5.4-5.5
            SMALL: 5e-6}                                # 4.1-4.105
    assert set(idle) == set(want)
    for name, secs in want.items():
        assert abs(idle[name] - secs) < 1e-12, name
    assert abs(sum(idle.values()) - (7e-3 - 4.31e-3)) < 1e-12


def test_program_time_counts_executions_inside_the_window():
    r = reduce(TRACE)
    assert program_time(r, "fused_decode_steps") == pytest.approx((4e-3, 2))
    assert program_time(r, "chunk_prefill") == pytest.approx((0.3e-3, 1))
    assert program_time(r, "no_such_program") == (0.0, 0)


def test_merge_clips_and_joins():
    assert merge([(5, 9), (0, 3), (2, 6), (12, 20)], 1, 15) == \
        [(1, 9), (12, 15)]
