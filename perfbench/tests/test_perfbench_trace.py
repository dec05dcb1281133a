"""The reduction from a trace to busy time, idle gaps by host span, and
device time per program, on a small trace recorded here (ns)."""
from __future__ import annotations

import dataclasses
import types

import pytest

import perfbench_tiny  # noqa: F401  (puts the harness on the path)
from harness import readings
from harness.serve import Quantum
from harness.trace import (IN_QUANTUM, OUTSIDE, SMALL, Event, Trace,
                           merge, program_time, read_xplane, reduce)

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


@pytest.mark.parametrize("program", ["jit_fused_decode_steps(2)",
                                     "jit__fused_steps_fleet(5)"])
def test_decode_readers_read_either_engines_decode_program(program):
    """The single engine's decode scan and the fleet's, under their own
    program names, give the same per-step time."""
    trace = dataclasses.replace(TRACE, modules=[
        dataclasses.replace(m, name=program) if "decode" in m.name else m
        for m in TRACE.modules])
    run = types.SimpleNamespace(quanta=[
        Quantum(index=7, prefill=[(0, 3)], decode=[[4, 9], [5, 10]]),
        Quantum(index=8, prefill=[], decode=[[6]])])
    ctx = readings.Context(cfg={}, run=run, setup_s=0.0, peak={},
                           reduced=reduce(trace))
    assert ctx.decode_device() == pytest.approx((4e-3, 3))
    assert readings.decode_step_ms(ctx) == pytest.approx(4e-3 * 1e3 / 3)
    assert readings.prefill_chunk_ms(ctx) == pytest.approx(0.3)


def test_merge_clips_and_joins():
    assert merge([(5, 9), (0, 3), (2, 6), (12, 20)], 1, 15) == \
        [(1, 9), (12, 15)]


def test_busy_time_is_the_mean_over_the_cells_chips():
    """A second chip busy 1.0 of the 7.0 ms window: the mean of both."""
    two = Trace(ops=TRACE.ops, modules=TRACE.modules, spans=TRACE.spans,
                other_ops=[[Event("fusion.1", 500_000, 1_000_000),
                            Event("fusion.2", 2_000_000, 500_000),
                            Event("fusion.3", 3_000_000, 500_000)]])
    one, r = reduce(TRACE), reduce(two)
    assert r.busy_ns == (one.busy_ns + 500_000 + 500_000 + 500_000) / 2
    assert (r.window_ns, r.quanta, r.idle_by_span) == \
        (one.window_ns, one.quanta, one.idle_by_span)


def _pb(*fields):
    """Protobuf wire bytes of (field, value) pairs: int -> varint, bytes
    or str -> length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_read_xplane_takes_the_cells_chips(tmp_path):
    """An XSpace with a host plane and three TPU planes: chip 0's ops and
    programs, the spans, and the ops of the further chips the cell uses."""
    def plane(pid, name, lines, names):
        meta = [(4, _pb((1, mid), (2, _pb((1, mid), (2, n)))))
                for mid, n in names.items()]
        return (1, _pb((1, pid), (2, name), *lines, *meta))

    def line(name, ts_ns, *events):       # events: (metadata, start, dur) ns
        return (3, _pb((2, name), (3, ts_ns), *(
            (4, _pb((1, m), (2, 1000 * s), (3, 1000 * d)))
            for m, s, d in events)))

    host = plane(1, "/host:CPU", [line("main", 1_000, (1, 0, 900))],
                 {1: "perfbench.quantum.0"})
    tpus = [plane(2 + c, f"/device:TPU:{c}",
                  [line("XLA Ops", 1_000, (1, 10 * c, 100 + c)),
                   line("XLA Modules", 1_000, (2, 0, 300))],
                  {1: f"fusion.{c}", 2: "jit_fused_decode_steps(1)"})
            for c in range(3)]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(_pb(host, *tpus))
    tr = read_xplane(str(tmp_path), chips=2)
    assert [(e.name, e.start, e.dur) for e in tr.ops] == \
        [("fusion.0", 1_000, 100)]
    assert [e.name for e in tr.modules] == ["jit_fused_decode_steps(1)"]
    assert [e.name for e in tr.spans] == ["perfbench.quantum.0"]
    assert [[(e.name, e.start, e.dur) for e in ops] for ops in tr.other_ops] \
        == [[("fusion.1", 1_010, 101)]]
    assert read_xplane(str(tmp_path)).other_ops == []
    assert reduce(tr).busy_ns == (100 + 101) / 2
