"""The reduction of the program's own instrumentation, on small traces
written here (ns): self time of nested device ops, device time by model
scope within each program, idle time by the innermost ``repro.*`` span,
the readers built on them, and a real CPU trace of the program's spans."""
from __future__ import annotations

import asyncio
import dataclasses
import json

import pytest

import perfbench_tiny as tiny
from harness import program_readings as PR
from harness import program_trace as PT
from harness.readings import Context
from harness.serve import Quantum
from harness.trace import SMALL, Event, Trace, reduce as bench_reduce

DECODE = "jit_fused_decode_steps(8)"
PREFILL = "jit__chunk_prefill_fn(1)"


def _span(name, start, dur, **args):
    return PT.Span(name, start, dur, tuple(args.items()))


# One benchmark quantum 0..10 ms holding one program quantum 0.1..9.9 ms
# and its phase spans: admit, a prefill launch whose program runs 1..3 ms
# (two fusions 5 us apart), a decode launch and the fetch during which its
# program runs 4..8 ms as one while loop of two fusions; then the server's
# pump 9.9..10 ms.
TRACE = PT.ProgramTrace(
    bench_quanta=[Event("perfbench.quantum.0", 0, 10_000_000)],
    spans=[_span("repro.quantum", 100_000, 9_800_000, step_num=1,
                 reserved_pages=40, mapped_pages=10),
           _span("repro.admit", 200_000, 300_000, rids="3 4"),
           _span("repro.prefill.launch", 600_000, 2_400_000, rids="3"),
           _span("repro.decode.launch", 3_500_000, 500_000),
           _span("repro.decode.fetch", 4_000_000, 5_000_000),
           _span("repro.server.pump", 9_900_000, 100_000)],
    modules=[Event(PREFILL, 1_000_000, 2_000_000),
             Event(DECODE, 4_000_000, 4_000_000)],
    ops=[PT.Op("%fusion.1 = bf16[8] fusion(x)", 1_000_000, 1_495_000,
               "attn_prefill"),
         PT.Op("%fusion.2 = bf16[8] fusion(x)", 2_500_000, 500_000, "mlp"),
         # the loop and the two fusions inside it, on the same line
         PT.Op("%while.5 = (s32[]) while(t)", 4_000_000, 4_000_000,
               PT.UNSCOPED),
         PT.Op("%fusion.7 = bf16[8] fusion(y)", 4_100_000, 1_900_000,
               "attn_decode"),
         # added by the compiler without metadata: takes its operand's
         PT.Op("%convert.9 = f32[8] convert(%fusion.7)", 6_000_000,
               1_500_000, PT.UNSCOPED)])


@pytest.fixture(scope="module")
def red():
    return PT.reduce(TRACE)


def test_nested_ops_count_each_nanosecond_once(red):
    self_ns = {op: ns for (_, op, _), ns in red.op_self_ns.items()}
    # the loop keeps what its two fusions leave: 4.0-4.1 and 7.5-8.0 ms
    assert self_ns == {"fusion.1": 1_495_000, "fusion.2": 500_000,
                       "while.5": 600_000, "fusion.7": 1_900_000,
                       "convert.9": 1_500_000}
    assert red.busy_ns == sum(self_ns.values()) == 1_995_000 + 4_000_000
    # the same union the benchmark's own reduction reads
    bench = bench_reduce(Trace(ops=[Event(o.name, o.start, o.dur)
                                    for o in TRACE.ops],
                               modules=TRACE.modules,
                               spans=TRACE.bench_quanta))
    assert red.busy_ns == bench.busy_ns
    assert red.window_ns == bench.window_ns


def test_scopes_and_the_unscoped_rest_make_each_programs_time(red):
    assert red.scope_ns == {
        PREFILL: {"attn_prefill": 1_495_000, "mlp": 500_000},
        DECODE: {PT.UNSCOPED: 600_000, "attn_decode": 3_400_000}}
    for prog, by in red.scope_ns.items():
        assert sum(by.values()) == red.program_busy_ns[prog]
    assert red.program_busy_ns[DECODE] == 4_000_000
    assert PT.scope_time(red, "fused_decode_steps", "attn_decode") == \
        pytest.approx(3.4e-3)
    assert PT.scope_time(red, "chunk_prefill", "attn_decode") == 0.0


def test_idle_is_cut_by_the_innermost_repro_span(red):
    want = {PT.OUTSIDE: 100_000,                      # 0-0.1
            # in the quantum and no phase: 0.1-0.2, 0.5-0.6, 3.0-3.5,
            # 9.0-9.9
            "repro.quantum": 100_000 + 100_000 + 500_000 + 900_000,
            "repro.admit": 300_000,                   # 0.2-0.5
            "repro.prefill.launch": 400_000,          # 0.6-1.0
            "repro.decode.launch": 500_000,           # 3.5-4.0
            "repro.decode.fetch": 1_000_000,          # 8.0-9.0
            "repro.server.pump": 100_000,             # 9.9-10.0
            SMALL: 5_000}                             # 2.495-2.5
    assert red.idle_ns == want
    assert sum(red.idle_ns.values()) == red.window_ns - red.busy_ns
    assert PT.stall_ns(red) == 3_900_000


def test_scope_of_takes_the_innermost_model_scope():
    assert PT.scope_of("jit(f)/while/body/mlp/dot_general") == "mlp"
    assert PT.scope_of("jit(f)/lm_head/x/attn_proj/dot") == "attn_proj"
    assert PT.scope_of("jit(f)/while/body/attn_decode/gather:") == \
        "attn_decode"
    assert PT.scope_of("jit(f)/while/body/copy") == PT.UNSCOPED
    assert PT.scope_of("") == PT.UNSCOPED


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


def test_op_names_read_the_event_metadata(tmp_path):
    """The scope lives in the ``tf_op`` stat of each op's event metadata,
    per program; the decoder reads it from the file's wire format."""
    stat_meta = [(5, _pb((1, k), (2, _pb((1, k), (2, name)))))
                 for k, name in ((1, "program_id"), (2, "tf_op"),
                                 (3, "hlo_category"), (4, "dot"))]

    def op(mid, name, program, tf_op):
        return (4, _pb((1, mid), (2, _pb(
            (1, mid), (2, name), (5, _pb((1, 1), (3, program))),
            (5, _pb((1, 3), (5, "loop fusion"))),
            (5, _pb((1, 2), (5, tf_op)))))))
    ops = [op(7, "%fusion.1 = f32[2] fusion(%p)", 11,
              "jit(f)/attn_decode/gather:"),
           op(8, "%fusion.1 = f32[2] fusion(%p)", 12, "jit(g)/mlp/dot:"),
           (4, _pb((1, 9), (2, _pb((1, 9), (2, "%copy.2 = f32[2] copy()")))))]
    line = (3, _pb((2, "XLA Ops"), (4, _pb((1, 7), (2, 5), (3, 9)))))
    host = (1, _pb((2, "/host:CPU"), *stat_meta, ops[0]))
    tpu = (1, _pb((1, 3), (2, "/device:TPU:0"), line, *ops, *stat_meta))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(host, tpu))
    assert PT.op_names(str(path), "/device:TPU:0") == {
        ("11", "%fusion.1 = f32[2] fusion(%p)"): "jit(f)/attn_decode/gather:",
        ("12", "%fusion.1 = f32[2] fusion(%p)"): "jit(g)/mlp/dot:"}
    assert PT.op_names(str(path), "/device:TPU:1") == {}
    assert PT._program_id("jit_fused_decode_steps(151754)") == "151754"


def test_an_op_without_metadata_takes_its_operands_scope():
    prog = Event(DECODE, 0, 100)
    ops = [PT.Op("%fusion.1 = f32[2] fusion(%p.0)", 0, 10, "mlp"),
           PT.Op("%fusion.2 = f32[2] fusion(%p.0)", 10, 10, "attn_decode"),
           PT.Op("%copy.3 = f32[2] copy(%fusion.1)", 20, 10, PT.UNSCOPED),
           PT.Op("%add.4 = f32[2] add(%fusion.1, %fusion.2)", 30, 10,
                 PT.UNSCOPED),
           PT.Op("%copy.5 = f32[2] copy(%copy.3)", 40, 10, PT.UNSCOPED),
           PT.Op("%copy.6 = f32[2] copy(%p.1)", 50, 10, PT.UNSCOPED)]
    got = {PT._op_name(o.name): o.scope
           for o in PT._inherit(ops, lambda op: prog.name)}
    assert got == {"fusion.1": "mlp", "fusion.2": "attn_decode",
                   "copy.3": "mlp", "add.4": PT.UNSCOPED,   # they disagree
                   "copy.5": "mlp", "copy.6": PT.UNSCOPED}


def test_a_trace_without_program_quanta_or_ops_reads_nothing():
    empty = dataclasses.replace(TRACE, bench_quanta=[], spans=[])
    assert PT.reduce(empty) is None
    assert PT.reduce(dataclasses.replace(TRACE, ops=[])) is None


# ---------------------------------------------------------------- readers

class _Run:
    trace_dir = "unused"
    quanta = [Quantum(index=0, prefill=[(0, 256)],
                      decode=[[999, 4000]] * 8)]


def _config(name):
    return json.loads((tiny.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def _ctx(red):
    cfg = _config("minicpm-2b")
    reduced = bench_reduce(Trace(ops=[Event(o.name, o.start, o.dur)
                                      for o in TRACE.ops],
                                 modules=TRACE.modules,
                                 spans=TRACE.bench_quanta))
    ctx = Context(cfg=cfg, run=_Run(), setup_s=0.0,
                  peak={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
                  reduced=reduced)
    ctx._program_reduction = red
    return ctx


def test_readers(red):
    ctx = _ctx(red)
    assert PR.attn_decode_ms(ctx) == pytest.approx(3.4 / 8)
    assert PR.attn_prefill_ms(ctx) == pytest.approx(1.495)
    assert PR.kv_mapped_share(ctx) == pytest.approx(25.0)
    assert PR.host_stall_ms(ctx) == pytest.approx(3.9)     # one quantum
    step = PR.decode_attention(ctx.cfg, [999, 4000])
    least = 8 * max(step["flops"] / 197e12, step["bytes"] / 819e9)
    assert PR.attn_decode_roofline(ctx) == pytest.approx(
        100 * least / 3.4e-3)


def test_decode_attention_counts_by_hand():
    c = PR.decode_attention(_config("internlm2-20b-pp8"), [0, 99])
    # 4 FLOPs per head dim, q head and key, 6 layers x 48 heads x 128
    assert c["flops"] == 4 * 6 * 48 * 128 * (1 + 100)
    assert c["bytes"] == 24_576 * (1 + 100)


def test_readers_find_nothing_in_a_program_without_instrumentation():
    bare = dataclasses.replace(
        TRACE, spans=[],
        ops=[dataclasses.replace(o, scope=PT.UNSCOPED) for o in TRACE.ops])
    ctx = _ctx(PT.reduce(bare))
    for reader in (PR.attn_decode_ms, PR.attn_decode_roofline,
                   PR.attn_prefill_ms, PR.kv_mapped_share,
                   PR.host_stall_ms):
        assert reader(ctx) is None, reader.__name__


# --------------------------------------------------- the program's spans

def test_program_spans_read_from_a_real_trace(tmp_path):
    """A CPU trace of a tiny engine: every program span is read with its
    arguments (the CPU trace has no device plane, so no ops)."""
    import jax
    from repro.models import Model, ModelConfig
    from repro.models.config import repeat_pattern
    from repro.serving import (AsyncServingServer, EngineConfig, Request,
                               ServingEngine)
    cfg = ModelConfig(
        name="tiny-pt", family="dense", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab=128, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 1), vocab_pad_multiple=8)
    m = Model(cfg)
    eng = ServingEngine(m, m.init(jax.random.PRNGKey(0)), EngineConfig(
        max_batch=2, max_len=32, sync_every=2, paged=True, page_size=4,
        prefill_chunk=8, prefix_sharing=True))
    server = AsyncServingServer(eng)

    async def go():
        for rid in range(3):
            await server.submit(Request(rid=rid, prompt=[rid + 1] * 9,
                                        max_new_tokens=3))
        await server.drain()
    with jax.profiler.trace(str(tmp_path)):
        asyncio.run(go())
    pt = PT.read(str(tmp_path))
    assert not pt.ops and not pt.bench_quanta
    quanta = [s for s in pt.spans if s.name == PT.QUANTUM]
    assert len(quanta) == eng._quantum
    args = [dict(q.args) for q in quanta]
    assert sum(a["reserved_pages"] for a in args) == \
        eng.stats()["reserved_page_quanta"]
    # a lone rid comes back as a number
    admitted = " ".join(str(dict(s.args).get("rids", "")) for s in pt.spans
                        if s.name == "repro.admit").split()
    assert sorted(map(int, admitted)) == [0, 1, 2]
