"""The benchmark's FLOP and byte counters against values worked out by
hand from the published shapes, for both configurations."""
from __future__ import annotations

import json

import pytest

import perfbench_tiny as tiny
from harness import counts, spec

# the dense block's own helpers, outside the architecture contract
dense = spec.load_module(
    tiny.BENCH_DIR / "architectures" / "dense_decoder.py",
    "perfbench_test_dense_decoder")


def _cfg(name):
    return json.loads((tiny.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def test_minicpm_2b():
    c = _cfg("minicpm-2b")
    # per layer: 4 d^2 attention (MHA 36 x 64 = 2304) + 3 d ff + 2 norms
    assert dense.layer_params(c) == 4 * 2304 ** 2 + 3 * 2304 * 5760 + 2 * 2304
    assert dense.layer_params(c) == 61_051_392
    # 40 layers + tied 122,753-row embedding + final norm
    assert counts.parameters(c) == 2_724_880_896
    assert counts.kv_bytes_per_token(c) == 40 * 2 * 36 * 64 * 2 == 368_640
    assert dense.decode_weight_bytes(c) == 2 * 2_724_880_896
    # matmuls of one token through 40 layers and the head
    assert dense.matmul_flops(c, True) == 2 * (40 * 61_046_784
                                               + 2304 * 122_753)
    step = counts.decode_step(c, [999])
    assert step["flops"] == 5_449_388_544 + 4 * 40 * 36 * 64 * 1000
    assert step["bytes"] == 5_449_761_792 + 1000 * 368_640


def test_internlm2_20b_pp8():
    c = _cfg("internlm2-20b-pp8")
    # q 48 x 128 = 6144, kv 8 x 128 = 1024
    assert dense.layer_params(c) == (2 * 6144 * 6144 + 2 * 6144 * 1024
                                      + 3 * 6144 * 16384 + 2 * 6144)
    assert dense.layer_params(c) == 390_082_560
    # 6 layers + untied embedding and head + final norm
    assert counts.parameters(c) == 3_477_682_176
    assert counts.kv_bytes_per_token(c) == 6 * 2 * 8 * 128 * 2 == 24_576
    # a 256-token first chunk: 32,896 query-key pairs, the head once
    assert counts.prefill_chunk(c, 0, 256) == (256 * 2 * 6 * 390_070_272
                                               + 4 * 6 * 48 * 128 * 32_896
                                               + 2 * 6144 * 92_544)
    assert counts.prefill_chunk(c, 0, 256) == 1_204_283_768_832
    # chunks add up: [0, 256) + [256, 512) == [0, 512) plus one more head
    head = 2 * 6144 * 92_544
    assert counts.prefill_chunk(c, 0, 256) + counts.prefill_chunk(
        c, 256, 512) == counts.prefill_chunk(c, 0, 512) + head


@pytest.mark.parametrize("name", ["minicpm-2b", "internlm2-20b-pp8"])
def test_memory_block_matches_the_arithmetic(name):
    c = _cfg(name)
    mem = counts.memory(c)
    for key in ("parameters", "weight_bytes", "kv_bytes_per_token",
                "pool_bytes"):
        assert c["memory"][key] == mem[key], key


def test_least_time_takes_the_binding_roof():
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000.0, 50.0, peak) == 10.0
    assert counts.least_time(100.0, 50.0, peak) == 5.0


# Readings of both configuration files taken from the harness before the
# architecture modules existed: moving the arithmetic behind
# ``architectures/dense_decoder.py`` must leave each of them as it was.
PINNED = {
    "minicpm-2b": {
        "memory": {"parameters": 2724880896, "weight_bytes": 5450347008,
                   "kv_bytes_per_token": 368640, "pool_bytes": 1138360320},
        "steps": {(0,): (5449757184.0, 5450130432.0),
                  (999,): (5818028544.0, 5818401792.0),
                  (0, 4095): (12409095168.0, 6960079872.0),
                  (17, 2500, 3000): (18383058432.0, 7484654592.0)},
        "chunks": {(0, 256): 1262930563584.0, (256, 512): 1287089754624.0,
                   (3840, 4096): 1625318429184.0, (1000, 1001): 5818397184.0},
        "attention": (1512161280.0, 1512161280.0),
    },
    "internlm2-20b-pp8": {
        "memory": {"parameters": 3477682176, "weight_bytes": 6958510080,
                   "kv_bytes_per_token": 24576, "pool_bytes": 1208352768},
        "steps": {(0,): (5818171392.0, 5818208256.0),
                  (999,): (5965479936.0, 5842759680.0),
                  (0, 4095): (12240175104.0, 5918871552.0),
                  (17, 2500, 3000): (18268028928.0, 5953843200.0)},
        "chunks": {(0, 256): 1204283768832.0, (256, 512): 1213947445248.0,
                   (3840, 4096): 1349238915072.0, (1000, 1001): 5965627392.0},
        "attention": (604864512.0, 100810752.0),
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_are_pinned_to_the_readings_before_the_move(name):
    c, want = _cfg(name), PINNED[name]
    assert counts.memory(c) == want["memory"]
    assert counts.kv_bytes_per_token(c) == want["memory"]["kv_bytes_per_token"]
    for positions, (flops, nbytes) in want["steps"].items():
        assert counts.decode_step(c, positions) == {"flops": flops,
                                                    "bytes": nbytes}
    for (start, stop), flops in want["chunks"].items():
        assert counts.prefill_chunk(c, start, stop) == flops
    flops, nbytes = want["attention"]
    assert counts.decode_attention(c, [0, 99, 4000]) == {"flops": flops,
                                                         "bytes": nbytes}
