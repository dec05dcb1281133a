"""The benchmark's FLOP and byte counters against values worked out by
hand from the published shapes, for both configurations."""
from __future__ import annotations

import json

import pytest

import perfbench_tiny as tiny
from harness import counts


def _cfg(name):
    return json.loads((tiny.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def test_minicpm_2b():
    c = _cfg("minicpm-2b")
    # per layer: 4 d^2 attention (MHA 36 x 64 = 2304) + 3 d ff + 2 norms
    assert counts.layer_params(c) == 4 * 2304 ** 2 + 3 * 2304 * 5760 + 2 * 2304
    assert counts.layer_params(c) == 61_051_392
    # 40 layers + tied 122,753-row embedding + final norm
    assert counts.parameters(c) == 2_724_880_896
    assert counts.kv_bytes_per_token(c) == 40 * 2 * 36 * 64 * 2 == 368_640
    assert counts.decode_weight_bytes(c) == 2 * 2_724_880_896
    # matmuls of one token through 40 layers and the head
    assert counts.matmul_flops(c, True) == 2 * (40 * 61_046_784
                                               + 2304 * 122_753)
    step = counts.decode_step(c, [999])
    assert step["flops"] == 5_449_388_544 + 4 * 40 * 36 * 64 * 1000
    assert step["bytes"] == 5_449_761_792 + 1000 * 368_640


def test_internlm2_20b_pp8():
    c = _cfg("internlm2-20b-pp8")
    # q 48 x 128 = 6144, kv 8 x 128 = 1024
    assert counts.layer_params(c) == (2 * 6144 * 6144 + 2 * 6144 * 1024
                                      + 3 * 6144 * 16384 + 2 * 6144)
    assert counts.layer_params(c) == 390_082_560
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
