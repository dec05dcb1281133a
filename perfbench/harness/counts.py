"""Operations and bytes the algorithm needs, from a configuration's shapes.

These are the benchmark's own functions of the configuration file, not the
program's cost model or the compiler's HLO counts, so no program change
moves them. A token at position p (0-based) attends p + 1 keys. Bytes are
of the served dtype; the vocabulary is the true one, not the padded rows.
"""
from __future__ import annotations

from typing import Dict, Iterable

from harness.model import dims


def _bytes(cfg: Dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def layer_params(cfg: Dict) -> int:
    m = dims(cfg)
    q, kv = m["hq"] * m["hd"], m["hkv"] * m["hd"]
    return (m["d"] * q + 2 * m["d"] * kv + q * m["d"] + 3 * m["d"] * m["ff"]
            + 2 * m["d"])


def parameters(cfg: Dict) -> int:
    """Parameters of the configuration as published (true vocabulary)."""
    m = dims(cfg)
    head = 0 if m["tied"] else m["d"] * m["vocab"]
    return m["L"] * layer_params(cfg) + m["vocab"] * m["d"] + head + m["d"]


def kv_bytes_per_token(cfg: Dict) -> int:
    m = dims(cfg)
    return m["L"] * 2 * m["hkv"] * m["hd"] * _bytes(cfg)


def decode_weight_bytes(cfg: Dict) -> int:
    """Weights one decode step has to read: every layer, the final norm and
    the output head (the embedding rows looked up are negligible)."""
    m = dims(cfg)
    return (m["L"] * layer_params(cfg) + m["d"] + m["d"] * m["vocab"]) \
        * _bytes(cfg)


def matmul_flops(cfg: Dict, head: bool) -> int:
    m = dims(cfg)
    return 2 * (m["L"] * (layer_params(cfg) - 2 * m["d"])
                + (m["d"] * m["vocab"] if head else 0))


def attention_flops(cfg: Dict, keys: int) -> int:
    """Scores and the weighted sum of values for one query over ``keys``."""
    m = dims(cfg)
    return 4 * m["L"] * m["hq"] * m["hd"] * keys


def decode_step(cfg: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """One fused decode micro-step over the live slots at ``positions``:
    the FLOPs, and the least bytes (weights once, the live KV read, one
    KV row written per slot)."""
    positions = list(positions)
    kvb = kv_bytes_per_token(cfg)
    flops = sum(matmul_flops(cfg, True) + attention_flops(cfg, p + 1)
                for p in positions)
    nbytes = decode_weight_bytes(cfg) + sum(p * kvb + kvb for p in positions)
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_chunk(cfg: Dict, start: int, stop: int) -> float:
    """FLOPs of a prompt chunk at positions [start, stop): every token
    through the layers, the head once for the last token's logits."""
    m = dims(cfg)
    n = stop - start
    keys = n * (start + stop + 1) // 2          # sum of p + 1 over the chunk
    return float(n * matmul_flops(cfg, False)
                 + 4 * m["L"] * m["hq"] * m["hd"] * keys
                 + 2 * m["d"] * m["vocab"])


def least_time(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])


def memory(cfg: Dict) -> Dict[str, int]:
    """The configuration's memory arithmetic as served: weights as stored
    (padded vocabulary rows included), KV per token, and the page pool."""
    m = dims(cfg)
    eng = cfg["engine"]
    stored = (m["L"] * layer_params(cfg) + m["vocab_pad"] * m["d"]
              + (0 if m["tied"] else m["d"] * m["vocab_pad"]) + m["d"])
    kvb = kv_bytes_per_token(cfg)
    return {"parameters": parameters(cfg),
            "weight_bytes": stored * _bytes(cfg),
            "kv_bytes_per_token": kvb,
            "pool_bytes": (eng["num_pages"] + 1) * eng["page_size"] * kvb}
