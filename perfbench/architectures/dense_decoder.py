"""A dense decoder (Llama-style block) as the benchmark sees it: its
tensors, the program's configuration and parameter tree over them, and
the operations and bytes its work needs.

The architecture module's contract (``harness.model`` and
``harness.counts`` load ``architectures/<architecture>.py`` by the
configuration file's ``architecture`` and call these by name):

- ``canonical_shapes(cfg)``: ``{tensor name: shape}`` of every weight the
  benchmark makes. ``harness.model.make_weights`` draws tensor number i of
  the names in sorted order from ``fold_in(key, i)``, so a name fixes its
  draw; the reference reads the same names.
- ``init_std(cfg, name, shape)``: the standard deviation of a tensor's
  normal draw, or None for a norm scale of ones.
- ``program_config(cfg)``: the program's ``ModelConfig``.
- ``program_params(weights)``: the program's parameter tree over the same
  arrays, without a copy (``harness.model`` checks it against the
  program's own parameter shapes).
- ``dims(cfg)``: the shape numbers the functions below use.
- ``parameters``, ``kv_bytes_per_token``, ``decode_step(cfg, positions)``
  (FLOPs and least bytes of one decode micro-step over live slots at
  those positions), ``prefill_chunk(cfg, start, stop)`` (FLOPs of a
  prompt chunk), ``decode_attention(cfg, positions)`` (attention's own
  FLOPs and bytes in one decode micro-step) and ``memory(cfg)`` (weights
  as stored, KV per token, page pool).

Every count is of the chip's own share of the deployment the
configuration file states: the layers, experts and vocabulary rows this
chip holds. A token at position p (0-based) attends p + 1 keys. Bytes are
of the served dtype; the vocabulary is the true one, not the padded rows.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional

from repro.models import ModelConfig

NORMS = ("ln1", "ln2", "final_norm")


def padded_vocab(cfg: Dict) -> int:
    m = cfg["assumed"]["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def dims(cfg: Dict) -> Dict[str, int]:
    """The shape numbers every counter needs."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": cfg["num_key_value_heads"], "hd": d // hq,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "vocab_pad": padded_vocab(cfg),
            "tied": bool(cfg["tie_word_embeddings"])}


# ------------------------------------------------------------- weights
def canonical_shapes(cfg: Dict) -> Dict[str, tuple]:
    m = dims(cfg)
    L, d, ff = m["L"], m["d"], m["ff"]
    q, kv = m["hq"] * m["hd"], m["hkv"] * m["hd"]
    shapes = {"embed": (m["vocab_pad"], d), "final_norm": (d,),
              "ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "ln2": (L, d),
              "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if not m["tied"]:
        shapes["lm_head"] = (d, m["vocab_pad"])
    return shapes


def init_std(cfg: Dict, name: str, shape: tuple) -> Optional[float]:
    """Norms 1, embedding N(0, embedding_std), each projection and the
    output head N(0, 1/fan_in). The embedding's spread is an assumption
    of the configuration file: with tied embeddings and a large
    scale_emb, a wide one makes a random model repeat its last token,
    whatever the context."""
    if name in NORMS:
        return None
    if name == "embed":
        return float(cfg["assumed"]["embedding_std"])
    return 1.0 / math.sqrt(shape[-2])


def program_config(cfg: Dict) -> ModelConfig:
    L = cfg["num_hidden_layers"]
    depth = cfg.get("scale_depth")
    base = cfg.get("dim_model_base")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=L,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        block_pattern=("dense",) * L, norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"],
        scale_emb=float(cfg.get("scale_emb", 1.0)),
        residual_scale=(depth / math.sqrt(L)) if depth else 1.0,
        logit_scale=(base / cfg["hidden_size"]) if base else 1.0,
        pad_heads_to_multiple=cfg["assumed"]["pad_heads_to_multiple"],
        vocab_pad_multiple=cfg["assumed"]["vocab_pad_multiple"])


def program_params(weights: Dict) -> Dict:
    layer = {"ln1": {"scale": weights["ln1"]},
             "attn": {k: weights[k] for k in ("wq", "wk", "wv", "wo")},
             "ln2": {"scale": weights["ln2"]},
             "mlp": {k: weights[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": {"tok": weights["embed"]}, "prefix": (),
            "unit": {"0": layer},
            "final_norm": {"scale": weights["final_norm"]},
            "lm_head": ({} if "lm_head" not in weights
                        else {"out": weights["lm_head"]})}


# -------------------------------------------------- operations and bytes
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


def decode_attention(cfg: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """Attention's own work in one decode micro-step over the live slots at
    ``positions``: scores and the value sum over p + 1 keys, p rows of K
    and V read and one written per slot."""
    positions = list(positions)
    kvb = kv_bytes_per_token(cfg)
    return {"flops": float(sum(attention_flops(cfg, p + 1)
                               for p in positions)),
            "bytes": float(sum((p + 1) * kvb for p in positions))}


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
