"""DeepSeek-V2 (MLA in every layer, one leading dense layer, then routed and
shared experts; YaRN rope) as the benchmark sees it. The contract is
``architectures/dense_decoder.py``'s.

The configuration file holds the published ``config.json`` of
deepseek-ai/DeepSeek-V2-Lite with ``n_routed_experts`` cut to the experts
this chip holds (its share of the stated expert-parallel deployment); the
router's width is the published count, ``published["n_routed_experts"]``.
Tensors carry the published modules' names; layer 0 (dense) is ``l0_*``
and the expert layers are stacked, so the program's tree is built from
the same arrays without a copy. The latent's up-projection is two tensors,
``k_b_proj`` and ``v_b_proj`` (the published ``kv_b_proj`` interleaves
them per head; random weights cannot tell).

What the counts contain:

- ``decode_step``: every weight this chip reads once per micro-step,
  except the routed experts, which it counts at zero FLOPs and zero bytes
  (no held expert need receive a token: zero is the least a step can
  need); per live slot the matmuls, the absorbed attention over p + 1
  latent rows (scores 576 wide, values 512), and the latent rows read
  and written.
- ``prefill_chunk``: attention in the reference's expanded form (each
  token's latent expanded once by ``k_b_proj`` and ``v_b_proj``, scores
  over 192 and values over 128 per head), and the routed experts at their
  expected top_k x held / routed pairs a token (6 x 8 / 64).
- ``decode_attention``: per live slot at position p and per layer, the
  absorption products (q_nope by k_b_proj, the latent value sum by
  v_b_proj), scores over p + 1 rows of 576 and values over p + 1 rows of
  512; bytes of p + 1 latent rows read and one written.
- ``moe_decode`` (read by ``harness/moe_readings.py``): the held experts'
  work in traced decode micro-steps, from the program's counters.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional

from repro.models import MLAConfig, MoEConfig, ModelConfig, YarnScaling

ATTN = ("ln1", "q_proj", "kv_a_proj", "kv_a_norm", "k_b_proj", "v_b_proj",
        "o_proj", "ln2")
NORMS = ("ln1", "kv_a_norm", "ln2")


def padded_vocab(cfg: Dict) -> int:
    m = cfg["assumed"]["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def dims(cfg: Dict) -> Dict[str, int]:
    """The shape numbers every counter needs."""
    moe_ff = cfg["moe_intermediate_size"]
    return {"L": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"], "d": cfg["hidden_size"],
            "hq": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "kvr": cfg["kv_lora_rank"],
            "ff": cfg["intermediate_size"], "moe_ff": moe_ff,
            "held": cfg["n_routed_experts"],
            "routed": cfg["published"]["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "shared_ff": moe_ff * cfg["n_shared_experts"],
            "vocab": cfg["vocab_size"], "vocab_pad": padded_vocab(cfg)}


# ------------------------------------------------------------- weights
def _attn_shapes(m: Dict) -> Dict[str, tuple]:
    d, h = m["d"], m["hq"]
    return {"ln1": (d,), "q_proj": (d, h * (m["nope"] + m["rope"])),
            "kv_a_proj": (d, m["kvr"] + m["rope"]), "kv_a_norm": (m["kvr"],),
            "k_b_proj": (m["kvr"], h * m["nope"]),
            "v_b_proj": (m["kvr"], h * m["vd"]),
            "o_proj": (h * m["vd"], d), "ln2": (d,)}


def canonical_shapes(cfg: Dict) -> Dict[str, tuple]:
    m = dims(cfg)
    if m["dense"] != 1:
        raise ValueError("the program's layer grouping takes one leading "
                         "dense layer")
    d, n = m["d"], m["L"] - 1
    attn = _attn_shapes(m)
    shapes = {"embed": (m["vocab_pad"], d), "final_norm": (d,),
              "lm_head": (d, m["vocab_pad"])}
    shapes.update({"l0_" + k: v for k, v in attn.items()})
    shapes.update({"l0_gate_proj": (d, m["ff"]), "l0_up_proj": (d, m["ff"]),
                   "l0_down_proj": (m["ff"], d)})
    shapes.update({k: (n,) + v for k, v in attn.items()})
    e, f, s = m["held"], m["moe_ff"], m["shared_ff"]
    shapes.update({"router": (n, d, m["routed"]),
                   "experts_gate": (n, e, d, f), "experts_up": (n, e, d, f),
                   "experts_down": (n, e, f, d),
                   "shared_gate": (n, d, s), "shared_up": (n, d, s),
                   "shared_down": (n, s, d)})
    return shapes


def init_std(cfg: Dict, name: str, shape: tuple) -> Optional[float]:
    """Norms 1, embedding N(0, embedding_std), every projection, expert,
    router and the output head N(0, 1/fan_in)."""
    if name == "final_norm" or name.removeprefix("l0_") in NORMS:
        return None
    if name == "embed":
        return float(cfg["assumed"]["embedding_std"])
    return 1.0 / math.sqrt(shape[-2])


# the published router and rope the program serves; any other value of
# these keys is a model the program does not implement
SERVED = {"scoring_func": "softmax", "topk_method": "greedy",
          "norm_topk_prob": False, "routed_scaling_factor": 1}


def program_config(cfg: Dict) -> ModelConfig:
    m = dims(cfg)
    y = cfg["rope_scaling"]
    for key, value in SERVED.items():
        if cfg[key] != value:
            raise ValueError(f"{cfg['name']}: {key} {cfg[key]!r}; the "
                             f"program serves {value!r} only")
    if y["type"] != "yarn" or y["mscale"] != y["mscale_all_dim"]:
        raise ValueError(f"{cfg['name']}: the program serves YaRN with "
                         "mscale == mscale_all_dim only")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["hq"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=m["ff"], vocab=m["vocab"],
        block_pattern=("mla",) + ("mla_moe",) * (m["L"] - 1),
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"],
                      kv_lora_rank=m["kvr"], qk_nope_head_dim=m["nope"],
                      qk_rope_head_dim=m["rope"], v_head_dim=m["vd"]),
        moe=MoEConfig(n_experts=m["routed"], top_k=m["top_k"],
                      d_ff_expert=m["moe_ff"],
                      n_shared_experts=cfg["n_shared_experts"],
                      dropless=True, n_held_experts=m["held"]),
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(y["factor"]),
            original_max_position_embeddings=y[
                "original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"],
        pad_heads_to_multiple=cfg["assumed"]["pad_heads_to_multiple"],
        vocab_pad_multiple=cfg["assumed"]["vocab_pad_multiple"])


def _layer(w: Dict, pre: str) -> Dict:
    return {"ln1": {"scale": w[pre + "ln1"]},
            "attn": {"w_q": w[pre + "q_proj"], "w_dkv": w[pre + "kv_a_proj"],
                     "kv_norm": {"scale": w[pre + "kv_a_norm"]},
                     "w_uk": w[pre + "k_b_proj"], "w_uv": w[pre + "v_b_proj"],
                     "wo": w[pre + "o_proj"]},
            "ln2": {"scale": w[pre + "ln2"]}}


def program_params(weights: Dict) -> Dict:
    dense = _layer(weights, "l0_")
    dense["mlp"] = {"w_gate": weights["l0_gate_proj"],
                    "w_up": weights["l0_up_proj"],
                    "w_down": weights["l0_down_proj"]}
    moe = _layer(weights, "")
    moe["moe"] = {"router": weights["router"],
                  "experts_gate": weights["experts_gate"],
                  "experts_up": weights["experts_up"],
                  "experts_down": weights["experts_down"],
                  "shared": {"w_gate": weights["shared_gate"],
                             "w_up": weights["shared_up"],
                             "w_down": weights["shared_down"]}}
    return {"embed": {"tok": weights["embed"]}, "prefix": (dense,),
            "unit": {"0": moe},
            "final_norm": {"scale": weights["final_norm"]},
            "lm_head": {"out": weights["lm_head"]}}


# -------------------------------------------------- operations and bytes
def _bytes(cfg: Dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def _count(shapes: Dict[str, tuple]) -> int:
    return sum(math.prod(s) for s in shapes.values())


def attn_params(cfg: Dict) -> int:
    """One layer's attention and its two norms."""
    return _count(_attn_shapes(dims(cfg)))


def matrix_params(cfg: Dict) -> Dict[str, int]:
    """Parameters in the matrices a token multiplies, by part, per layer:
    attention (the absorption's k_b_proj and v_b_proj included), the dense
    layer's MLP, an expert layer's router and shared experts, one routed
    expert."""
    m = dims(cfg)
    a = _attn_shapes(m)
    return {"attn": _count({k: v for k, v in a.items() if len(v) == 2}),
            "dense_mlp": 3 * m["d"] * m["ff"],
            "router": m["d"] * m["routed"],
            "shared": 3 * m["d"] * m["shared_ff"],
            "expert": 3 * m["d"] * m["moe_ff"]}


def parameters(cfg: Dict) -> int:
    """Parameters this chip holds, at the true vocabulary: every layer's
    attention, the dense layer, each expert layer's router, held experts
    and shared experts, the embedding, the head and the final norm."""
    m = dims(cfg)
    p = matrix_params(cfg)
    moe_layer = p["router"] + m["held"] * p["expert"] + p["shared"]
    return (m["L"] * attn_params(cfg) + p["dense_mlp"]
            + (m["L"] - 1) * moe_layer + 2 * m["vocab"] * m["d"] + m["d"])


def kv_bytes_per_token(cfg: Dict) -> int:
    m = dims(cfg)
    return m["L"] * (m["kvr"] + m["rope"]) * _bytes(cfg)


def _unrouted_matmul_params(cfg: Dict) -> int:
    """Per token: the matrices every token multiplies, routed experts out."""
    m, p = dims(cfg), matrix_params(cfg)
    return (m["L"] * p["attn"] + p["dense_mlp"]
            + (m["L"] - 1) * (p["router"] + p["shared"]))


def decode_weight_bytes(cfg: Dict) -> int:
    """Weights one decode step has to read, the routed experts out: every
    layer's attention and norms, the dense layer, routers and shared
    experts, the final norm and the output head."""
    m, p = dims(cfg), matrix_params(cfg)
    return (m["L"] * attn_params(cfg) + p["dense_mlp"]
            + (m["L"] - 1) * (p["router"] + p["shared"]) + m["d"]
            + m["d"] * m["vocab"]) * _bytes(cfg)


def _absorbed_attention_flops(cfg: Dict, keys: int) -> int:
    """One decode query's attention over ``keys`` latent rows, all layers:
    scores over kv_lora_rank + rope, the value sum over kv_lora_rank."""
    m = dims(cfg)
    return 2 * m["L"] * m["hq"] * (2 * m["kvr"] + m["rope"]) * keys


def decode_step(cfg: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """One fused decode micro-step over the live slots at ``positions``:
    the FLOPs, and the least bytes (the weights but the routed experts
    once, the live latent rows read, one row written per slot)."""
    m = dims(cfg)
    positions = list(positions)
    kvb = kv_bytes_per_token(cfg)
    per_tok = 2 * (_unrouted_matmul_params(cfg) + m["d"] * m["vocab"])
    flops = sum(per_tok + _absorbed_attention_flops(cfg, p + 1)
                for p in positions)
    nbytes = decode_weight_bytes(cfg) + sum((p + 1) * kvb for p in positions)
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_chunk(cfg: Dict, start: int, stop: int) -> float:
    """FLOPs of a prompt chunk at positions [start, stop): every token
    through the layers (the routed experts at top_k x held / routed pairs
    a token), attention in the expanded form over p + 1 keys, and the
    head once for the last token's logits."""
    m, p = dims(cfg), matrix_params(cfg)
    n = stop - start
    keys = n * (start + stop + 1) // 2          # sum of p + 1 over the chunk
    pairs = m["top_k"] * m["held"] / m["routed"]
    per_tok = 2 * (_unrouted_matmul_params(cfg)
                   + (m["L"] - 1) * pairs * p["expert"])
    attn = 2 * m["L"] * m["hq"] * (m["nope"] + m["rope"] + m["vd"]) * keys
    return float(n * per_tok + attn + 2 * m["d"] * m["vocab"])


def decode_attention(cfg: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """Attention's own work in one decode micro-step over the live slots at
    ``positions``: per layer the two absorption products and the absorbed
    scores and value sum over p + 1 latent rows; p + 1 rows read and one
    written per slot."""
    m = dims(cfg)
    positions = list(positions)
    kvb = kv_bytes_per_token(cfg)
    absorb = 2 * m["L"] * m["hq"] * 2 * m["kvr"] * m["nope"]
    return {"flops": float(sum(absorb + _absorbed_attention_flops(cfg, p + 1)
                               for p in positions)),
            "bytes": float(sum((p + 2) * kvb for p in positions))}


def moe_decode(cfg: Dict, pairs: float, experts_hit: float,
               steps: int) -> Dict[str, float]:
    """The held experts' work in ``steps`` decode micro-steps that routed
    ``pairs`` token-expert pairs to held experts, ``experts_hit`` (layer,
    expert) pairs receiving one: 3 products of d x moe_ff a pair, the
    router's d x routed a row and expert layer (every one of the
    ``max_batch`` rows is routed); bytes of each hit expert's weights and
    the routers', once a step."""
    m, p = dims(cfg), matrix_params(cfg)
    b = _bytes(cfg)
    rows = cfg["engine"]["max_batch"] * steps * (m["L"] - 1)
    return {"flops": float(2 * pairs * p["expert"] + 2 * rows * p["router"]),
            "bytes": float(experts_hit * p["expert"] * b
                           + steps * (m["L"] - 1) * p["router"] * b)}


def memory(cfg: Dict) -> Dict[str, int]:
    """The configuration's memory arithmetic as served: weights as stored
    (padded vocabulary rows included), KV per token, and the page pool."""
    m = dims(cfg)
    eng = cfg["engine"]
    stored = parameters(cfg) + 2 * (m["vocab_pad"] - m["vocab"]) * m["d"]
    kvb = kv_bytes_per_token(cfg)
    return {"parameters": parameters(cfg),
            "weight_bytes": stored * _bytes(cfg),
            "kv_bytes_per_token": kvb,
            "pool_bytes": (eng["num_pages"] + 1) * eng["page_size"] * kvb}
