"""A configuration file's model, as the program serves it.

The benchmark builds the program's ``ModelConfig`` from its own
configuration file (not from ``repro.configs``), so a change to the
program's presets cannot move the yardstick. Weights are random and made
by the benchmark, on the device, in one jitted call from the seed, under
the benchmark's own names (``canonical``). The program's parameter tree is
assembled from the same arrays, and the reference reads the canonical
names, so both see the same numbers and the reference takes nothing the
program made.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model, ModelConfig


def dims(cfg: Dict) -> Dict[str, int]:
    """The shape numbers every counter and the reference need."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": cfg["num_key_value_heads"], "hd": d // hq,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "vocab_pad": padded_vocab(cfg), "tied": bool(cfg["tie_word_embeddings"])}


def padded_vocab(cfg: Dict) -> int:
    m = cfg["assumed"]["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def dtype_of(cfg: Dict):
    return jnp.dtype(cfg["torch_dtype"])


def program_config(cfg: Dict) -> ModelConfig:
    """The program's ModelConfig for a dense decoder configuration file."""
    if cfg["architecture"] != "dense_decoder":
        raise ValueError(f"{cfg['name']}: no program mapping for "
                         f"architecture {cfg['architecture']!r}")
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


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``stream`` of a run, from a seed of any size."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0] >> 1)),
                              int(words[1] >> 1))


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


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight in one jitted call on the default device, in the
    served dtype: norms 1, embedding N(0, embedding_std), each projection
    and the output head N(0, 1/fan_in). The embedding's spread is an
    assumption of the configuration file: with tied embeddings and a large
    scale_emb, a wide one makes a random model repeat its last token,
    whatever the context."""
    shapes = canonical_shapes(cfg)
    dt = dtype_of(cfg)
    init_std = float(cfg["assumed"]["embedding_std"])

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name in ("ln1", "ln2", "final_norm"):
                out[name] = jnp.ones(shape, dt)
                continue
            std = init_std if name == "embed" else 1.0 / math.sqrt(shape[-2])
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           dt) * jnp.asarray(std, dt))
        return out

    return jax.jit(build)(key_from_seed(seed, 0))


def program_params(weights: Dict[str, jax.Array], model: Model) -> Dict:
    """The program's parameter tree over the same arrays (no copy), checked
    leaf for leaf against the program's own parameter shapes."""
    layer = {"ln1": {"scale": weights["ln1"]},
             "attn": {k: weights[k] for k in ("wq", "wk", "wv", "wo")},
             "ln2": {"scale": weights["ln2"]},
             "mlp": {k: weights[k] for k in ("w_gate", "w_up", "w_down")}}
    tree = {"embed": {"tok": weights["embed"]}, "prefix": (),
            "unit": {"0": layer},
            "final_norm": {"scale": weights["final_norm"]},
            "lm_head": ({} if "lm_head" not in weights
                        else {"out": weights["lm_head"]})}
    want = model.param_shapes()
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got)
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got)):
        raise ValueError(f"{model.cfg.name}: the program's parameter tree no "
                         f"longer matches the benchmark's mapping:\n{want}\n"
                         f"vs\n{got}")
    return tree

