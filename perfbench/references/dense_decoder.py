"""Plain float32 reference of a dense decoder (Llama-style block).

    h   = embed[tokens] * scale_emb
    per layer:
      a = attention(rmsnorm(h) ; wq, wk, wv, RoPE, causal softmax, wo)
      h = h + residual_scale * a
      f = (silu(rmsnorm(h) wg) * (rmsnorm(h) wu)) wd
      h = h + residual_scale * f
    logits = (rmsnorm(h) @ head) * logit_scale     head = embed^T if tied

MiniCPM's muP factors: scale_emb, residual_scale = scale_depth /
sqrt(num_hidden_layers), logit_scale = dim_model_base / hidden_size; other
models have them at 1. Query head i reads key/value head i // (Hq / Hkv).
RoPE rotates interleaved pairs (2i, 2i+1) with inverse frequency
theta^(-2i / head_dim); Hugging Face's rotate_half layout is the same map
under a fixed permutation of the q and k projection columns, which random
weights cannot tell apart.

Every matrix product runs at ``Precision.HIGHEST`` in float32 on weights
read from the served bfloat16 values. The whole sequence goes through one
layer at a time, with attention in blocks of query rows, so the largest
temporary is one block's scores. The ``fp8`` variant quantizes every
weight matrix to float8_e4m3 with one scale per output channel: the
lower-precision control that a correct comparison must reject.

This file imports nothing of the program under test.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
SEQ_BUCKET = 1024
LOGIT_BLOCK = 256
FP8_MAX = 448.0


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round-trip ``w`` through float8_e4m3 with a scale per slice along
    ``axis`` (the reduction axis of the product it feeds)."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _weight(w: jax.Array, fp8: bool, axis: int = -2) -> jax.Array:
    w = w.astype(jnp.float32)
    return _fp8(w, axis) if fp8 else w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * inv        # (S, 1, hd/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("hp", "fp8"))
def _layer(h, w, layer, *, hp, fp8):
    """One decoder layer over a whole padded sequence h: (S, d) float32."""
    eps, theta, rs, hq, hkv = hp
    lw = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
          for k, v in w.items()}
    S, d = h.shape
    hd = lw["wq"].shape[-1] // hq
    pos = jnp.arange(S)
    x = _rmsnorm(h, lw["ln1"].astype(jnp.float32), eps)
    q = jnp.dot(x, _weight(lw["wq"], fp8), precision=HI).reshape(S, hq, hd)
    k = jnp.dot(x, _weight(lw["wk"], fp8), precision=HI).reshape(S, hkv, hd)
    v = jnp.dot(x, _weight(lw["wv"], fp8), precision=HI).reshape(S, hkv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    kv_of_q = jnp.arange(hq) // (hq // hkv)
    k, v = k[:, kv_of_q], v[:, kv_of_q]                       # (S, hq, hd)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        qpos = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, hq * hd)
    h = h + rs * jnp.dot(o, _weight(lw["wo"], fp8), precision=HI)
    x = _rmsnorm(h, lw["ln2"].astype(jnp.float32), eps)
    g = jnp.dot(x, _weight(lw["w_gate"], fp8), precision=HI)
    u = jnp.dot(x, _weight(lw["w_up"], fp8), precision=HI)
    f = jnp.dot(jax.nn.silu(g) * u, _weight(lw["w_down"], fp8), precision=HI)
    return h + rs * f


@functools.partial(jax.jit, static_argnames=("scale_emb", "fp8"))
def _embed(embed, tokens, *, scale_emb, fp8):
    e = _weight(embed, fp8, axis=-1) if fp8 else None
    rows = (e[tokens] if fp8 else embed[tokens].astype(jnp.float32))
    return rows * scale_emb


@functools.partial(jax.jit, static_argnames=("eps", "logit_scale", "vocab",
                                             "fp8"))
def _logits(h_rows, final_norm, head, *, eps, logit_scale, vocab, fp8):
    """Logits over the true vocabulary for a block of hidden rows; ``head``
    is (d, V) or, tied, the (V, d) embedding."""
    x = _rmsnorm(h_rows, final_norm.astype(jnp.float32), eps)
    if head.shape[0] == x.shape[-1]:                      # untied (d, V)
        out = jnp.dot(x, _weight(head, fp8, axis=-2), precision=HI)
    else:                                                 # tied (V, d)
        out = jnp.dot(x, _weight(head, fp8, axis=-1).T, precision=HI)
    return out[:, :vocab] * logit_scale


def hyper(cfg: Dict) -> Dict:
    """The numbers of a configuration file that the equations above use."""
    L = cfg["num_hidden_layers"]
    depth = cfg.get("scale_depth")
    base = cfg.get("dim_model_base")
    return {"L": L, "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "residual_scale": (depth / math.sqrt(L)) if depth else 1.0,
            "scale_emb": float(cfg.get("scale_emb", 1.0)),
            "logit_scale": (base / cfg["hidden_size"]) if base else 1.0,
            "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "vocab": cfg["vocab_size"]}


def logits(cfg: Dict, weights: Dict[str, jax.Array], tokens, at,
           fp8: bool = False) -> np.ndarray:
    """Reference logits (len(at), vocab) of the sequence ``tokens`` at the
    positions ``at`` (each predicts the token after it)."""
    hp = hyper(cfg)
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    S_pad = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    padded = np.zeros((S_pad,), np.int32)
    padded[:S] = tokens
    h = _embed(weights["embed"], jnp.asarray(padded),
               scale_emb=hp["scale_emb"], fp8=fp8)
    layer_w = {k: weights[k] for k in ("ln1", "wq", "wk", "wv", "wo", "ln2",
                                       "w_gate", "w_up", "w_down")}
    key = (hp["eps"], hp["theta"], hp["residual_scale"], hp["hq"], hp["hkv"])
    for layer in range(hp["L"]):
        h = _layer(h, layer_w, jnp.asarray(layer, jnp.int32), hp=key, fp8=fp8)
    head = weights.get("lm_head", weights["embed"])
    at = np.asarray(at, np.int32)
    out = []
    for i in range(0, len(at), LOGIT_BLOCK):
        idx = np.zeros((LOGIT_BLOCK,), np.int32)
        part = at[i:i + LOGIT_BLOCK]
        idx[:len(part)] = part
        block = _logits(h[jnp.asarray(idx)], weights["final_norm"], head,
                        eps=hp["eps"], logit_scale=hp["logit_scale"],
                        vocab=hp["vocab"], fp8=fp8)
        out.append(np.asarray(block)[:len(part)])
    return np.concatenate(out, 0)
