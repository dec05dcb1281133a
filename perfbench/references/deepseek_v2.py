"""Plain float32 reference of DeepSeek-V2 (MLA + routed and shared experts,
YaRN rope), after the published modelling code (modeling_deepseek.py of
deepseek-ai/DeepSeek-V2-Lite).

    h = embed[tokens]
    per layer:
      x = rmsnorm(h)
      q = x q_proj                      per head: q_nope (128) | q_pe (64)
      c, k_pe = x kv_a_proj             latent (512) | shared rope key (64)
      c = rmsnorm(c)
      k_nope = c k_b_proj, v = c v_b_proj             per head (expanded)
      q_pe, k_pe = YaRN-roped
      a = causal softmax(scale * [q_nope|q_pe].[k_nope|k_pe]) v ; h += a o_proj
      x = rmsnorm(h)
      layer 0:  h += (silu(x gate) * (x up)) down
      others:   p = softmax(x router), float32, over every routed expert
                g, e = greedy top-k of p   (renormalised only with
                                            norm_topk_prob, else times
                                            routed_scaling_factor)
                h += sum over the pairs whose expert is held of
                     g * expert_e(x)  +  shared(x)
    logits = rmsnorm(h) lm_head

YaRN: inverse frequencies theta^(-2i/64) blended with the same over
``factor`` by a linear ramp between the correction dims of ``beta_fast``
and ``beta_slow`` rotations over ``original_max_position_embeddings``
(floored and ceiled as published); cos and sin times
mscale(mscale) / mscale(mscale_all_dim); softmax scale
(nope + rope)^-0.5 x mscale(mscale_all_dim)^2, where
mscale(m) = 0.1 m ln(factor) + 1.

Departures from the published code, none of which random weights can
observe or which the deployment states:
- rope rotates interleaved pairs (2i, 2i+1), as the program does; the
  published code permutes q_pe and k_pe into halves first, a fixed
  permutation of the projections' columns;
- the latent's up-projection is two tensors, ``k_b_proj`` and
  ``v_b_proj``, where the published ``kv_b_proj`` interleaves them per head;
- only the held experts (0 .. n_routed_experts-1 of the configuration
  file, the chip's share of its expert-parallel deployment) are computed,
  each as a dense product over every token weighted by its gate (zero
  where the token did not pick it); what the experts held elsewhere add
  is left out, as in the program;
- every product is float32 at ``Precision.HIGHEST``, on weights read from
  the served bfloat16 values.

The whole sequence goes through one layer at a time, with attention in
blocks of query rows. The ``fp8`` variant rounds every weight matrix to
float8_e4m3 with one scale per output channel, the router's included: the
lower-precision control that a correct comparison must reject. The
interface is ``references/dense_decoder.py``'s.

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
Q_BLOCK = 256
SEQ_BUCKET = 4096
LOGIT_BLOCK = 256
FP8_MAX = 448.0
ATTN = ("ln1", "q_proj", "kv_a_proj", "kv_a_norm", "k_b_proj", "v_b_proj",
        "o_proj", "ln2")
DENSE = ("gate_proj", "up_proj", "down_proj")
MOE = ("router", "experts_gate", "experts_up", "experts_down",
       "shared_gate", "shared_up", "shared_down")


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _weight(w: jax.Array, fp8: bool, axis: int = -2) -> jax.Array:
    w = w.astype(jnp.float32)
    return _fp8(w, axis) if fp8 else w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """The rope's inverse frequencies, YaRN-blended when ``yarn`` is set."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return extra.astype(np.float32)
    orig = yarn["original_max_position_embeddings"]

    def corr(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(yarn["beta_fast"])), 0)
    high = min(math.ceil(corr(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    inter = extra / yarn["factor"]
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def softmax_scale(cfg: Dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    yarn = cfg.get("rope_scaling")
    if yarn and yarn.get("mscale_all_dim"):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def _rope(x, pos, inv, cs_scale):
    """x: (S, H, r); rotate pairs (2i, 2i+1) by pos * inv[i]."""
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang) * cs_scale, jnp.sin(ang) * cs_scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _swiglu(x, gate, up, down, fp8):
    g = jnp.dot(x, _weight(gate, fp8), precision=HI)
    u = jnp.dot(x, _weight(up, fp8), precision=HI)
    return jnp.dot(jax.nn.silu(g) * u, _weight(down, fp8), precision=HI)


def _attention(h, lw, hp, fp8):
    """h + MLA over the whole padded sequence h: (S, d) float32; returns
    it and the normed input of the feed-forward."""
    (eps, H, nope, rope, vd, kvr, scale, inv, cs) = hp
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rmsnorm(h, lw["ln1"], eps)
    q = jnp.dot(x, _weight(lw["q_proj"], fp8), precision=HI).reshape(
        S, H, nope + rope)
    kv = jnp.dot(x, _weight(lw["kv_a_proj"], fp8), precision=HI)
    c = _rmsnorm(kv[:, :kvr], lw["kv_a_norm"], eps)
    inv = jnp.asarray(np.asarray(inv, np.float32))
    q_pe = _rope(q[..., nope:], pos, inv, cs)
    k_pe = _rope(kv[:, None, kvr:], pos, inv, cs)             # (S, 1, r)
    k_nope = jnp.dot(c, _weight(lw["k_b_proj"], fp8),
                     precision=HI).reshape(S, H, nope)
    v = jnp.dot(c, _weight(lw["v_b_proj"], fp8), precision=HI).reshape(
        S, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rope))], -1)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(qf, qb_start, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, H * vd)
    h = h + jnp.dot(o, _weight(lw["o_proj"], fp8), precision=HI)
    return h, _rmsnorm(h, lw["ln2"], eps)


@functools.partial(jax.jit, static_argnames=("hp", "fp8"))
def _dense_layer(h, w, *, hp, fp8):
    h, x = _attention(h, w, hp, fp8)
    return h + _swiglu(x, w["gate_proj"], w["up_proj"], w["down_proj"], fp8)


@functools.partial(jax.jit, static_argnames=("hp", "route", "fp8"))
def _moe_layer(h, w, layer, *, hp, route, fp8):
    top_k, norm_topk, scaling = route
    lw = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
          for k, v in w.items()}
    h, x = _attention(h, lw, hp, fp8)
    logits = jnp.dot(x, _weight(lw["router"], fp8), precision=HI)
    p = jax.nn.softmax(logits, axis=-1)
    g, e = jax.lax.top_k(p, top_k)
    if top_k > 1 and norm_topk:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    else:
        g = g * scaling
    held = lw["experts_gate"].shape[0]
    gate_of = jnp.stack([jnp.sum(jnp.where(e == i, g, 0.0), -1)
                         for i in range(held)], 0)            # (held, S)

    def expert(acc, i):
        y = _swiglu(x, lw["experts_gate"][i], lw["experts_up"][i],
                    lw["experts_down"][i], fp8)
        return acc + gate_of[i][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    y = y + _swiglu(x, lw["shared_gate"], lw["shared_up"],
                    lw["shared_down"], fp8)
    return h + y


@functools.partial(jax.jit, static_argnames=("fp8",))
def _embed(embed, tokens, *, fp8):
    e = _weight(embed, fp8, axis=-1) if fp8 else embed.astype(jnp.float32)
    return e[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "fp8"))
def _logits(h_rows, final_norm, head, *, eps, vocab, fp8):
    x = _rmsnorm(h_rows, final_norm, eps)
    return jnp.dot(x, _weight(head, fp8), precision=HI)[:, :vocab]


def hyper(cfg: Dict) -> tuple:
    """The numbers of a configuration file that attention uses (hashable,
    a static argument of the jitted layers)."""
    rope = cfg["qk_rope_head_dim"]
    yarn = cfg.get("rope_scaling")
    cs = (yarn_mscale(yarn["factor"], yarn["mscale"])
          / yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
          if yarn else 1.0)
    return (float(cfg["rms_norm_eps"]), cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], rope, cfg["v_head_dim"],
            cfg["kv_lora_rank"], softmax_scale(cfg),
            tuple(float(f) for f in inv_freq(rope, float(cfg["rope_theta"]),
                                              yarn)), float(cs))


def logits(cfg: Dict, weights: Dict[str, jax.Array], tokens, at,
           fp8: bool = False) -> np.ndarray:
    """Reference logits (len(at), vocab) of the sequence ``tokens`` at the
    positions ``at`` (each predicts the token after it)."""
    hp = hyper(cfg)
    route = (cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
             float(cfg["routed_scaling_factor"]))
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    S_pad = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    padded = np.zeros((S_pad,), np.int32)
    padded[:S] = tokens
    h = _embed(weights["embed"], jnp.asarray(padded), fp8=fp8)
    h = _dense_layer(h, {k: weights["l0_" + k] for k in ATTN + DENSE},
                     hp=hp, fp8=fp8)
    moe_w = {k: weights[k] for k in ATTN + MOE}
    for layer in range(cfg["num_hidden_layers"] - 1):
        h = _moe_layer(h, moe_w, jnp.asarray(layer, jnp.int32), hp=hp,
                       route=route, fp8=fp8)
    at = np.asarray(at, np.int32)
    out = []
    for i in range(0, len(at), LOGIT_BLOCK):
        idx = np.zeros((LOGIT_BLOCK,), np.int32)
        part = at[i:i + LOGIT_BLOCK]
        idx[:len(part)] = part
        block = _logits(h[jnp.asarray(idx)], weights["final_norm"],
                        weights["lm_head"], eps=float(cfg["rms_norm_eps"]),
                        vocab=cfg["vocab_size"], fp8=fp8)
        out.append(np.asarray(block)[:len(part)])
    return np.concatenate(out, 0)
