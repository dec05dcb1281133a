"""Basic layers: norms, RoPE, MLPs, embeddings. Pure-functional JAX.

Params are plain nested dicts of jnp arrays; every function takes the
param dict explicitly. Compute follows the usual mixed-precision discipline:
activations in cfg.dtype (bf16 target), norm statistics and softmax in f32.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import shard


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def normal(key, shape, scale, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return normal(key, (d_in, d_out), s, dtype)


# --- norms -----------------------------------------------------------------


def rmsnorm_init(dim: int, dtype) -> Dict:
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rmsnorm(p: Dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype) -> Dict:
    return {"scale": jnp.ones((dim,), dtype=dtype),
            "bias": jnp.zeros((dim,), dtype=dtype)}


def layernorm(p: Dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# --- rotary embeddings -----------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: 0.1 * mscale * ln(factor) + 1,
    and 1 without interpolation."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(rot: int, theta: float, scaling
                          ) -> Tuple[int, int]:
    """The dims between which YaRN ramps from interpolated to original
    frequencies: those completing ``beta_fast`` and ``beta_slow``
    rotations over the original context, floored and ceiled as published
    (10 and 23 for DeepSeek-V2-Lite's 64 rope dims)."""
    def dim(rotations):
        return (rot * math.log(scaling.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(dim(scaling.beta_fast))
    high = math.ceil(dim(scaling.beta_slow))
    return max(low, 0), min(high, rot - 1)


def rope_frequencies(head_dim: int, rotary_pct: float, theta: float,
                     scaling=None) -> Tuple[int, jax.Array]:
    """(rotary_dim, inv_freq[rotary_dim/2]); ``scaling`` (a
    ``YarnScaling``) blends theta^(-2i/rot) and the same over ``factor``
    by a linear ramp over the correction range."""
    rot = int(head_dim * rotary_pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if scaling is not None:
        low, high = yarn_correction_range(rot, theta, scaling)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        extra = 1.0 - ramp              # 1 where the original frequency stays
        inv = (inv / scaling.factor) * (1.0 - extra) + inv * extra
    return rot, inv


def apply_rope(x: jax.Array, positions: jax.Array, rotary_pct: float,
               theta: float, scaling=None) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S) int32."""
    hd = x.shape[-1]
    rot, inv = rope_frequencies(hd, rotary_pct, theta, scaling)
    if rot == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]                      # (..., S, 1, rot/2)
    sin = jnp.sin(ang)[..., :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2].astype(jnp.float32), xr[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    roped = jnp.stack([r1, r2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([roped, xp], axis=-1) if rot < hd else roped


# --- MLPs ------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": dense_init(k1, d_model, d_ff, dtype),
            "w_up": dense_init(k2, d_model, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d_model, dtype)}


def swiglu(p: Dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    if h.ndim == 3:
        h = shard(h, "batch", "seq", "d_ff")
    elif h.ndim == 2:
        h = shard(h, "tokens", "d_ff")
    out = jnp.einsum("...f,fd->...d", h, p["w_down"])
    if out.ndim == 2:
        out = shard(out, "tokens", None)
    return out


# --- embeddings / logits ---------------------------------------------------


def embedding_init(key, cfg) -> Dict:
    dt = dtype_of(cfg)
    p = {"tok": normal(key, (cfg.padded_vocab, cfg.d_model), 0.02, dt)}
    return p


def embed(p: Dict, cfg, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.scale_emb != 1.0:
        x = x * jnp.asarray(cfg.scale_emb, dtype=x.dtype)
    return shard(x, "batch", "seq", "embed")


def lm_head_init(key, cfg) -> Dict:
    if cfg.tie_embeddings:
        return {}
    dt = dtype_of(cfg)
    return {"out": dense_init(key, cfg.d_model, cfg.padded_vocab, dt, scale=0.02)}


def logits(head_p: Dict, embed_p: Dict, cfg, x: jax.Array) -> jax.Array:
    w = embed_p["tok"].T if cfg.tie_embeddings else head_p["out"]
    out = jnp.einsum("...d,dv->...v", x, w).astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        out = out * cfg.logit_scale
    if out.ndim == 3:
        out = shard(out, "batch", "seq", "vocab")
    return out
