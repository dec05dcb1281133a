"""Per-layer blocks: init + apply for every kind in ``BLOCK_KINDS``.

``block_apply`` has one signature for all kinds; the ``LayerCtx`` carries
everything mode/position dependent. Cache entries are per-layer pytrees
(attention KV, MLA latent, SSM state, RWKV state, cross-attn KV) — ``None``
for layers without state (training mode).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import moe_sharded as MOES
from repro.models import rwkv as RWKV
from repro.models import ssm as SSM
from repro.sharding import shard


@dataclasses.dataclass
class LayerCtx:
    cfg: Any
    mode: str                           # train | prefill | decode
    positions: jax.Array                # (B, S) int32 absolute positions
    mask: Optional[jax.Array] = None    # (B, S) 1=real token
    memory: Optional[jax.Array] = None  # image / encoder embeddings (B,M,d)
    emb_orig: Optional[jax.Array] = None  # Zamba2 concat input
    layer_idx: int = 0                  # absolute depth (chunk alternation)
    batch: int = 1
    max_len: int = 0                    # cache allocation length
    page_tbl: Optional[jax.Array] = None  # (B, max_pages) paged-KV block table


def _layer_window_chunk(cfg, layer_idx: int):
    window = cfg.sliding_window
    chunk = cfg.attn_chunk if cfg.layer_uses_chunked_attn(layer_idx) else None
    return window, chunk


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def block_init(key, cfg, kind: str) -> Dict:
    dt = L.dtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if kind in ("dense", "parallel", "moe"):
        p = {"ln1": L.rmsnorm_init(d, dt),
             "attn": A.attn_init(ks[0], cfg, qk_norm=(kind == "parallel"))}
        if kind == "moe":
            p["ln2"] = L.rmsnorm_init(d, dt)
            p["moe"] = MOE.moe_init(ks[1], cfg)
        else:
            p["ln2"] = L.rmsnorm_init(d, dt)
            p["mlp"] = L.swiglu_init(ks[1], d, cfg.d_ff, dt)
        return p
    if kind in ("mla", "mla_moe"):
        p = {"ln1": L.rmsnorm_init(d, dt), "attn": A.mla_init(ks[0], cfg),
             "ln2": L.rmsnorm_init(d, dt)}
        if kind == "mla_moe":
            p["moe"] = MOE.moe_init(ks[1], cfg)
        else:
            p["mlp"] = L.swiglu_init(ks[1], d, cfg.d_ff, dt)
        return p
    if kind == "mamba2":
        return {"ln1": L.rmsnorm_init(d, dt), "ssm": SSM.ssm_init(ks[0], cfg)}
    if kind == "rwkv6":
        return RWKV.rwkv_layer_init(ks[0], cfg)
    if kind == "shared":
        # Zamba2 weight-shared block on concat(h, emb): width 2d
        return {"ln1": L.rmsnorm_init(2 * d, dt),
                "attn": A.attn_init(ks[0], cfg, d_in=2 * d),
                "ln2": L.rmsnorm_init(2 * d, dt),
                "mlp": {"w_gate": L.dense_init(ks[1], 2 * d, cfg.d_ff, dt),
                        "w_up": L.dense_init(ks[2], 2 * d, cfg.d_ff, dt),
                        "w_down": L.dense_init(ks[3], cfg.d_ff, d, dt)}}
    if kind == "cross":
        return {"ln1": L.rmsnorm_init(d, dt),
                "attn": A.cross_attn_init(ks[0], cfg, gated=True),
                "ln2": L.rmsnorm_init(d, dt),
                "mlp": L.swiglu_init(ks[1], d, cfg.d_ff, dt),
                "gate_mlp": jnp.zeros((), dt)}
    if kind == "enc":
        return {"ln1": L.rmsnorm_init(d, dt), "attn": A.attn_init(ks[0], cfg),
                "ln2": L.rmsnorm_init(d, dt),
                "mlp": L.swiglu_init(ks[1], d, cfg.d_ff, dt)}
    if kind == "dec":
        return {"ln1": L.rmsnorm_init(d, dt), "attn": A.attn_init(ks[0], cfg),
                "ln_x": L.rmsnorm_init(d, dt),
                "xattn": A.cross_attn_init(ks[1], cfg, gated=False),
                "ln2": L.rmsnorm_init(d, dt),
                "mlp": L.swiglu_init(ks[2], d, cfg.d_ff, dt)}
    raise ValueError(f"unknown block kind {kind!r}")


# --------------------------------------------------------------------------
# cache init
# --------------------------------------------------------------------------


def block_cache_init(cfg, kind: str, batch: int, max_len: int,
                     memory_len: int = 0, layer_idx: int = 0) -> Optional[Dict]:
    dt = L.dtype_of(cfg)
    if cfg.layer_uses_chunked_attn(layer_idx):
        # chunked local attention only ever attends within the current
        # chunk: a ring of `attn_chunk` slots suffices (global layers keep
        # the full-length cache).
        max_len = min(max_len, cfg.attn_chunk)
    if kind in ("dense", "parallel", "moe", "enc"):
        return A.init_kv_cache(cfg, batch, max_len)
    if kind in ("mla", "mla_moe"):
        return A.init_mla_cache(cfg, batch, max_len)
    if kind == "mamba2":
        return SSM.init_ssm_state(cfg, batch)
    if kind == "rwkv6":
        return RWKV.init_rwkv_state(cfg, batch)
    if kind == "shared":
        return A.init_kv_cache(cfg, batch, max_len)
    if kind == "cross":
        hd, n_kvp = cfg.head_dim_, cfg.n_kv_heads_padded
        M = memory_len or cfg.n_image_tokens or cfg.encoder_seq
        return {"k": jnp.zeros((batch, M, n_kvp, hd), dt),
                "v": jnp.zeros((batch, M, n_kvp, hd), dt)}
    if kind == "dec":
        c = A.init_kv_cache(cfg, batch, max_len)
        hd, n_kvp = cfg.head_dim_, cfg.n_kv_heads_padded
        M = memory_len or cfg.encoder_seq
        c["xk"] = jnp.zeros((batch, M, n_kvp, hd), dt)
        c["xv"] = jnp.zeros((batch, M, n_kvp, hd), dt)
        return c
    return None


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------


def _mlp(p, x):
    """The dense feed-forward, under the profiler scope ``mlp``."""
    with jax.named_scope("mlp"):
        return L.swiglu(p, x)


def _moe_dispatch(p, cfg, x):
    """Sharded (shard_map all_to_all) MoE when a multi-device mesh context is
    active; dense capacity-dispatch otherwise (CPU unit tests). Scoped
    ``mlp`` like the dense feed-forward."""
    with jax.named_scope("mlp"):
        if MOES.use_sharded_moe(cfg):
            return MOES.moe_ffn_sharded(p, cfg, x)
        return MOE.moe_ffn(p, cfg, x)


def _res(cfg, x, delta):
    if delta.ndim == 3:
        # pin (batch, seq, replicated-d): under FSDP, leaving this free lets
        # GSPMD shard activations' d over the data axis and replicate batch,
        # turning per-layer weight gathers (MBs) into activation gathers
        # (GBs) — EXPERIMENTS.md SSPerf H1 iter 3
        delta = shard(delta, "batch", "seq", "embed")
    if cfg.residual_scale != 1.0:
        delta = delta * jnp.asarray(cfg.residual_scale, dtype=delta.dtype)
    return x + delta


def _norm3(p, x, eps):
    """rmsnorm + (batch, seq, replicated-d) constraint: the constraint's
    transpose pins the block-input cotangent, which otherwise inherits the
    FSDP weight sharding in the backward dots (SSPerf H2 iter 2)."""
    h = L.rmsnorm(p, x, eps)
    if h.ndim == 3:
        h = shard(h, "batch", "seq", "embed")
    return h


def block_apply(p: Dict, cfg, kind: str, ctx: LayerCtx, x: jax.Array,
                cache: Optional[Dict]) -> Tuple[jax.Array, Optional[Dict],
                                                Dict]:
    """Returns (x, new_cache, aux_losses)."""
    aux: Dict = {}
    window, chunk = _layer_window_chunk(cfg, ctx.layer_idx)

    if kind in ("dense", "moe"):
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        a, cache = A.self_attention(p["attn"], cfg, h, ctx.positions,
                                    window, chunk, cache, ctx.mode,
                                    page_tbl=ctx.page_tbl)
        x = _res(cfg, x, a)
        h2 = _norm3(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            f, aux = _moe_dispatch(p["moe"], cfg, h2)
        else:
            f = _mlp(p["mlp"], h2)
        return _res(cfg, x, f), cache, aux

    if kind == "parallel":                       # StableLM-2: parallel residual
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        a, cache = A.self_attention(p["attn"], cfg, h, ctx.positions,
                                    window, chunk, cache, ctx.mode,
                                    page_tbl=ctx.page_tbl)
        h2 = _norm3(p["ln2"], x, cfg.norm_eps)
        f = _mlp(p["mlp"], h2)
        return _res(cfg, x, a + f), cache, aux

    if kind in ("mla", "mla_moe"):
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        a, cache = A.mla_attention(p["attn"], cfg, h, ctx.positions,
                                   window, cache, ctx.mode)
        x = _res(cfg, x, a)
        h2 = _norm3(p["ln2"], x, cfg.norm_eps)
        if kind == "mla_moe":
            f, aux = _moe_dispatch(p["moe"], cfg, h2)
        else:
            f = _mlp(p["mlp"], h2)
        return _res(cfg, x, f), cache, aux

    if kind == "mamba2":
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        y, new_state = SSM.mamba2_block(p["ssm"], cfg, h, cache, ctx.mode,
                                        ctx.mask)
        return _res(cfg, x, y), (new_state if new_state is not None else cache), aux

    if kind == "rwkv6":
        return (*RWKV.rwkv_block(p, cfg, x, cache, ctx.mode), aux)

    if kind == "shared":                          # Zamba2
        assert ctx.emb_orig is not None
        cat = jnp.concatenate([x, ctx.emb_orig], axis=-1)
        h = _norm3(p["ln1"], cat, cfg.norm_eps)
        a, cache = A.self_attention(p["attn"], cfg, h, ctx.positions,
                                    window, None, cache, ctx.mode,
                                    page_tbl=ctx.page_tbl)
        x = _res(cfg, x, a)
        cat2 = jnp.concatenate([x, ctx.emb_orig], axis=-1)
        h2 = _norm3(p["ln2"], cat2, cfg.norm_eps)
        with jax.named_scope("mlp"):
            g = jnp.einsum("...d,df->...f", h2, p["mlp"]["w_gate"])
            u = jnp.einsum("...d,df->...f", h2, p["mlp"]["w_up"])
            f = jnp.einsum(
                "...f,fd->...d",
                jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u,
                p["mlp"]["w_down"])
        return _res(cfg, x, f), cache, aux

    if kind == "cross":                           # VLM gated cross-attn layer
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        if ctx.mode in ("train", "prefill") and ctx.memory is not None:
            cache = A.build_cross_cache(p["attn"], cfg, ctx.memory)
        a = A.cross_attention(p["attn"], cfg, h, cache)
        x = _res(cfg, x, a)
        h2 = _norm3(p["ln2"], x, cfg.norm_eps)
        f = _mlp(p["mlp"], h2)
        f = f * jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(f.dtype)
        return _res(cfg, x, f), cache, aux

    if kind == "enc":                             # bidirectional
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        q, k, v = A._qkv(p["attn"], cfg, h, ctx.positions)
        kk, vv = A._expand_kv(cfg, k), A._expand_kv(cfg, v)
        Sk = kk.shape[1]
        bias = jnp.zeros((x.shape[0], 1, x.shape[1], Sk), jnp.float32)
        if ctx.mask is not None:
            bias = jnp.where(ctx.mask[:, None, None, :] > 0, 0.0, A.NEG_INF)
        o = A._direct_attention(q, kk, vv, bias)
        a = jnp.einsum("...h,hd->...d", o.reshape(o.shape[:-2] + (-1,)),
                       p["attn"]["wo"])
        x = _res(cfg, x, a)
        h2 = _norm3(p["ln2"], x, cfg.norm_eps)
        return _res(cfg, x, _mlp(p["mlp"], h2)), None, aux

    if kind == "dec":                             # enc-dec decoder layer
        h = _norm3(p["ln1"], x, cfg.norm_eps)
        kv_cache = (None if cache is None else
                    {k: cache[k] for k in ("k", "v", "pos_ids", "length")})
        a, kv_cache = A.self_attention(p["attn"], cfg, h, ctx.positions,
                                       window, None, kv_cache, ctx.mode,
                                       page_tbl=ctx.page_tbl)
        x = _res(cfg, x, a)
        hx = _norm3(p["ln_x"], x, cfg.norm_eps)
        if ctx.mode in ("train", "prefill") and ctx.memory is not None:
            xc = A.build_cross_cache(p["xattn"], cfg, ctx.memory)
        else:
            xc = {"k": cache["xk"], "v": cache["xv"]}
        a2 = A.cross_attention(p["xattn"], cfg, hx, xc)
        x = _res(cfg, x, a2)
        h2 = _norm3(p["ln2"], x, cfg.norm_eps)
        x = _res(cfg, x, _mlp(p["mlp"], h2))
        new_cache = None
        if kv_cache is not None:
            new_cache = dict(kv_cache)
            new_cache["xk"], new_cache["xv"] = xc["k"], xc["v"]
        return x, new_cache, aux

    raise ValueError(f"unknown block kind {kind!r}")
