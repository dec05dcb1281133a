"""Attention: GQA/MHA, MLA (DeepSeek), sliding-window/chunked local,
cross-attention; unified KV-cache pytree for serving.

Design notes
------------
* Head padding for sharding: the production mesh has a 16-way ``model``
  axis; configs whose q/kv head counts don't divide it are padded
  (``cfg.*_padded``). Padded q heads get zero wq columns + zero wo rows, so
  outputs are bit-identical to the unpadded model. KV heads are duplicated
  when the pad factor is integral (balanced cache layout), else zero-padded.
  A static ``kv_index`` map (q head -> kv head) keeps GQA math exact under
  any padding combination.
* KV cache: ``{"k","v": (B, W, Hkv, hd), "pos_ids": (B, W) int32,
  "length": (B,) int32}``; W = min(max_len, sliding_window). Ring buffer for
  windowed attention; ``pos_ids`` (-1 = empty) drives masking, so windowed,
  chunked, and full attention share one decode path.
* Keys are stored rotated (RoPE applied at write time) — standard practice;
  ring-buffer eviction then needs no re-rotation.
* Long-sequence forward uses a two-level flash-style scan (q chunks x kv
  chunks, running softmax) to keep activation memory O(chunk^2), which is
  what makes the 32k-prefill dry-runs fit in 16 GB HBM.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.sharding import shard

NEG_INF = -1e9
DIRECT_ATTN_MAX_SEQ = 2048     # above this, use the flash-style scan
Q_CHUNK = 512
KV_CHUNK = 1024


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def _kv_index_map(n_q: int, n_kv: int, n_q_pad: int, n_kv_pad: int) -> np.ndarray:
    """Static map q-head -> kv-head honoring the original GQA grouping.

    When only KV heads are padded (consecutive-duplicate layout from
    attn_init), the map is the uniform divide i // (n_q_pad // n_kv_pad):
    shard-aligned (q head i and its kv head land on the same model-axis
    shard) and expressible as a local reshape — see uniform_gqa_group().
    """
    group = n_q // n_kv
    dup = n_kv_pad // n_kv if n_kv_pad % n_kv == 0 else 1
    if n_q_pad == n_q and dup > 1 and n_q_pad % n_kv_pad == 0:
        gp = n_q_pad // n_kv_pad
        idx = (np.arange(n_q_pad) // gp).astype(np.int32)
        # correctness: padded kv c is a copy of orig kv c // dup
        assert all((idx[i] // dup) == (i // group) for i in range(n_q))
        return idx
    idx = np.zeros((n_q_pad,), dtype=np.int32)
    for i in range(n_q):
        orig_kv = i // group
        idx[i] = orig_kv * dup + (i % dup if dup > 1 else 0)
    return idx


def uniform_gqa_group(cfg) -> Optional[int]:
    """Group size when the q->kv map is the uniform divide (grouped-einsum
    attention, no head-expansion gather); None otherwise."""
    n_q, n_kv = cfg.n_heads, cfg.n_kv_heads
    n_qp, n_kvp = cfg.n_heads_padded, cfg.n_kv_heads_padded
    if n_qp % n_kvp:
        return None
    idx = _kv_index_map(n_q, n_kv, n_qp, n_kvp)
    gp = n_qp // n_kvp
    if np.array_equal(idx, np.arange(n_qp) // gp):
        return gp
    return None


def attn_init(key, cfg, d_in: Optional[int] = None, qk_norm: bool = False) -> Dict:
    """Self/cross attention params. ``d_in`` overrides the input width
    (Zamba2 shared block takes concat(h, emb) = 2*d_model)."""
    dt = L.dtype_of(cfg)
    d = d_in if d_in is not None else cfg.d_model
    hd = cfg.head_dim_
    n_q, n_kv = cfg.n_heads, cfg.n_kv_heads
    n_qp, n_kvp = cfg.n_heads_padded, cfg.n_kv_heads_padded
    ks = jax.random.split(key, 6)
    wq = L.dense_init(ks[0], d, n_qp * hd, dt)
    wk = L.dense_init(ks[1], d, n_kv * hd, dt)
    wv = L.dense_init(ks[2], d, n_kv * hd, dt)
    wo = L.dense_init(ks[3], n_qp * hd, cfg.d_model, dt)
    # zero the padded q heads (columns of wq, rows of wo)
    if n_qp > n_q:
        wq = wq.at[:, n_q * hd:].set(0)
        wo = wo.at[n_q * hd:, :].set(0)
    if n_kvp > n_kv:
        if n_kvp % n_kv == 0:
            dup = n_kvp // n_kv
            wk = jnp.repeat(wk.reshape(d, n_kv, hd), dup, axis=1).reshape(d, -1)
            wv = jnp.repeat(wv.reshape(d, n_kv, hd), dup, axis=1).reshape(d, -1)
        else:
            pad = (n_kvp - n_kv) * hd
            wk = jnp.pad(wk, ((0, 0), (0, pad)))
            wv = jnp.pad(wv, ((0, 0), (0, pad)))
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if qk_norm:
        p["q_norm"] = L.rmsnorm_init(hd, dt)
        p["k_norm"] = L.rmsnorm_init(hd, dt)
    return p


def mla_init(key, cfg) -> Dict:
    m = cfg.mla
    dt = L.dtype_of(cfg)
    d = cfg.d_model
    H = cfg.n_heads_padded
    ks = jax.random.split(key, 7)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:           # one query projection (V2-Lite)
        p = {"w_q": L.dense_init(ks[0], d, H * qk_head, dt)}
        q_up = "w_q"
    else:
        p = {"w_dq": L.dense_init(ks[0], d, m.q_lora_rank, dt),
             "q_norm": L.rmsnorm_init(m.q_lora_rank, dt),
             "w_uq": L.dense_init(ks[1], m.q_lora_rank, H * qk_head, dt)}
        q_up = "w_uq"
    p.update({
        "w_dkv": L.dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dt),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, dt),
        "w_uk": L.dense_init(ks[3], m.kv_lora_rank, H * m.qk_nope_head_dim, dt),
        "w_uv": L.dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim, dt),
        "wo": L.dense_init(ks[5], H * m.v_head_dim, d, dt),
    })
    nH = cfg.n_heads
    if H > nH:
        p[q_up] = p[q_up].at[:, nH * qk_head:].set(0)
        p["w_uk"] = p["w_uk"].at[:, nH * m.qk_nope_head_dim:].set(0)
        p["w_uv"] = p["w_uv"].at[:, nH * m.v_head_dim:].set(0)
        p["wo"] = p["wo"].at[nH * m.v_head_dim:, :].set(0)
    return p


# --------------------------------------------------------------------------
# masking
# --------------------------------------------------------------------------


def self_attn_bias(q_pos: jax.Array, k_pos: jax.Array,
                   window: Optional[int], chunk: Optional[int]) -> jax.Array:
    """(..., Sq, Sk) additive bias. k_pos == -1 marks empty cache slots."""
    qp = q_pos[..., :, None].astype(jnp.int32)
    kp = k_pos[..., None, :].astype(jnp.int32)
    ok = (kp >= 0) & (kp <= qp)
    if window is not None:
        ok &= kp > qp - window
    if chunk is not None:
        ok &= (kp // chunk) == (qp // chunk)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# --------------------------------------------------------------------------
# core attention math (reference path; Pallas kernels in repro.kernels)
# --------------------------------------------------------------------------


def _direct_attention(q, k, v, bias, scale=None):
    """q: (B,Sq,H,hd), k/v: (B,Sk,H,hd), bias: (B,1|H,Sq,Sk) -> (B,Sq,H,hd).
    ``scale`` defaults to hd^-0.5."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = scores + bias
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _flash_attention(q, k, v, q_pos, k_pos, window, chunk, scale=None):
    """Two-level running-softmax scan; O(Q_CHUNK*KV_CHUNK) score memory.

    hd (q/k dim) may differ from hd_v (MLA: 192 vs 128).
    """
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    nq = -(-Sq // Q_CHUNK)
    nk = -(-Sk // KV_CHUNK)
    Sq_p, Sk_p = nq * Q_CHUNK, nk * KV_CHUNK
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    kp_ = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, ((0, 0), (0, Sq_p - Sq)), constant_values=0)
    kpos = jnp.pad(k_pos, ((0, 0), (0, Sk_p - Sk)), constant_values=-1)

    def blkshard(x):
        # keep batch/head shardings pinned through the chunk loops — GSPMD
        # propagation through lax.map/scan otherwise degrades to replicated
        # (EXPERIMENTS.md SSPerf H1 iter 3: a replicated-batch all-reduce)
        return shard(x, None, "batch", None, "heads", None)

    q_blocks = blkshard(jnp.moveaxis(qp.reshape(B, nq, Q_CHUNK, H, hd), 1, 0))
    qpos_blocks = jnp.moveaxis(qpos.reshape(B, nq, Q_CHUNK), 1, 0)
    k_blocks = blkshard(jnp.moveaxis(kp_.reshape(B, nk, KV_CHUNK, H, hd), 1, 0))
    v_blocks = blkshard(jnp.moveaxis(vp.reshape(B, nk, KV_CHUNK, H, hd_v), 1, 0))
    kpos_blocks = jnp.moveaxis(kpos.reshape(B, nk, KV_CHUNK), 1, 0)

    def per_q_block(qb, qposb):
        # qb: (B, Qc, H, hd)
        def kv_step(carry, inp):
            m, l, acc = carry
            kb, vb, kposb = inp
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32) * scale
            s = shard(s, "batch", "heads", None, None)
            s = s + self_attn_bias(qposb, kposb, window, chunk)[:, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qb.dtype), vb).astype(jnp.float32)
            acc_new = shard(acc_new, "batch", "heads", None, None)
            return (m_new, l_new, acc_new), None

        m0 = shard(jnp.full((B, H, Q_CHUNK), -jnp.inf, jnp.float32),
                   "batch", "heads", None)
        l0 = shard(jnp.zeros((B, H, Q_CHUNK), jnp.float32),
                   "batch", "heads", None)
        a0 = shard(jnp.zeros((B, H, Q_CHUNK, hd_v), jnp.float32),
                   "batch", "heads", None, None)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (k_blocks, v_blocks, kpos_blocks))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)   # (B, Qc, H, hd)

    out_blocks = jax.lax.map(lambda args: per_q_block(*args),
                             (q_blocks, qpos_blocks))
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, Sq_p, H, hd_v)
    return shard(out[:, :Sq], "batch", "seq", "heads", None)


def attention_core(q, k, v, q_pos, k_pos, window=None, chunk=None,
                   scale=None):
    """Dispatch between direct and flash-scan attention (same math)."""
    Sk = k.shape[1]
    if Sk <= DIRECT_ATTN_MAX_SEQ:
        bias = self_attn_bias(q_pos, k_pos, window, chunk)[:, None]
        return _direct_attention(q, k, v, bias, scale)
    return _flash_attention(q, k, v, q_pos, k_pos, window, chunk, scale)


# --------------------------------------------------------------------------
# KV cache
# --------------------------------------------------------------------------


def cache_width(cfg, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def init_kv_cache(cfg, batch: int, max_len: int, dtype=None) -> Dict:
    dt = dtype or L.dtype_of(cfg)
    W = cache_width(cfg, max_len)
    H, hd = cfg.n_kv_heads_padded, cfg.head_dim_
    return {
        "k": jnp.zeros((batch, W, H, hd), dt),
        "v": jnp.zeros((batch, W, H, hd), dt),
        "pos_ids": jnp.full((batch, W), -1, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _ring_slots(length: jax.Array, W: int) -> jax.Array:
    return jnp.mod(length, W)


def prefill_write_cache(cache: Dict, k: jax.Array, v: jax.Array,
                        pos_ids: jax.Array) -> Dict:
    """Write a full prompt (possibly longer than the ring) into the cache.

    For token j the ring slot is j % W; when S > W only the last W tokens
    survive. Computed as a deterministic gather (no duplicate-scatter
    ambiguity).
    """
    B, S = k.shape[0], k.shape[1]
    W = cache["k"].shape[1]
    if S <= W:
        newk = cache["k"].at[:, :S].set(k)
        newv = cache["v"].at[:, :S].set(v)
        newpos = cache["pos_ids"].at[:, :S].set(pos_ids)
    else:
        s = jnp.arange(W)
        j = s + W * ((S - 1 - s) // W)          # latest token landing in slot s
        newk = jnp.take(k, j, axis=1)
        newv = jnp.take(v, j, axis=1)
        newpos = jnp.take(pos_ids, j, axis=1)
    length = jnp.max(pos_ids, axis=1) + 1
    return {"k": newk, "v": newv, "pos_ids": newpos, "length": length}


def decode_write_cache(cache: Dict, k1: jax.Array, v1: jax.Array) -> Dict:
    """Append one token per sequence. k1/v1: (B, 1, Hkv, hd)."""
    B = k1.shape[0]
    W = cache["k"].shape[1]
    slot = _ring_slots(cache["length"], W)
    bidx = jnp.arange(B)
    return {
        "k": cache["k"].at[bidx, slot].set(k1[:, 0]),
        "v": cache["v"].at[bidx, slot].set(v1[:, 0]),
        "pos_ids": cache["pos_ids"].at[bidx, slot].set(cache["length"]),
        "length": cache["length"] + 1,
    }


# ---- paged KV pool (serving/paged.py owns the allocator; the layout ops
# ---- live here with the rest of the cache code) ---------------------------
#
# Paged cache leaf group: {"k_pages","v_pages": (Hkv, num_pages+1, ps, *),
# "pos_ids": (B, W) LOGICAL (-1 empty), "length": (B,)}. Head-major, so a
# row is addressed by (page, offset) and a (Hkv, (num_pages+1)*ps, *)
# reshape is the flat pool that decode scores in place. The last physical
# page is a TRASH page: writes by slots with no mapped page (finished slots
# coasting inside a fused chunk) land there, and unmapped logical pages
# gather from there — always masked because the logical pos_ids row is -1.
#
# Inside the model's layer scan a group's page leaves are the whole STACKED
# pool ([L,] Hkv, num_pages+1, ps, *), carried from layer to layer, and the
# group holds its layer's index under ``PAGE_LAYER``: writes scatter the
# new rows straight into [layer, :, page, offset] and reads take the layer
# where it lies, so no layer's pages are sliced out and written back.

PAGES = "_pages"         # suffix of a paged group's page leaves
PAGE_LAYER = "layer"     # a scanned group's index into its stacked leaves


def page_leaf_names(group) -> list:
    """The page leaves of a paged leaf group, in the group's order; [] for
    anything else (a contiguous cache group, a recurrent state, None)."""
    if not isinstance(group, dict):
        return []
    return [k for k in group if k.endswith(PAGES)]


def _page_dims(cache: Dict, name: str) -> Tuple[int, int]:
    """(pages incl. trash, page size) of one page leaf, stacked or not."""
    return cache[name].shape[-3], cache[name].shape[-2]


def _layer_pages(cache: Dict, name: str) -> jax.Array:
    """One layer's (Hkv, P+1, ps, *) page leaf: the leaf itself, or the
    group's layer of the stacked pool (a dynamic index the consuming op
    reads through)."""
    leaf = cache[name]
    layer = cache.get(PAGE_LAYER)
    return leaf if layer is None else leaf[layer]


def _write_rows(cache: Dict, name: str, pg: jax.Array, off: jax.Array,
                rows: jax.Array) -> jax.Array:
    """Scatter ``rows`` (*idx, Hkv, width) into page leaf ``name`` at
    physical (page, offset) pairs ``pg``/``off`` (*idx): one scatter into
    the leaf as it lies, the stacked pool at the group's layer included.

    Into the stacked pool, one slot's row (decode, ``pg`` of shape (B,))
    is written as Hkv rows of ``width`` (the head an index, like the
    page), and a chunk's rows (``pg`` (B, S)) each as one (Hkv, width)
    window: the forms under which XLA's TPU scatter updates the pool in
    its own layout, where the other form makes it copy the pool to
    another layout first (AOT compile for v5e; PERF.md §3)."""
    leaf = cache[name]
    rows = rows.astype(leaf.dtype)
    layer = cache.get(PAGE_LAYER)
    if layer is None:                   # indexed shape (Hkv, *idx, width)
        return leaf.at[:, pg, off].set(jnp.moveaxis(rows, -2, 0))
    if pg.ndim == 1:                    # indexed shape (B, Hkv, width)
        h = jnp.arange(leaf.shape[1])
        return leaf.at[layer, h, pg[:, None], off[:, None]].set(rows)
    return leaf.at[layer, :, pg, off].set(rows)   # (B, S, Hkv, width)


def _flat_rows(pages: jax.Array) -> jax.Array:
    """(Hkv, P+1, ps, hd) -> the flat pool (Hkv, (P+1)*ps, hd)."""
    H, P1, ps, hd = pages.shape
    return pages.reshape(H, P1 * ps, hd)


def paged_decode_write(cache: Dict, tbl: jax.Array, k1: jax.Array,
                       v1: jax.Array) -> Dict:
    """Append one token per slot through the (B, max_pages) block table."""
    return _paged_decode_write(cache, tbl, {"k_pages": k1, "v_pages": v1})


def _paged_decode_write(cache: Dict, tbl: jax.Array, new: Dict) -> Dict:
    """``paged_decode_write`` for any page leaves: ``new`` maps each page
    leaf of the group (``k_pages``/``v_pages``, or the latent's
    ``ckv_pages``/``krope_pages``) to its (B, 1, Hkv, width) row."""
    t = cache["length"]
    P1, ps = _page_dims(cache, next(iter(new)))
    trash = P1 - 1
    B = t.shape[0]
    M = tbl.shape[1]
    W = cache["pos_ids"].shape[1]
    bidx = jnp.arange(B)
    lp = t // ps
    pg = tbl[bidx, jnp.clip(lp, 0, M - 1)]
    pg = jnp.where((pg < 0) | (lp >= M), trash, pg)  # physical page (B,)
    t_c = jnp.clip(t, 0, W - 1)
    return {
        **cache,
        **{n: _write_rows(cache, n, pg, t % ps, r[:, 0])
           for n, r in new.items()},
        "pos_ids": cache["pos_ids"].at[bidx, t_c].set(t),
        "length": t + 1,
    }


def paged_chunk_write(cache: Dict, tbl: jax.Array, k: jax.Array,
                      v: jax.Array, positions: jax.Array) -> Dict:
    """Append one prompt chunk per slot through the block table.

    k/v: (B, S, Hkv, hd); positions: (B, S) LOGICAL (-1 = pad). The pages
    covering the chunk must already be mapped (``alloc_chunk_pages``). Pad
    tokens and rows past the table land in the trash page, and ``pos_ids``
    is only written at valid positions, so pads never unmask — the same
    invariant as the single-token decode write, extended to S tokens.
    """
    return _paged_chunk_write(cache, tbl, {"k_pages": k, "v_pages": v},
                              positions)


def _paged_chunk_write(cache: Dict, tbl: jax.Array, new: Dict,
                       positions: jax.Array) -> Dict:
    """``paged_chunk_write`` for any page leaves, as ``_paged_decode_write``
    (``new``: page leaf -> (B, S, Hkv, width))."""
    t = positions
    P1, ps = _page_dims(cache, next(iter(new)))
    trash = P1 - 1
    B, S = t.shape
    M = tbl.shape[1]
    W = cache["pos_ids"].shape[1]
    bidx = jnp.arange(B)[:, None]
    valid = t >= 0
    lp = jnp.where(valid, t // ps, M)                # pads -> out of range
    pg = tbl[bidx, jnp.clip(lp, 0, M - 1)]
    pg = jnp.where(valid & (lp < M) & (pg >= 0), pg, trash)   # (B, S)
    off = jnp.where(valid, t % ps, 0)
    col = jnp.where(valid, jnp.clip(t, 0, W - 1), W)  # W = dropped
    return {
        **cache,
        **{n: _write_rows(cache, n, pg, off, r) for n, r in new.items()},
        "pos_ids": cache["pos_ids"].at[bidx, col].set(t, mode="drop"),
        "length": cache["length"] + valid.sum(axis=1).astype(jnp.int32),
    }


def serving_cache_axes(leaf: jax.Array) -> Tuple[Optional[str], ...]:
    """Logical sharding axes for one leaf of a SHARD-STACKED serving state
    tree (cache pools, allocator arrays, slot state, token buffers): the
    leading axis is the fleet axis ``"shard"``; every other dim is
    shard-local. This is the whole sharding contract of the mesh-sharded
    engine — KV heads, pages, and batch rows are never split WITHIN a
    shard, because the decode/chunked kernels' (B, Hkv, pages) grids and
    the allocator's LIFO free stack both assume whole device-local pools.
    Resolved to mesh axes via repro.sharding.rules.SERVING_RULES."""
    return ("shard",) + (None,) * (leaf.ndim - 1)


def copy_page_rows(pages: jax.Array, src_pg: jax.Array,
                   dst_pg: jax.Array) -> jax.Array:
    """Copy whole pages ``src_pg[i] -> dst_pg[i]`` inside one pool leaf —
    the data half of copy-on-write (serving/paged.py owns the refcount
    half). pages: ([R,] Hkv, P+1, ps, hd); src_pg/dst_pg: (n,) physical
    ids, dst < 0 = skip (the write is dropped past the pool edge). A page
    is the CoW unit: the copy is one gather + one scatter per leaf, no
    row-level bookkeeping."""
    P1 = pages.shape[-3]
    src = jnp.take(pages, jnp.clip(src_pg, 0, P1 - 1), axis=-3)
    dst = jnp.where(dst_pg < 0, P1, dst_pg)             # P1 = out of bounds
    if pages.ndim == 4:
        return pages.at[:, dst].set(src, mode="drop")
    return pages.at[:, :, dst].set(src, mode="drop")


def gather_pages_hb(pages: jax.Array, tbl: jax.Array,
                    layer: Optional[jax.Array] = None) -> jax.Array:
    """Head-major logical view (Hkv, B, W, hd) of a page pool, as ONE
    page-granular gather with no transpose — the decode hot path's layout
    (the attention einsums contract it in place). The Pallas path instead
    chases the block table inside the kernel (kernels/decode_attention.py).
    With ``layer``, ``pages`` is the stacked (L, Hkv, P+1, ps, hd) pool and
    the gather reads that layer's pages from it directly.
    """
    P1, ps, hd = pages.shape[-3:]
    safe = jnp.where(tbl < 0, P1 - 1, tbl)           # (B, M)
    if layer is None:
        g = pages[:, safe]                           # (H, B, M, ps, hd)
    else:
        H = pages.shape[1]
        idx = jnp.stack([jnp.broadcast_to(layer, safe.shape), safe], -1)
        g = jax.lax.gather(
            pages, idx.astype(jnp.int32),
            jax.lax.GatherDimensionNumbers(
                offset_dims=(0, 3, 4), collapsed_slice_dims=(0, 2),
                start_index_map=(0, 2)),
            slice_sizes=(1, H, 1, ps, hd),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return g.reshape(g.shape[0], tbl.shape[0], tbl.shape[1] * ps, hd)


def gather_pages(pages: jax.Array, tbl: jax.Array) -> jax.Array:
    """Logical (B, W, Hkv, hd) cache view of a page pool — the layout of
    the contiguous cache leaf, for reference/eq checks."""
    return jnp.moveaxis(gather_pages_hb(pages, tbl), 0, 2)


def _pool_key_positions(tbl: jax.Array, pos_ids: jax.Array,
                        n_pages: int) -> jax.Array:
    """(B, (P+1)*ps) logical position of every pool row as each slot sees
    it, -1 where the row's page is not in that slot's block table.

    tbl: (B, M); pos_ids: (B, M*ps) LOGICAL; n_pages = P+1, trash included.
    One scatter inverts the table (physical page -> the slot's logical
    page, -1 unmapped; no table maps the trash page), then one
    page-granular gather reads the slot's logical positions through it.
    A page shared by several slots maps into each through that slot's own
    row; stale rows of a recycled page and rows past a slot's length read
    ``pos_ids`` -1.
    """
    B, M = tbl.shape
    ps = pos_ids.shape[1] // M
    slot = jnp.arange(B)[:, None]
    logical = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (B, M))
    dst = jnp.where(tbl < 0, n_pages, tbl)           # unmapped -> dropped
    inv = jnp.full((B, n_pages), -1, jnp.int32).at[slot, dst].set(
        logical, mode="drop")
    kpos = pos_ids.reshape(B, M, ps)[slot, jnp.maximum(inv, 0)]
    kpos = jnp.where(inv[:, :, None] >= 0, kpos, -1)  # (B, P+1, ps)
    return kpos.reshape(B, n_pages * ps)


def _pool_decode_attention(qs, cache, page_tbl, positions, window, chunk,
                           gp, keys=("k_pages",), values="v_pages",
                           scale=None):
    """Decode attention of one query per slot against the flat page pool,
    masked by the block table: per kv head one (B*g) x R score matmul and
    one value matmul, with no per-slot copy of the pool. The same softmax
    over the same keys as the gathered view (masked rows add exact zeros),
    at the same precision: bf16 operands, f32 scores and accumulation.

    ``qs`` holds one (B, 1, Hq, width) query part per page leaf in
    ``keys``; the scores are the sum of their products (MLA's absorbed
    decode scores its latent query against ``ckv_pages`` and its rope
    query against ``krope_pages``, one latent kv head of group Hq, and
    sums values from ``ckv_pages``). ``scale`` defaults to width^-0.5 of
    a single query part. A scanned group's leaves are read at its layer
    of the stacked pool."""
    kfs = [_flat_rows(_layer_pages(cache, k)) for k in keys]
    vf = _flat_rows(_layer_pages(cache, values))
    kpos = _pool_key_positions(page_tbl, cache["pos_ids"],
                               _page_dims(cache, keys[0])[0])
    bias = self_attn_bias(positions, kpos, window, chunk)   # (B, 1, R)
    B, _, Hq, hd = qs[0].shape
    n_kv = vf.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    sc = None
    for q, kf in zip(qs, kfs):
        # (Hkv, B*g, width): the queries of one kv head are one matmul's rows
        qk = jnp.swapaxes(q.reshape(B, n_kv, gp, q.shape[-1]), 0, 1).reshape(
            n_kv, B * gp, q.shape[-1])
        part = jnp.einsum("kqd,krd->kqr", qk, kf,
                          preferred_element_type=jnp.float32)
        sc = part if sc is None else sc + part
    sc = sc * scale
    sc = sc.reshape(n_kv, B, gp, -1) + bias[None]
    w = jax.nn.softmax(sc, axis=-1).astype(qs[0].dtype)
    out = jnp.einsum("kqr,krd->kqd", w.reshape(n_kv, B * gp, -1), vf)
    hd_v = vf.shape[-1]
    return jnp.swapaxes(out.reshape(n_kv, B, gp, hd_v), 0, 1).reshape(
        B, 1, Hq, hd_v)


# --------------------------------------------------------------------------
# GQA self-attention block
# --------------------------------------------------------------------------


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _qkv(p, cfg, x, positions, qk_norm=False):
    hd = cfg.head_dim_
    n_qp, n_kvp = cfg.n_heads_padded, cfg.n_kv_heads_padded
    q = _split_heads(jnp.einsum("...d,dh->...h", x, p["wq"]), n_qp, hd)
    k = _split_heads(jnp.einsum("...d,dh->...h", x, p["wk"]), n_kvp, hd)
    v = _split_heads(jnp.einsum("...d,dh->...h", x, p["wv"]), n_kvp, hd)
    if qk_norm and "q_norm" in p:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = L.apply_rope(q, positions, cfg.rotary_pct, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rotary_pct, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _expand_kv(cfg, k):
    idx = jnp.asarray(_kv_index_map(cfg.n_heads, cfg.n_kv_heads,
                                    cfg.n_heads_padded, cfg.n_kv_heads_padded))
    return jnp.take(k, idx, axis=2)


def self_attention(p: Dict, cfg, x: jax.Array, positions: jax.Array,
                   layer_window: Optional[int], layer_chunk: Optional[int],
                   cache: Optional[Dict] = None, mode: str = "train",
                   page_tbl: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, Optional[Dict]]:
    """mode: 'train' (no cache) | 'prefill' (build cache) | 'decode' (1 tok)
    | 'chunk' (S-token prompt chunk appended to a paged cache).

    A decode cache may be either the contiguous per-slot layout or a paged
    leaf group (``k_pages`` present), in which case ``page_tbl`` maps the
    slot's logical pages to the shared pool. Both layouts feed the SAME
    attention math on masked logical positions, so they are token-for-token
    equivalent (tests/test_paged_parity.py pins this). Chunk mode is the
    paged decode path widened to S queries: the chunk's keys are written
    first, then the queries score the slot's whole logical history — the
    ``k_pos <= q_pos`` mask gives in-chunk causality for free.

    Named scopes for the profiler (metadata only): ``attn_proj`` holds the
    q/k/v and output projections; ``attn_decode`` (decode mode) and
    ``attn_prefill`` (chunk mode) hold everything between them — the KV
    write, the cache read, the scores, the softmax and the value sum.
    """
    with jax.named_scope("attn_proj"):
        q, k, v = _qkv(p, cfg, x, positions, qk_norm="q_norm" in p)
    use_kernel = cfg.attn_impl != "ref"
    if use_kernel and uniform_gqa_group(cfg) is None:
        raise ValueError(
            f"{cfg.name}: attn_impl={cfg.attn_impl!r} needs a uniform GQA "
            "q->kv head map, which this head padding does not give; use "
            "attn_impl='ref'")
    if mode in ("decode", "chunk"):
        with jax.named_scope(_CACHED_SCOPE[mode]):
            out, cache = _cached_attention(cfg, q, k, v, positions,
                                           layer_window, layer_chunk, cache,
                                           mode, page_tbl, use_kernel)
    else:
        if mode == "prefill":
            cache = prefill_write_cache(cache, k, v, positions)
        if use_kernel:
            from repro.kernels import ops as KOPS
            out = jnp.moveaxis(
                KOPS.flash_attention(
                    jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                    jnp.moveaxis(v, 1, 2), positions, positions,
                    window=layer_window, chunk=layer_chunk,
                    impl=cfg.attn_impl), 1, 2)
        else:
            kk = _expand_kv(cfg, k)
            vv = _expand_kv(cfg, v)
            out = attention_core(q, kk, vv, positions, positions,
                                 layer_window, layer_chunk)
    with jax.named_scope("attn_proj"):
        out = shard(out, "batch", "seq", "heads", None)
        flat = out.reshape(out.shape[:-2] + (-1,))
        y = jnp.einsum("...h,hd->...d", flat, p["wo"])
    return y, cache


_CACHED_SCOPE = {"decode": "attn_decode", "chunk": "attn_prefill"}


def _cached_attention(cfg, q, k, v, positions, layer_window, layer_chunk,
                      cache, mode, page_tbl, use_kernel):
    """Decode or chunk mode of ``self_attention`` between the projections:
    write the new keys and values, then attend over the slot's history.
    Returns (out (B, S, Hq, hd), cache)."""
    assert cache is not None
    paged = "k_pages" in cache
    if mode == "chunk":
        assert paged and page_tbl is not None, \
            "chunked prefill needs a paged cache + block table"
        cache = paged_chunk_write(cache, page_tbl, k, v, positions)
    elif paged:
        assert page_tbl is not None, "paged decode cache needs page_tbl"
        cache = paged_decode_write(cache, page_tbl, k, v)
    else:
        cache = decode_write_cache(cache, k, v)
    gp = uniform_gqa_group(cfg)
    if use_kernel:
        from repro.kernels import ops as KOPS
        if mode == "chunk":
            # (B, Hkv, max_pages) GQA grid with the whole (group, S)
            # query chunk per program — one HBM read per page per
            # group, independent of chunk size
            out = jnp.moveaxis(
                KOPS.chunked_prefill_attention(
                    jnp.moveaxis(q, 1, 2), _layer_pages(cache, "k_pages"),
                    _layer_pages(cache, "v_pages"), page_tbl, positions,
                    cache["pos_ids"], window=layer_window,
                    chunk=layer_chunk, impl=cfg.attn_impl),
                1, 2)                           # (B, S, Hq, hd_v)
        elif paged:
            # same (B, Hkv, nk) grid; the scalar-prefetched block table
            # redirects each program's page DMA — still one HBM read
            # per (batch, kv head, logical page)
            out = KOPS.paged_decode_attention(
                q[:, 0], _layer_pages(cache, "k_pages"),
                _layer_pages(cache, "v_pages"), page_tbl,
                positions[:, 0], cache["pos_ids"],
                window=layer_window, chunk=layer_chunk,
                impl=cfg.attn_impl)[:, None]    # (B, 1, Hq, hd)
        else:
            # (B, Hkv, W, hd) is the grouped-decode kernel's native
            # layout: its (B, Hkv, nk) grid reads each KV block once
            # per GQA group
            out = KOPS.decode_attention(
                q[:, 0],                        # (B, Hq, hd)
                jnp.moveaxis(cache["k"], 1, 2),  # (B, Hkv, W, hd)
                jnp.moveaxis(cache["v"], 1, 2),
                positions[:, 0], cache["pos_ids"],
                window=layer_window, chunk=layer_chunk,
                impl=cfg.attn_impl)[:, None]    # (B, 1, Hq, hd)
    elif paged and mode == "decode" and gp is not None:
        # one query a slot: score the pool in place, masked by the block
        # table, instead of gathering every slot's W rows out of it
        out = _pool_decode_attention((q,), cache, page_tbl, positions,
                                     layer_window, layer_chunk, gp)
    else:
        bias = self_attn_bias(positions, cache["pos_ids"],
                              layer_window, layer_chunk)[:, None]
        if gp is not None:
            # grouped attention: contract against the shard-local kv
            # head directly — no head-expansion gather of the cache
            # (perf: the take-based expansion all-gathers the cache
            # over the model axis; EXPERIMENTS.md SSPerf H3). Same
            # math on either layout; only the cache einsum signature
            # differs: a paged pool (chunk mode here) is gathered
            # page-granular into the head-major (Hkv, B, W, hd) view
            # ("kbsd") and contracted in place — garbage rows carry
            # logical pos -1 and mask to exactly-zero softmax weight, so
            # this is bit-identical to the contiguous slot pool ("bskd").
            if paged:
                layer = cache.get(PAGE_LAYER)
                kk = gather_pages_hb(cache["k_pages"], page_tbl, layer)
                vv = gather_pages_hb(cache["v_pages"], page_tbl, layer)
                kv_layout, n_kv = "kbsd", kk.shape[0]
            else:
                kk = shard(cache["k"], "batch", "kv_seq", "kv_heads",
                           None)
                vv = shard(cache["v"], "batch", "kv_seq", "kv_heads",
                           None)
                kv_layout, n_kv = "bskd", kk.shape[2]
            B_, Sq_ = q.shape[0], q.shape[1]
            hd = q.shape[-1]
            qg = q.reshape(B_, Sq_, n_kv, gp, hd)
            scale = 1.0 / math.sqrt(hd)
            # bf16 x bf16 -> f32 accumulation in the dot itself (MXU-
            # native; avoids materializing an f32 copy of the 32k
            # cache — H3 iter 3)
            sc = jnp.einsum(f"bqkgd,{kv_layout}->bkgqs", qg, kk,
                            preferred_element_type=jnp.float32) * scale
            sc = sc + bias[:, :, None]
            w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
            out = jnp.einsum(f"bkgqs,{kv_layout}->bqkgd", w, vv)
            out = out.reshape(B_, Sq_, -1, hd)
        else:
            if paged:
                ck = gather_pages(_layer_pages(cache, "k_pages"), page_tbl)
                cv = gather_pages(_layer_pages(cache, "v_pages"), page_tbl)
            else:
                ck, cv = cache["k"], cache["v"]
            kk = _expand_kv(cfg, ck)
            vv = _expand_kv(cfg, cv)
            kk = shard(kk, "batch", "kv_seq", "heads", None)
            vv = shard(vv, "batch", "kv_seq", "heads", None)
            out = _direct_attention(q, kk, vv, bias)
    return out, cache


# --------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# --------------------------------------------------------------------------


def init_mla_cache(cfg, batch: int, max_len: int, dtype=None) -> Dict:
    dt = dtype or L.dtype_of(cfg)
    m = cfg.mla
    W = cache_width(cfg, max_len)
    return {
        "ckv": jnp.zeros((batch, W, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, W, m.qk_rope_head_dim), dt),
        "pos_ids": jnp.full((batch, W), -1, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _mla_qkv_latent(p, cfg, x, positions):
    """Returns per-head q (nope+rope) and the shared latent k parts."""
    m = cfg.mla
    H = cfg.n_heads_padded
    if "w_q" in p:                      # no query down-projection
        q = jnp.einsum("...d,dh->...h", x, p["w_q"])
    else:
        cq = L.rmsnorm(p["q_norm"],
                       jnp.einsum("...d,dr->...r", x, p["w_dq"]),
                       cfg.norm_eps)
        q = jnp.einsum("...r,rh->...h", cq, p["w_uq"])
    q = q.reshape(q.shape[:-1] + (H, m.qk_nope_head_dim + m.qk_rope_head_dim))
    q = shard(q, "batch", "seq", "heads", None)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = L.apply_rope(q_rope, positions, 1.0, cfg.rope_theta,
                          cfg.rope_scaling)

    dkv = jnp.einsum("...d,dr->...r", x, p["w_dkv"])
    ckv = L.rmsnorm(p["kv_norm"], dkv[..., :m.kv_lora_rank], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:]
    # shared-rope key (one per token, broadcast over heads)
    k_rope = L.apply_rope(k_rope[..., None, :], positions, 1.0,
                          cfg.rope_theta, cfg.rope_scaling)[..., 0, :]
    return q_nope, q_rope, ckv, k_rope


def mla_softmax_scale(cfg) -> float:
    """(nope + rope)^-0.5, times YaRN's mscale(mscale_all_dim)^2 when the
    rope is scaled (DeepSeek-V2: 192^-0.5 x 1.2608^2 = 0.1147214)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


# the latent's page leaves: the normed latent and the roped key, each as
# one head of the page pool (1, P+1, page_size, width)
LATENT_PAGES = ("ckv_pages", "krope_pages")
MLA_KV_BLOCK = 1024      # history rows per step of the chunked-prefill loop


def _latent_rows(ckv, k_rope):
    """(B, S, width) latent and rope key -> the page leaves' new rows."""
    return {"ckv_pages": ckv[:, :, None, :],
            "krope_pages": k_rope[:, :, None, :]}


def _mla_chunk_attention(q_nope, q_rope, cache, tbl, positions, w_uk, w_uv,
                         scale, window):
    """Chunk-mode MLA over latent pages, in the expanded form: each block
    of MLA_KV_BLOCK history rows is gathered through the block table,
    expanded to per-head k_nope and v (``w_uk``, ``w_uv``), scored with
    the chunk's queries and folded into a running softmax. The loop stops
    at the block holding the chunk's last position, so a chunk costs the
    history it has, not ``max_len``.

    q_nope: (B, S, H, n); q_rope: (B, S, H, r); w_uk: (kvr, H, n); w_uv:
    (kvr, H, v). Returns (B, S, H, v). A block's rows are gathered from
    the page leaves as they lie (a scanned group's layer of the stacked
    pool included)."""
    P1, ps = _page_dims(cache, "ckv_pages")
    layer = cache.get(PAGE_LAYER)
    lead = (0,) if layer is None else (layer, 0)       # the one latent head
    B, S, H, _ = q_nope.shape
    vd = w_uv.shape[-1]
    W = cache["pos_ids"].shape[1]
    kb = math.gcd(W, MLA_KV_BLOCK)                     # a page multiple
    pb = kb // ps
    n_blk = jnp.clip((jnp.max(positions) + kb) // kb, 1, W // kb)

    def body(j, carry):
        m_, l_, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(tbl, j * pb, pb, axis=1)
        pages = jnp.where(pages < 0, P1 - 1, pages)    # unmapped -> trash
        at = lead + (pages[:, :, None], jnp.arange(ps))
        ck, kr = (cache[n][at].reshape(B, kb, -1)      # (B, kb, kvr|r)
                  for n in LATENT_PAGES)
        kpos = jax.lax.dynamic_slice_in_dim(cache["pos_ids"], j * kb, kb,
                                            axis=1)
        k_nope = jnp.einsum("bkr,rhn->bkhn", ck, w_uk)
        vv = jnp.einsum("bkr,rhv->bkhv", ck, w_uv)
        sc = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
        sc = sc + self_attn_bias(positions, kpos, window, None)[:, None]
        m_new = jnp.maximum(m_, sc.max(axis=-1))
        pw = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_ - m_new)
        l_new = l_ * corr + pw.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhv->bhqv", pw.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((B, H, S), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, S), jnp.float32),
            jnp.zeros((B, H, S, vd), jnp.float32))
    _, l_, acc = jax.lax.fori_loop(0, n_blk, body, init)
    out = acc / jnp.maximum(l_, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q_nope.dtype)


def _mla_flash_fused(q_nope, q_rope, ckv, k_rope, w_uk, w_uv,
                     q_pos, k_pos, window, scale):
    """Flash scan over kv chunks with the latent->per-head expansion fused
    into each chunk step (never materializes (B, S, H, nope+rope) keys).

    q_nope: (B,Sq,H,n); q_rope: (B,Sq,H,r); ckv: (B,Sk,kvr);
    k_rope: (B,Sk,r); w_uk: (kvr,H,n); w_uv: (kvr,H,v).
    """
    B, Sq, H, n = q_nope.shape
    r = q_rope.shape[-1]
    kvr = ckv.shape[-1]
    v_dim = w_uv.shape[-1]
    Sk = ckv.shape[1]
    nq = -(-Sq // Q_CHUNK)
    nk = -(-Sk // KV_CHUNK)
    Sq_p, Sk_p = nq * Q_CHUNK, nk * KV_CHUNK
    qn = jnp.pad(q_nope, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    qr = jnp.pad(q_rope, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    ck = jnp.pad(ckv, ((0, 0), (0, Sk_p - Sk), (0, 0)))
    kr = jnp.pad(k_rope, ((0, 0), (0, Sk_p - Sk), (0, 0)))
    qpos = jnp.pad(q_pos, ((0, 0), (0, Sq_p - Sq)), constant_values=0)
    kpos = jnp.pad(k_pos, ((0, 0), (0, Sk_p - Sk)), constant_values=-1)

    qn_b = jnp.moveaxis(qn.reshape(B, nq, Q_CHUNK, H, n), 1, 0)
    qr_b = jnp.moveaxis(qr.reshape(B, nq, Q_CHUNK, H, r), 1, 0)
    qpos_b = jnp.moveaxis(qpos.reshape(B, nq, Q_CHUNK), 1, 0)
    ck_b = jnp.moveaxis(ck.reshape(B, nk, KV_CHUNK, kvr), 1, 0)
    kr_b = jnp.moveaxis(kr.reshape(B, nk, KV_CHUNK, r), 1, 0)
    kpos_b = jnp.moveaxis(kpos.reshape(B, nk, KV_CHUNK), 1, 0)

    def per_q_block(qnb, qrb, qposb):
        def kv_step(carry, inp):
            m, l, acc = carry
            ckb, krb, kposb = inp
            # fused expansion: per-chunk K/V only (KV_CHUNK x H x n)
            k_nope = shard(jnp.einsum("bkr,rhn->bkhn", ckb, w_uk),
                           "batch", "seq", "heads", None)
            vv = shard(jnp.einsum("bkr,rhv->bkhv", ckb, w_uv),
                       "batch", "seq", "heads", None)
            s = (jnp.einsum("bqhn,bkhn->bhqk", qnb, k_nope)
                 + jnp.einsum("bqhr,bkr->bhqk", qrb, krb)
                 ).astype(jnp.float32) * scale
            s = s + self_attn_bias(qposb, kposb, window, None)[:, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            pw = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + pw.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhv->bhqv", pw.astype(vv.dtype), vv).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, Q_CHUNK), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, Q_CHUNK), jnp.float32)
        a0 = jnp.zeros((B, H, Q_CHUNK, v_dim), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (ck_b, kr_b, kpos_b))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q_nope.dtype)

    out_blocks = jax.lax.map(lambda a: per_q_block(*a), (qn_b, qr_b, qpos_b))
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, Sq_p, H, v_dim)
    return out[:, :Sq]


def mla_attention(p: Dict, cfg, x: jax.Array, positions: jax.Array,
                  layer_window: Optional[int],
                  cache: Optional[Dict] = None, mode: str = "train",
                  page_tbl: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, Optional[Dict]]:
    """Naive (expanded) path for train/prefill; absorbed path for decode.

    The absorbed decode computes scores in the 512-dim latent space
    directly against the cached ``ckv`` — this is what makes the MLA cache
    (576 B/token/layer in bf16) pay off at 500k context.

    A paged cache (``ckv_pages`` present: the latent leaf group of
    ``serving/paged.py``) serves decode and chunk mode through
    ``page_tbl``. Decode absorbs ``w_uk`` into the query and scores the
    whole pool in place (``_pool_decode_attention`` with one latent kv
    head, 576 wide for scores and 512 for values), then projects the
    latent value sum with ``w_uv``. Chunk mode writes the chunk's latent
    rows and attends in the expanded form over the history it has
    (``_mla_chunk_attention``): per key, the expanded form's scores and
    values cost 16 x (192 + 128) multiply-adds against the absorbed
    form's 16 x (576 + 512), and a chunk's queries outnumber the once-
    per-key expansion. Scopes as ``self_attention``: ``attn_proj`` for
    the projections, ``attn_decode`` / ``attn_prefill`` from the cache
    write through the value projection (the absorption products
    included).
    """
    m = cfg.mla
    H = cfg.n_heads_padded
    scale = mla_softmax_scale(cfg)
    with jax.named_scope("attn_proj"):
        q_nope, q_rope, ckv, k_rope = _mla_qkv_latent(p, cfg, x, positions)
    paged = cache is not None and "ckv_pages" in cache
    if paged and cfg.attn_impl != "ref":
        raise ValueError(
            f"{cfg.name}: attn_impl={cfg.attn_impl!r} has no kernel for "
            "latent (MLA) pages; use attn_impl='ref'")
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)

    if paged:
        assert mode in ("decode", "chunk") and page_tbl is not None, \
            "latent pages serve decode and chunk mode through page_tbl"
        with jax.named_scope(_CACHED_SCOPE[mode]):
            if mode == "decode":
                cache = _paged_decode_write(cache, page_tbl,
                                            _latent_rows(ckv, k_rope))
                q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
                o_lat = _pool_decode_attention(
                    (q_lat, q_rope), cache, page_tbl, positions,
                    layer_window, None, H, keys=LATENT_PAGES,
                    values="ckv_pages", scale=scale)
                out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
            else:
                cache = _paged_chunk_write(cache, page_tbl,
                                           _latent_rows(ckv, k_rope),
                                           positions)
                out = _mla_chunk_attention(q_nope, q_rope, cache, page_tbl,
                                           positions, w_uk, w_uv, scale,
                                           layer_window)
    elif mode == "decode":
        assert cache is not None
        B = x.shape[0]
        W = cache["ckv"].shape[1]
        slot = _ring_slots(cache["length"], W)
        bidx = jnp.arange(B)
        cache = {
            "ckv": cache["ckv"].at[bidx, slot].set(ckv[:, 0]),
            "k_rope": cache["k_rope"].at[bidx, slot].set(k_rope[:, 0]),
            "pos_ids": cache["pos_ids"].at[bidx, slot].set(cache["length"]),
            "length": cache["length"] + 1,
        }
        ckv_all = shard(cache["ckv"], "batch", "kv_seq", None)
        krope_all = shard(cache["k_rope"], "batch", "kv_seq", None)
        # absorb: q_lat[h] = q_nope[h] @ w_uk[h]^T  (B,1,H,kvr)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
        s = (jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_all)
             + jnp.einsum("bqhn,bkn->bhqk", q_rope, krope_all)
             ).astype(jnp.float32) * scale
        # NOTE: no score-tensor constraint here — the MLA latent cache is
        # head-free, so forcing a head sharding on scores only adds
        # resharding traffic (EXPERIMENTS.md SSPerf, deepseek-decode
        # regression follow-up)
        s = s + self_attn_bias(positions, cache["pos_ids"],
                               layer_window, None)[:, None]
        w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", w, ckv_all)
        out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    else:
        if mode == "prefill":
            cache = prefill_write_cache(
                {"k": cache["ckv"][..., None, :], "v": cache["k_rope"][..., None, :],
                 "pos_ids": cache["pos_ids"], "length": cache["length"]},
                ckv[..., None, :], k_rope[..., None, :], positions)
            cache = {"ckv": cache["k"][..., 0, :], "k_rope": cache["v"][..., 0, :],
                     "pos_ids": cache["pos_ids"], "length": cache["length"]}
        if cfg.mla_fused_prefill and x.shape[1] > DIRECT_ATTN_MAX_SEQ:
            out = _mla_flash_fused(q_nope, q_rope, ckv, k_rope, w_uk, w_uv,
                                   positions, positions, layer_window, scale)
        else:
            k_nope = shard(jnp.einsum("bkr,rhn->bkhn", ckv, w_uk),
                           "batch", "seq", "heads", None)
            vv = shard(jnp.einsum("bkr,rhv->bkhv", ckv, w_uv),
                       "batch", "seq", "heads", None)
            kk = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_rope[:, :, None, :],
                    k_nope.shape[:3] + (m.qk_rope_head_dim,))], axis=-1)
            kk = shard(kk, "batch", "seq", "heads", None)
            qq = jnp.concatenate([q_nope, q_rope], axis=-1)
            qq = shard(qq, "batch", "seq", "heads", None)
            out = attention_core(qq, kk, vv, positions, positions,
                                 layer_window, None, scale)
    with jax.named_scope("attn_proj"):
        flat = out.reshape(out.shape[:-2] + (-1,))
        return jnp.einsum("...h,hd->...d", flat, p["wo"]), cache


# --------------------------------------------------------------------------
# Cross-attention (VLM image layers; enc-dec decoder)
# --------------------------------------------------------------------------


def cross_attn_init(key, cfg, gated: bool = False) -> Dict:
    p = attn_init(key, cfg)
    if gated:
        p["gate"] = jnp.zeros((), L.dtype_of(cfg))
    return p


def build_cross_cache(p: Dict, cfg, memory: jax.Array) -> Dict:
    """Precompute K/V from encoder/image embeddings (static during decode)."""
    hd = cfg.head_dim_
    n_kvp = cfg.n_kv_heads_padded
    k = _split_heads(jnp.einsum("...d,dh->...h", memory, p["wk"]), n_kvp, hd)
    v = _split_heads(jnp.einsum("...d,dh->...h", memory, p["wv"]), n_kvp, hd)
    return {"k": k, "v": v}


def cross_attention(p: Dict, cfg, x: jax.Array,
                    cross_cache: Dict) -> jax.Array:
    hd = cfg.head_dim_
    q = _split_heads(jnp.einsum("...d,dh->...h", x, p["wq"]),
                     cfg.n_heads_padded, hd)
    kk = _expand_kv(cfg, cross_cache["k"])
    vv = _expand_kv(cfg, cross_cache["v"])
    Sk = kk.shape[1]
    bias = jnp.zeros((x.shape[0], 1, x.shape[1], Sk), jnp.float32)
    out = _direct_attention(q, kk, vv, bias)
    flat = out.reshape(out.shape[:-2] + (-1,))
    y = jnp.einsum("...h,hd->...d", flat, p["wo"])
    if "gate" in p:
        y = y * jnp.tanh(p["gate"].astype(jnp.float32)).astype(y.dtype)
    return y
