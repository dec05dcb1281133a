"""Chunked-prefill Pallas TPU kernel: a fixed-size chunk of prompt queries
per slot against the slot's paged KV history, split-KV with running-softmax
combine and a scalar-prefetched block table.

This is the attention path that lets the serving engine interleave prompt
processing with decode (Sarathi-style chunked prefill): chunk *i* of a
prompt attends over its own S queries PLUS the KV of chunks 0..i-1 already
resident in the shared page pool. The chunk's keys are written to the pool
*before* the call, so one mask — ``k_pos <= q_pos`` on logical positions —
covers both the history and in-chunk causality.

Layout: q (B, Hq, S, hd) with S = prefill chunk size; k_pages / v_pages
(Hkv, num_pages+1, page_size, hd/hd_v) shared physical pool (last page =
trash); block_tbl (B, max_pages) int32 logical->physical (-1 = unmapped ->
trash); q_pos (B, S) int32 (-1 = pad query); k_pos (B, max_pages*page_size)
LOGICAL positions (-1 = empty).

Grid (B, Hkv, max_pages) — the decode kernel's GQA-grouped grid
(kernels/decode_attention.py) with the whole (group, S, hd) query chunk of
each KV head resident in VMEM: every KV page is pulled from HBM exactly
once per (batch, kv head, logical page), independent of Hq AND of S — the
chunk rides along for free on the memory-bound page read, which is what
makes mixed prefill+decode quanta cheap. ``chunked_prefill_grid_spec``
exposes the shapes so tests can assert this without re-deriving internals.

TPU block rules (Mosaic) shape the position operands: q_pos is viewed as
(B, S, 1) so each row's query positions arrive as a full (S, 1) column,
k_pos as (B, max_pages, 1, page_size) so each page's positions are a full
(1, page_size) row, and the running softmax state is kept as (group*S, 1)
columns.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def chunked_prefill_grid_spec(B: int, Hq: int, Hkv: int, S: int, hd: int,
                              hd_v: int, page_size: int, num_pages: int,
                              max_pages: int) -> Dict:
    """Grid + block shapes for the chunked-prefill kernel.

    Contract (asserted by tests/test_chunked_prefill_kernel.py): the head
    grid axis is Hkv, the k/v blocks carry ONE physical page of ONE kv
    head, and the q/o blocks carry the full (group, S) query chunk — so
    each page is read from HBM exactly once per (batch, kv head), the same
    traffic shape as the paged decode kernel at any chunk size.
    """
    assert Hq % Hkv == 0, "kernel requires uniform GQA grouping"
    group = Hq // Hkv
    return {
        "grid": (B, Hkv, max_pages),
        "q_block": (1, group, S, hd),
        "k_block": (1, 1, page_size, hd),
        "v_block": (1, 1, page_size, hd_v),
        "o_block": (1, group, S, hd_v),
        "group": group,
        "chunk_len": S,
        "block_k": page_size,
        "num_kv_blocks": max_pages,
        "kv_block_hbm_reads_per_group": 1,
        "paged": True,
        "page_size": page_size,
        "num_pages": num_pages,
        "kv_pool_shape": (Hkv, num_pages + 1, page_size),
    }


def _chunked_prefill_kernel(tbl_ref, q_ref, k_ref, v_ref, qpos_ref, kpos_ref,
                            o_ref, m_ref, l_ref, acc_ref, *, window, chunk,
                            n_kv, scale):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                      # (group, S, hd)
    g, S, hd = q.shape
    k = k_ref[0, 0].astype(jnp.float32)                   # (ps, hd)
    v = v_ref[0, 0].astype(jnp.float32)                   # (ps, hd_v)
    qpos = qpos_ref[0]                                    # (S, 1)
    kpos = kpos_ref[0, 0]                                 # (1, ps)

    # (group*S, ps) scores: every query row of the chunk vs this page
    q2 = q.reshape(g * S, hd)
    s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = (kpos >= 0) & (kpos <= qpos)                     # (S, ps)
    if window is not None:
        ok &= kpos > qpos - window
    if chunk is not None:
        ok &= (kpos // chunk) == (qpos // chunk)
    if g > 1:
        ok = jnp.concatenate([ok] * g, axis=0)            # (group*S, ps)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                   # (group*S, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        hd_v = acc_ref.shape[-1]
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = o.reshape(g, S, hd_v).astype(o_ref.dtype)


def chunked_prefill_attention(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tbl: jax.Array,
                              q_pos: jax.Array, k_pos: jax.Array,
                              window: Optional[int] = None,
                              chunk: Optional[int] = None,
                              interpret: bool = False) -> jax.Array:
    """q: (B,Hq,S,hd); k_pages/v_pages: (Hkv,P+1,ps,*); block_tbl: (B,M);
    q_pos: (B,S); k_pos: (B,M*ps). Returns (B,Hq,S,hd_v)."""
    B, Hq, S, hd = q.shape
    Hkv, P1, ps, _ = k_pages.shape
    hd_v = v_pages.shape[-1]
    M = block_tbl.shape[1]
    spec = chunked_prefill_grid_spec(B, Hq, Hkv, S, hd, hd_v,
                                     page_size=ps, num_pages=P1 - 1,
                                     max_pages=M)
    group = spec["group"]
    trash = P1 - 1

    def page_of(b, ik, tbl):
        p = tbl[b, ik]
        return jnp.where(p < 0, trash, p)

    kernel = functools.partial(_chunked_prefill_kernel, window=window,
                               chunk=chunk, n_kv=M,
                               scale=1.0 / math.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=spec["grid"],
        in_specs=[
            # the whole (group, S) query chunk of kv head h rides along
            pl.BlockSpec(spec["q_block"],
                         lambda b, h, ik, tbl: (b, h, 0, 0)),
            # k/v blocks are ONE physical page of ONE kv head, located by
            # chasing the prefetched block table (as in paged decode)
            pl.BlockSpec(spec["k_block"],
                         lambda b, h, ik, tbl: (h, page_of(b, ik, tbl), 0, 0)),
            pl.BlockSpec(spec["v_block"],
                         lambda b, h, ik, tbl: (h, page_of(b, ik, tbl), 0, 0)),
            pl.BlockSpec((1, S, 1), lambda b, h, ik, tbl: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps), lambda b, h, ik, tbl: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec(spec["o_block"],
                               lambda b, h, ik, tbl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group * S, 1), jnp.float32),
            pltpu.VMEM((group * S, 1), jnp.float32),
            pltpu.VMEM((group * S, hd_v), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd_v), q.dtype),
        interpret=interpret,
    )(block_tbl.astype(jnp.int32), q, k_pages, v_pages,
      q_pos.reshape(B, S, 1), k_pos.reshape(B, M, 1, ps))
