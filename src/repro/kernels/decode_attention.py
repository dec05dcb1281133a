"""Flash-decode Pallas TPU kernel: one query token per sequence against a
(ring-buffer) KV cache, split-KV with running-softmax combine.

Layout: q (B, Hq, hd); k (B, Hkv, W, hd); v (B, Hkv, W, hd_v) — hd_v may
differ from hd (MLA-absorbed decode: q/k live in the 512+64-dim latent,
v IS the 512-dim latent; see ``mla_decode_attention`` in ops.py);
k_pos (B, W) int32 (-1 empty); q_pos (B,) int32 current absolute position.

TPU block rules (Mosaic): the last two dims of every VMEM block must be
(8k, 128k) or the array's own. The wrappers therefore view q/o as
(B, Hkv, group, hd) so a block's last two dims are the full (group, hd)
at any group size, view k_pos as (B, nk, 1, bk) so each kv block's
positions are a full (1, bk) row even for 16-token pages, and pass q_pos
(one scalar per row) through scalar prefetch into SMEM. The running
softmax state is kept as (group, 1) columns.

Grid (B, Hkv, num_kv_blocks): one program per KV head, with the whole
(group, hd) GQA query block resident in VMEM — every query head of the
group scores against the KV block the program just pulled from HBM. The kv
axis is innermost/sequential and the running (m, l, acc) state sits in VMEM
scratch, so the memory-bound decode read of the KV cache happens exactly
ONCE PER GROUP, not once per query head — the roofline-optimal traffic
(decode HBM bytes ~ B * Hkv * W * (hd + hd_v), independent of Hq).
``decode_grid_spec`` exposes the grid/BlockSpec shapes so tests can assert
this property without re-deriving kernel internals.
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
DEFAULT_BK = 512


def decode_grid_spec(B: int, Hq: int, Hkv: int, W: int, hd: int, hd_v: int,
                     block_k: int = DEFAULT_BK,
                     page_size: Optional[int] = None,
                     num_pages: Optional[int] = None) -> Dict:
    """Grid + block shapes for the GQA-grouped decode kernel.

    The contract asserted by tests/test_engine_fused.py: the head grid axis is
    Hkv (not Hq), the k/v blocks carry a single KV head, and the q/o blocks
    (of the (B, Hkv, group, hd) head-grouped view) carry the full GQA group
    — so the number of HBM reads of each KV block equals the number of grid
    points touching it, i.e. exactly one per (batch, kv head, kv block).

    Paged extension (``page_size``/``num_pages`` given): the kv grid axis
    iterates the slot's ``max_pages`` LOGICAL pages and the k/v BlockSpecs
    index the (Hkv, num_pages+1, page_size, hd) physical pool through the
    scalar-prefetched block table — the kv block is one physical page of
    one kv head, so the one-HBM-read-per-(batch, kv head, logical page)
    contract carries over unchanged from the contiguous kernel.
    """
    assert Hq % Hkv == 0, "kernel requires uniform GQA grouping"
    group = Hq // Hkv
    if page_size is not None:
        assert num_pages is not None and W % page_size == 0
        nk = W // page_size                  # max logical pages per slot
        return {
            "grid": (B, Hkv, nk),
            "q_block": (1, 1, group, hd),
            "k_block": (1, 1, page_size, hd),
            "v_block": (1, 1, page_size, hd_v),
            "o_block": (1, 1, group, hd_v),
            "group": group,
            "block_k": page_size,
            "num_kv_blocks": nk,
            "kv_block_hbm_reads_per_group": 1,
            "paged": True,
            "page_size": page_size,
            "num_pages": num_pages,
            "kv_pool_shape": (Hkv, num_pages + 1, page_size),
        }
    bk = min(block_k, W)
    nk = -(-W // bk)
    return {
        "grid": (B, Hkv, nk),
        "q_block": (1, 1, group, hd),
        "k_block": (1, 1, bk, hd),
        "v_block": (1, 1, bk, hd_v),
        "o_block": (1, 1, group, hd_v),
        "group": group,
        "block_k": bk,
        "num_kv_blocks": nk,
        "kv_block_hbm_reads_per_group": 1,
        "paged": False,
    }


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, window, chunk, n_kv, scale):
    b, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (group, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                   # (bk, hd_v)
    qpos = qpos_ref[b]                                    # SMEM scalar
    kpos = kpos_ref[0, 0]                                 # (1, bk)

    # (group, bk) scores: contract hd without materializing k^T
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = (kpos >= 0) & (kpos <= qpos)
    if window is not None:
        ok &= kpos > qpos - window
    if chunk is not None:
        ok &= (kpos // chunk) == (qpos // chunk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                   # (group, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _scratch(group: int, hd_v: int):
    return [pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd_v), jnp.float32)]


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, k_pos: jax.Array,
                     window: Optional[int] = None,
                     chunk: Optional[int] = None,
                     block_k: int = DEFAULT_BK,
                     interpret: bool = False) -> jax.Array:
    """q: (B,Hq,hd); k: (B,Hkv,W,hd); v: (B,Hkv,W,hd_v); q_pos: (B,);
    k_pos: (B,W). Returns (B,Hq,hd_v)."""
    B, Hq, hd = q.shape
    hd_v = v.shape[-1]
    Hkv, W = k.shape[1], k.shape[2]
    spec = decode_grid_spec(B, Hq, Hkv, W, hd, hd_v, block_k)
    group, bk, nk = spec["group"], spec["block_k"], spec["num_kv_blocks"]
    if W % bk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - W), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - W), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, nk * bk - W)), constant_values=-1)

    kernel = functools.partial(_decode_kernel, window=window, chunk=chunk,
                               n_kv=nk, scale=1.0 / math.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=spec["grid"],
        in_specs=[
            # q/o blocks cover the whole GQA group of kv head h
            pl.BlockSpec(spec["q_block"], lambda b, h, ik, qp: (b, h, 0, 0)),
            # k/v blocks carry ONE kv head: read once per (b, h, ik)
            pl.BlockSpec(spec["k_block"], lambda b, h, ik, qp: (b, h, ik, 0)),
            pl.BlockSpec(spec["v_block"], lambda b, h, ik, qp: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, ik, qp: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec(spec["o_block"],
                               lambda b, h, ik, qp: (b, h, 0, 0)),
        scratch_shapes=_scratch(group, hd_v),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd_v), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), q.reshape(B, Hkv, group, hd), k, v,
      k_pos.reshape(B, nk, 1, bk))
    return out.reshape(B, Hq, hd_v)


def _paged_decode_kernel(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, kpos_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, window, chunk,
                         n_kv, scale):
    # identical math to the contiguous kernel — the block table only moves
    # WHICH physical page the k/v BlockSpecs DMA'd in (see index maps)
    _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   m_ref, l_ref, acc_ref, window=window, chunk=chunk,
                   n_kv=n_kv, scale=scale)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tbl: jax.Array,
                           q_pos: jax.Array, k_pos: jax.Array,
                           window: Optional[int] = None,
                           chunk: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Block-table decode attention over a shared page pool.

    q: (B, Hq, hd); k_pages: (Hkv, P+1, ps, hd); v_pages: (Hkv, P+1, ps,
    hd_v); block_tbl: (B, M) int32 physical page per logical page (-1 =
    unmapped -> trash page P); q_pos: (B,); k_pos: (B, M*ps) LOGICAL
    positions (-1 = empty). Returns (B, Hq, hd_v).

    The grid is the contiguous kernel's (B, Hkv, nk) with nk = M logical
    pages; the block table and q_pos ride in as scalar-prefetch operands
    so the k/v index maps can chase the table — each physical page is
    still read from HBM exactly once per (batch, kv head) GQA group.
    Unmapped logical pages resolve to the trash page and are masked by
    their -1 logical positions, so the running softmax never sees them.
    """
    B, Hq, hd = q.shape
    Hkv, P1, ps, _ = k_pages.shape
    hd_v = v_pages.shape[-1]
    M = block_tbl.shape[1]
    spec = decode_grid_spec(B, Hq, Hkv, M * ps, hd, hd_v,
                            page_size=ps, num_pages=P1 - 1)
    group = spec["group"]
    trash = P1 - 1

    def page_of(b, ik, tbl):
        p = tbl[b, ik]
        return jnp.where(p < 0, trash, p)

    kernel = functools.partial(_paged_decode_kernel, window=window,
                               chunk=chunk, n_kv=M,
                               scale=1.0 / math.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=spec["grid"],
        in_specs=[
            pl.BlockSpec(spec["q_block"],
                         lambda b, h, ik, tbl, qp: (b, h, 0, 0)),
            # k/v blocks are ONE physical page of ONE kv head, located by
            # chasing the prefetched block table
            pl.BlockSpec(spec["k_block"], lambda b, h, ik, tbl, qp:
                         (h, page_of(b, ik, tbl), 0, 0)),
            pl.BlockSpec(spec["v_block"], lambda b, h, ik, tbl, qp:
                         (h, page_of(b, ik, tbl), 0, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda b, h, ik, tbl, qp: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec(spec["o_block"],
                               lambda b, h, ik, tbl, qp: (b, h, 0, 0)),
        scratch_shapes=_scratch(group, hd_v),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd_v), q.dtype),
        interpret=interpret,
    )(block_tbl.astype(jnp.int32), q_pos.astype(jnp.int32),
      q.reshape(B, Hkv, group, hd), k_pages, v_pages,
      k_pos.reshape(B, M, 1, ps))
    return out.reshape(B, Hq, hd_v)
