"""Flash attention (prefill) Pallas TPU kernel.

Layout: q (B, Hq, Sq, hd), k/v (B, Hkv, Sk, hd), positions q_pos/k_pos
(B, S) int32 (-1 = invalid slot). Supports causal masking, sliding window,
chunked (local) attention, and GQA via a uniform q->kv head divide in the
BlockSpec index map.

TPU mapping: grid (B, Hq, num_q_blocks, num_kv_blocks) — the kv axis is the
innermost (sequential on TPU), so the running-softmax state (m, l, acc)
lives in VMEM scratch and persists across kv steps; the output block is
written on the last kv step. Block shapes default to (128, 128) q x kv
tiles with the full head dim — MXU-aligned (hd is 64/128 in all assigned
configs) and well under VMEM (~(2*bq*hd + 2*bk*hd + bq*bk) * 4B ~ 0.4 MB).
Mosaic wants the last two dims of a block to be (8k, 128k) or the
array's own, so q_pos is viewed as (B, Sq, 1) (a (bq, 1) column per q
block), k_pos as (B, nk, 1, bk) (a full (1, bk) row per kv block), and
the running softmax state is kept as (bq, 1) columns.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

DEFAULT_BQ = 128
DEFAULT_BK = 128


def _mask(qpos, kpos, window, chunk):
    ok = (kpos >= 0) & (kpos <= qpos)
    if window is not None:
        ok &= kpos > qpos - window
    if chunk is not None:
        ok &= (kpos // chunk) == (qpos // chunk)
    return ok


def _flash_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, window, chunk, n_kv, scale):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    qpos = qpos_ref[0]                                   # (bq, 1)
    kpos = kpos_ref[0, 0]                                # (1, bk)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(qpos, kpos, window, chunk), s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, k_pos: jax.Array,
                    window: Optional[int] = None,
                    chunk: Optional[int] = None,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Hq,Sq,hd); k/v: (B,Hkv,Sk,hd); q_pos: (B,Sq); k_pos: (B,Sk)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, "kernel requires uniform GQA grouping"
    group = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    # pad to block multiples; padded kv slots get pos -1 (masked out)
    if Sq % bq or Sk % bk:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, nq * bq - Sq)), constant_values=0)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, nk * bk - Sk)),
                        constant_values=-1)

    kernel = functools.partial(_flash_kernel, window=window, chunk=chunk,
                               n_kv=nk, scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, iq, ik: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, q_pos.reshape(B, nq * bq, 1), k_pos.reshape(B, nk, 1, bk))
    return out[:, :, :Sq]
