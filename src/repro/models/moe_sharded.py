"""Expert-parallel MoE via shard_map + all_to_all (the TPU-native path).

GSPMD lowers cross-shard gather/scatter dispatch to full-table all-gathers
(measured: 1.3 TiB/device peak on DeepSeek-V3 train — see EXPERIMENTS.md),
so the sharded path is explicit:

  1. tokens are sharded over every mesh axis; each device routes its local
     tokens and scatters them into a per-expert send buffer (local memory
     ops, no FLOP inflation);
  2. ``all_to_all`` over the expert axes moves token buffers to their
     expert's owner (THE MoE collective);
  3. experts whose weights don't fit one chip are additionally split on the
     FFN dim over the remaining axis ("fa"): tokens are all-gathered across
     that axis and partial outputs ``psum_scatter``-ed back;
  4. reverse ``all_to_all`` + local weighted combine.

Axis split: ``expert_axes(E, mesh)`` picks the largest (data, model) subset
whose size divides E for the expert dim ("ea"); the remainder shards d_ff
("fa"). DeepSeek-V3 (E=256 = data*model) gets pure 256-way expert
parallelism; Llama-4 (E=128) gets 16-way experts x 16-way FFN. The "pod"
axis always replicates experts (per-pod expert parallelism).

Capacity is per (sender shard, expert) — GShard-style local capacity.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.moe import load_balance_loss, router_topk, router_z_loss
from repro.sharding import shard
from repro.sharding.api import current_context


from repro.sharding.rules import expert_axes


def use_sharded_moe(cfg) -> bool:
    ctx = current_context()
    if ctx is None:
        return False
    ea, _ = expert_axes(cfg.moe.n_experts, ctx.mesh)
    size = 1
    for a in ea:
        size *= ctx.mesh.shape[a]
    return size > 1


def moe_ffn_sharded(p: Dict, cfg, x: jax.Array) -> Tuple[jax.Array, Dict]:
    """Drop-in replacement for moe.moe_ffn when a mesh context is active."""
    ctx = current_context()
    mesh = ctx.mesh
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    nd = mesh.size
    all_axes = tuple(mesh.axis_names)
    ea, fa = expert_axes(E, mesh)
    Gea = 1
    for a in ea:
        Gea *= mesh.shape[a]
    Gfa = 1
    for a in fa:
        Gfa *= mesh.shape[a]

    T_pad = -(-T // nd) * nd
    xt = x.reshape(T, d)
    if T_pad > T:
        xt = jnp.pad(xt, ((0, T_pad - T), (0, 0)))
    T_loc = T_pad // nd
    # per (sender, expert) capacity, >=1, mult of 4
    C = max(4, -(-int(T_loc * k * m.capacity_factor) // E) * 1)
    C = -(-C // 4) * 4

    ea_spec = ea if len(ea) != 1 else ea[0]
    fa_spec = (fa if len(fa) != 1 else fa[0]) if fa else None

    w_specs = {
        "router": P(None, None),
        "experts_gate": P(ea_spec, None, fa_spec),
        "experts_up": P(ea_spec, None, fa_spec),
        "experts_down": P(ea_spec, fa_spec, None),
    }

    def body(xt_loc, router_w, w_g, w_u, w_d):
        # xt_loc: (T_loc, d); w_g/w_u: (E_loc, d, f_loc); w_d: (E_loc, f_loc, d)
        rl = jnp.einsum("td,de->te", xt_loc.astype(jnp.float32), router_w)
        gates, ids = router_topk(rl, k)                       # (T_loc, k)
        flat_ids = ids.reshape(-1)
        order = jnp.argsort(flat_ids, stable=True)
        sorted_ids = flat_ids[order]
        counts = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(T_loc * k, dtype=jnp.int32) - offsets[sorted_ids]
        keep = pos < C
        slot = jnp.where(keep, sorted_ids * C + pos, E * C)
        tok_idx = order // k

        send = jnp.zeros((E * C + 1, d), xt_loc.dtype)
        send = send.at[slot].set(xt_loc[tok_idx])             # local scatter
        send = send[:E * C].reshape(E, C, d)

        # ---- all_to_all to expert owners (split experts, concat capacity)
        buf = send
        for ax in ea:
            buf = jax.lax.all_to_all(buf, ax, split_axis=0, concat_axis=1,
                                     tiled=True)
        # buf: (E_loc, C*Gea, d)
        if fa:
            for ax in fa:
                buf = jax.lax.all_gather(buf, ax, axis=1, tiled=True)
        # buf: (E_loc, C*Gea*Gfa, d)

        g = jnp.einsum("ecd,edf->ecf", buf, w_g)
        u = jnp.einsum("ecd,edf->ecf", buf, w_u)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
        out = jnp.einsum("ecf,efd->ecd", h, w_d)              # partial over f
        if fa:
            for ax in reversed(fa):
                out = jax.lax.psum_scatter(out, ax, scatter_dimension=1,
                                           tiled=True)
        # out: (E_loc, C*Gea, d)
        for ax in reversed(ea):
            out = jax.lax.all_to_all(out, ax, split_axis=1, concat_axis=0,
                                     tiled=True)
        # out: (E, C, d) — back at the sender, per-expert slots

        out_flat = jnp.concatenate(
            [out.reshape(E * C, d), jnp.zeros((1, d), out.dtype)], axis=0)
        gathered = out_flat[slot]                             # (T_loc*k, d)
        weight = jnp.where(keep, gates.reshape(-1)[order], 0.0
                           ).astype(xt_loc.dtype)
        y = jnp.zeros((T_loc, d), xt_loc.dtype).at[tok_idx].add(
            gathered * weight[:, None])

        aux_cnt = counts.astype(jnp.float32)                  # (E,)
        aux = jnp.stack([
            load_balance_loss(rl, ids, E),
            router_z_loss(rl),
            1.0 - jnp.mean(keep.astype(jnp.float32)),
        ])
        # average aux metrics over all devices
        aux = jax.lax.pmean(aux, all_axes)
        return y, aux

    sm = shard_map(
        body, mesh=mesh,
        in_specs=(P(all_axes, None), w_specs["router"],
                  w_specs["experts_gate"], w_specs["experts_up"],
                  w_specs["experts_down"]),
        out_specs=(P(all_axes, None), P()),
        check_vma=False,
    )
    y, aux_v = sm(xt, p["router"], p["experts_gate"], p["experts_up"],
                  p["experts_down"])
    y = y[:T]

    if m.n_shared_experts:
        # shared expert runs in plain SPMD: pin the token sharding or the
        # (B*S, d) tables replicate across the mesh (SSPerf H2 iter 3)
        xt2 = shard(x.reshape(T, d), "tokens", None)
        ys = shard(L.swiglu(p["shared"], xt2), "tokens", None)
        y = y + ys

    aux = {"moe_aux": aux_v[0], "moe_z": aux_v[1], "moe_drop_frac": aux_v[2]}
    return y.reshape(B, S, d), aux
