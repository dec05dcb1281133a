"""Mixture-of-experts FFN: a capacity layer with scatter dispatch
(``moe_ffn``), and a dropless layer over the experts a chip holds
(``moe_ffn_held``).

TPU-native design choices:

* Dispatch uses sort + scatter into fixed ``(E*C, d)`` buffers rather than
  the one-hot-matmul (GShard einsum) dispatch — the einsum dispatch costs
  ``O(T^2 * k * capacity_factor * d)`` FLOPs, which at trillion-token scale
  dwarfs the expert FLOPs themselves and would wreck the roofline analysis.
  Scatter/gather are memory ops; the only FLOP inflation left is the
  capacity padding (``capacity_factor``, default 1.25x).
* Expert matmuls are a single batched einsum ``(E,C,d) x (E,d,f)`` so the
  ``model`` mesh axis shards the expert dim (expert parallelism); token
  movement into expert shards lowers to an all-to-all under GSPMD.
* Tokens beyond an expert's capacity are dropped (standard Switch behavior);
  the router's load-balance auxiliary loss keeps drops rare.

The capacity layer's router is a softmax over the top-k logits. It is not
DeepSeek-V3's published router (sigmoid scores with a bias correction,
node-limited groups) and models nothing of it: the V3 preset runs this
layer as a stand-in with V3's shapes.

The dropless layer (``MoEConfig.dropless``) is DeepSeek-V2's published
router and expert layer, cut to one chip's share of an expert-parallel
deployment: the router (float32) scores all ``n_experts``, takes softmax
over them and the greedy top-k, with gates not renormalised
(DeepSeek-V2's ``norm_topk_prob`` false, ``routed_scaling_factor`` 1);
the layer holds experts ``0 .. n_held_experts-1`` and computes every
token-expert pair routed to one of them, whatever the batch, as grouped
products (``jax.lax.ragged_dot``) over the pairs sorted by expert. Pairs
routed to experts held elsewhere add nothing here. Scopes: ``moe_route``
(router, top-k, dispatch, combine) and ``moe_experts`` (the held
experts' products), inside the block's ``mlp`` with the shared experts.
Its aux counts, summed over layers by the model: ``moe_pairs`` (pairs
routed to held experts) and ``moe_experts_hit`` (held experts that
received at least one pair).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding import shard


def moe_init(key, cfg) -> Dict:
    m = cfg.moe
    dt = L.dtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    E = m.held
    # the dropless router is stored like the other weights (the published
    # checkpoint's bf16) and computed in float32
    p = {
        "router": L.dense_init(ks[0], d, m.n_experts,
                               dt if m.dropless else jnp.float32),
        "experts_gate": L.normal(ks[1], (E, d, m.d_ff_expert),
                                 1.0 / (d ** 0.5), dt),
        "experts_up": L.normal(ks[2], (E, d, m.d_ff_expert),
                               1.0 / (d ** 0.5), dt),
        "experts_down": L.normal(ks[3], (E, m.d_ff_expert, d),
                                 1.0 / (m.d_ff_expert ** 0.5), dt),
    }
    if m.n_shared_experts:
        p["shared"] = L.swiglu_init(ks[4], d,
                                    m.d_ff_expert * m.n_shared_experts, dt)
    return p


def router_topk(router_logits: jax.Array, top_k: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Returns (gates (T,k) softmax-normalized over the selected experts,
    expert_ids (T,k))."""
    vals, ids = jax.lax.top_k(router_logits, top_k)
    gates = jax.nn.softmax(vals.astype(jnp.float32), axis=-1)
    return gates, ids


def load_balance_loss(router_logits: jax.Array, expert_ids: jax.Array,
                      n_experts: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    p_mean = probs.mean(axis=0)                                   # (E,)
    counts = jnp.zeros((n_experts,), jnp.float32).at[
        expert_ids.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(expert_ids.size, 1)
    return n_experts * jnp.sum(f * p_mean)


def router_z_loss(router_logits: jax.Array) -> jax.Array:
    z = jax.nn.logsumexp(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z * z)


def moe_ffn(p: Dict, cfg, x: jax.Array) -> Tuple[jax.Array, Dict]:
    """x: (B, S, d) -> (B, S, d), aux-loss dict."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    k = m.top_k
    E = m.n_experts
    # capacity per expert (multiple of 8 for TPU-friendly layouts)
    C = max(8, int(-(-T * k * m.capacity_factor // E)))
    C = -(-C // 8) * 8

    xt = shard(x.reshape(T, d), "tokens", None)
    rl = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    rl = shard(rl, "tokens", None)
    gates, ids = router_topk(rl, k)                                # (T,k)

    flat_ids = ids.reshape(-1)                                     # (T*k,)
    flat_gates = gates.reshape(-1)
    # stable ordering: sort by expert id, tokens keep relative order
    order = jnp.argsort(flat_ids, stable=True)
    order = shard(order, "expert_flat")
    sorted_ids = flat_ids[order]
    # position within expert group
    counts = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos_in_expert = (jnp.arange(T * k, dtype=jnp.int32)
                     - offsets[sorted_ids])
    keep = pos_in_expert < C
    slot = jnp.where(keep, sorted_ids * C + pos_in_expert, E * C)  # E*C = drop
    slot = shard(slot, "expert_flat")

    tok_idx = order // k                                           # source token
    # scatter tokens into per-expert capacity buffers (the all-to-all)
    buf = shard(jnp.zeros((E * C + 1, d), x.dtype), "expert_flat", None)
    buf = buf.at[slot].set(jnp.take(xt, tok_idx, axis=0))
    expert_in = buf[:E * C].reshape(E, C, d)
    expert_in = shard(expert_in, "experts", None, None)

    g = jnp.einsum("ecd,edf->ecf", expert_in, p["experts_gate"])
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["experts_up"])
    h = shard(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u,
              "experts", None, None)
    out = jnp.einsum("ecf,efd->ecd", h, p["experts_down"])
    out = shard(out, "experts", None, None)

    out_flat = jnp.concatenate(
        [out.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = shard(jnp.take(out_flat, slot, axis=0), "expert_flat", None)
    weight = jnp.where(keep, flat_gates[order], 0.0).astype(x.dtype)
    contrib = gathered * weight[:, None]
    y = shard(jnp.zeros((T, d), x.dtype), "tokens", None).at[tok_idx].add(contrib)
    y = shard(y, "tokens", None)

    if m.n_shared_experts:
        y = y + L.swiglu(p["shared"], xt)

    aux = {
        "moe_aux": load_balance_loss(rl, ids, E),
        "moe_z": router_z_loss(rl),
        "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y.reshape(B, S, d), aux


def router_softmax_topk(router_logits: jax.Array, top_k: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V2's router: softmax over every expert's logit, then the
    greedy top-k of those scores, not renormalised. Returns (gates (T, k)
    float32, expert ids (T, k))."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    return jax.lax.top_k(probs, top_k)


def held_pairs(ids: jax.Array, gates: jax.Array, n_held: int):
    """The token-expert pairs routed to held experts, sorted by expert:
    (order (T*k,) pair index by expert, pairs to other experts last;
    group_sizes (n_held,); weights (T*k,) float32 in that order, 0 for
    pairs not held)."""
    flat = ids.reshape(-1)
    held = flat < n_held
    order = jnp.argsort(jnp.where(held, flat, n_held), stable=True)
    sizes = jnp.zeros((n_held,), jnp.int32).at[flat].add(1, mode="drop")
    weights = jnp.where(held, gates.reshape(-1), 0.0)[order]
    return order, sizes, weights


def moe_ffn_held(p: Dict, cfg, x: jax.Array) -> Tuple[jax.Array, Dict]:
    """The dropless layer over the held experts (module docstring).
    x: (B, S, d) -> (B, S, d), and the counts of its pairs."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with jax.named_scope("moe_route"):
        rl = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
        gates, ids = router_softmax_topk(rl, m.top_k)
        order, sizes, weights = held_pairs(ids, gates, m.held)
        tok = order // m.top_k                       # source token per row
        xs = jnp.take(xt, tok, axis=0)               # (T*k, d), held first
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, p["experts_gate"], sizes)
        u = jax.lax.ragged_dot(xs, p["experts_up"], sizes)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        out = jax.lax.ragged_dot(h, p["experts_down"], sizes)
    with jax.named_scope("moe_route"):
        # rows past the held pairs belong to no group, and the grouped
        # product does not promise what it leaves there (any bits, NaN
        # among them): select them away, do not weigh them by zero
        held = jnp.arange(out.shape[0]) < sizes.sum()
        out = jnp.where(held[:, None], out.astype(jnp.float32), 0.0)
        y = jnp.zeros((B * S, d), jnp.float32).at[tok].add(
            out * weights[:, None]).astype(x.dtype)
    if m.n_shared_experts:
        y = y + L.swiglu(p["shared"], xt)
    aux = {"moe_pairs": sizes.sum(),
           "moe_experts_hit": (sizes > 0).sum(dtype=jnp.int32)}
    return y.reshape(B, S, d), aux
