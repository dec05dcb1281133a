"""On-device sampling, termination, and slot-pool insertion for the fused
serving step.

The seed engine ran decode one token at a time with the sampler, EOS check,
and budget bookkeeping in Python — a device->host sync (and a handful of
scalar transfers) per generated token. Everything here is designed to run
under one ``jax.jit``:

  * ``sample``            — temperature/greedy next-token choice.
  * ``fused_decode_steps``— a ``lax.scan`` of ``n_steps`` full engine
    micro-steps (decode -> sample -> EOS/budget masking -> done flags).
    The host only syncs once per chunk, on the stacked (n_steps, B) token
    and emission matrices.
  * ``insert_prefill``    — scatter a batch-n prefilled cache into n slots
    of the batch-B pool in ONE pass per leaf (``.at[slots].set``), instead
    of the seed's per-request whole-tree copies.

Slot state is a plain dict pytree of fixed-shape device arrays::

    {"active": (B,) bool,   # slot is decoding
     "budget": (B,) int32,  # decode tokens still allowed
     "eos":    (B,) int32}  # per-slot EOS id, -1 = none

Termination semantics match the seed loop token-for-token: a step first
emits the sampled token for every active slot, then decrements the budget
and raises ``done`` on budget exhaustion or EOS — so the EOS token itself
is emitted, and a request for N new tokens emits exactly N (1 from prefill
+ N-1 decode).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def init_slot_state(max_batch: int) -> Dict[str, jax.Array]:
    return {
        "active": jnp.zeros((max_batch,), bool),
        "budget": jnp.zeros((max_batch,), jnp.int32),
        "eos": jnp.full((max_batch,), -1, jnp.int32),
    }


def sample(logits: jax.Array, key: jax.Array,
           temperature: float) -> jax.Array:
    """logits: (B, vocab) -> (B,) int32. temperature <= 0 means greedy."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def fused_decode_steps(model, params, caches, cur_tokens: jax.Array,
                       state: Dict[str, jax.Array], key: jax.Array,
                       n_steps: int, temperature: float,
                       page_size: int = 0, freeze_inactive: bool = False
                       ) -> Tuple:
    """Run ``n_steps`` fused engine micro-steps fully on device.

    cur_tokens: (B, 1) int32 — last token of every slot.
    Returns (caches, cur_tokens, state, tok_mat, emit_mat, counts) where
    tok_mat/emit_mat are (n_steps, B): the sampled token per step and
    whether the slot was active (i.e. the token is a real emission), and
    counts maps each of the model's counters (``Model.decode_step``'s
    ``counters``: the dropless MoE layer's pairs and experts hit, summed
    over layers) to its (n_steps,) values; {} for a model without them.
    Finished/free slots keep re-feeding their last token; their logits are
    computed but never read (same batch-shape invariance as the seed).

    page_size > 0 marks a paged KV pool (``caches["paged"]`` holds the
    shared allocator): before each micro-step, alloc-on-write pops a free
    page for every ACTIVE slot whose next token starts a new logical page —
    inactive slots never allocate, so finished slots coasting to the chunk
    boundary write to the trash page instead of draining the pool. Popped
    pages enter the table singly referenced; decode appends never need
    copy-on-write because by the time a slot is armed, the page holding
    its last cached token is private (prefix-sharing CoW runs in the
    chunked-prefill path) and every later page is popped fresh — the
    refcounted-allocator suite (tests/test_prefix_sharing.py) asserts no
    write ever lands in a page with refcount > 1.

    Profiler scopes: the model's own (``attn_decode``, ``attn_proj``,
    ``mlp``, ``lm_head``), and ``sample`` for sampling and the emission
    masks.

    ``freeze_inactive`` (chunked-prefill engines) restores inactive slots'
    write cursors to their pre-step values after each micro-step
    (``paged.freeze_inactive_cursors``): a slot parked mid-chunked-prefill
    keeps its logical position exact while decode chunks run around it.
    Non-chunked engines skip the extra selects — their inactive slots are
    free/finished and get fully re-initialized at insertion anyway.
    """
    vocab = model.cfg.vocab
    keys = jax.random.split(key, n_steps)

    def body(carry, k_i):
        caches, toks, active, budget = carry
        if page_size:
            from repro.serving import paged as _paged
            caches = dict(caches)
            caches["paged"] = _paged.alloc_decode_pages(
                caches["paged"], caches["t"], active, page_size)
            prev = caches
            logits, caches, counts = model.decode_step(params, caches, toks,
                                                       counters=True)
            if freeze_inactive:
                caches = _paged.freeze_inactive_cursors(prev, caches,
                                                        active)
        else:
            logits, caches, counts = model.decode_step(params, caches, toks,
                                                       counters=True)
        with jax.named_scope("sample"):
            nxt = sample(logits[:, :vocab], k_i, temperature)
            nxt = jnp.where(active, nxt, toks[:, 0])
            emitted = active
            budget = budget - emitted.astype(jnp.int32)
            done = emitted & ((budget <= 0) |
                              ((state["eos"] >= 0) & (nxt == state["eos"])))
            active = active & ~done
        return (caches, nxt[:, None], active, budget), (nxt, emitted, counts)

    (caches, cur_tokens, active, budget), (tok_mat, emit_mat, counts) = \
        jax.lax.scan(body, (caches, cur_tokens, state["active"],
                            state["budget"]), keys)
    new_state = {"active": active, "budget": budget, "eos": state["eos"]}
    return caches, cur_tokens, new_state, tok_mat, emit_mat, counts


def insert_prefill(pool, src, slots: jax.Array, cur_tokens: jax.Array,
                   first_tokens: jax.Array, state: Dict[str, jax.Array],
                   budgets: jax.Array, eos_ids: jax.Array) -> Tuple:
    """Insert a batch-n prefilled cache tree into ``slots`` of the batch-B
    pool, set the slots' first decode tokens, and arm their slot state —
    one scatter per cache leaf for the whole admission batch.

    pool/src: matching cache pytrees with batch sizes B and >= n (the
    engine pads the prefill batch to a power of two to bound trace shapes;
    pad rows are sliced off here). Scanned ``unit`` leaves carry batch on
    axis 1. slots/budgets/eos_ids: (n,) arrays. A zero budget arms the
    slot inactive — the prefill token was the request's whole budget.
    """
    n = slots.shape[0]

    def leaf(kp, d, s):
        top = kp[0]
        bdim = 1 if getattr(top, "key", None) == "unit" else 0
        if s.shape[bdim] != n:
            s = jax.lax.slice_in_dim(s, 0, n, axis=bdim)
        if bdim == 0:
            return d.at[slots].set(s.astype(d.dtype))
        return d.at[:, slots].set(s.astype(d.dtype))

    pool = jax.tree_util.tree_map_with_path(leaf, pool, src)
    cur_tokens, state = arm_slots(cur_tokens, state, slots, first_tokens,
                                  budgets, eos_ids)
    return pool, cur_tokens, state


def arm_slots(cur_tokens: jax.Array, state: Dict[str, jax.Array],
              slots: jax.Array, first_tokens: jax.Array,
              budgets: jax.Array, eos_ids: jax.Array) -> Tuple:
    """Set the admitted slots' first decode tokens and arm their device
    state (shared by the contiguous and paged insertion paths — the
    termination semantics MUST stay identical for token-for-token parity).
    A zero budget arms the slot inactive."""
    n = slots.shape[0]
    cur_tokens = cur_tokens.at[slots, 0].set(first_tokens[:n])
    state = {
        "active": state["active"].at[slots].set(budgets > 0),
        "budget": state["budget"].at[slots].set(budgets),
        "eos": state["eos"].at[slots].set(eos_ids),
    }
    return cur_tokens, state


def disarm_slots(state: Dict[str, jax.Array],
                 slots: jax.Array) -> Dict[str, jax.Array]:
    """Deactivate ``slots`` mid-decode (preemption eviction or deadline
    cancellation): the inverse of ``arm_slots``. A disarmed slot stops
    sampling at the next fused chunk exactly like a slot whose ``done``
    flag fired — budget zeroed so any stale read sees a spent slot. The
    caller snapshots the remaining budget from its host mirror BEFORE
    disarming (resume needs it)."""
    return {
        "active": state["active"].at[slots].set(False),
        "budget": state["budget"].at[slots].set(0),
        "eos": state["eos"].at[slots].set(-1),
    }


def prefill_bucket(length: int, min_bucket: int = 8) -> int:
    """Power-of-two length bucket (>= min_bucket): bounds the number of
    distinct prefill trace shapes to log2(max prompt length)."""
    b = min_bucket
    while b < length:
        b *= 2
    return b
