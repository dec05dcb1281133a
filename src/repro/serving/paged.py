"""Paged KV cache pool: shared page pool + per-slot block tables + an
on-device free-page-stack allocator.

The contiguous slot pool allocates ``max_len`` KV rows per slot per layer
whether or not a request ever uses them; provisioned-but-idle HBM is pure
embodied carbon (paper Eq. 2-4 — the footprint scales with installed
memory, not with traffic). Paging shares one physical pool of
``num_pages`` fixed-size pages across all slots, so the same GB serves
however many concurrent requests actually fit — GreenLLM / EcoServe both
assume this paged-attention-class baseline under their carbon policies.

Layout (per attention-cache leaf; head-major so appends/gathers are flat
single-row advanced indexing, and one (page, head) pair is one kernel
block)::

    k_pages / v_pages : (Hkv, num_pages + 1, page_size, hd)
    pos_ids           : (B, W) int32  — LOGICAL positions, -1 = empty
    length            : (B,)  int32

plus ONE shared allocator at ``caches["paged"]`` (every layer of a slot
has identical occupancy, so one block table serves all layers)::

    tbl  : (B, max_pages) int32 physical page per logical page, -1 = none
    free : (num_pages,)   int32 stack; free[:top] are free page ids
    top  : ()             int32 free-page count
    ref  : (num_pages,)   int32 per-page reference count (# block-table
                          entries mapping the page; 0 = free)

An MLA layer's latent leaf group has the same shape with other page
leaves: ``ckv_pages`` (1, num_pages + 1, page_size, kv_lora_rank) holds
the normed latent and ``krope_pages`` (1, ..., qk_rope_head_dim) the
roped key, one head each. Everything below walks a group's page leaves
(the keys ending in ``_pages``) whatever they are, so the allocator, the
prefix index, copy-on-write, scrub, export and migrate treat both alike.

Prefix sharing (PR 4): a physical page may be mapped by SEVERAL slots'
block tables when their prompts share a page-aligned prefix — the engine's
host-side prefix index maps token-chunk hashes to resident page runs and
``map_shared_prefix`` increfs them into a new slot's table, so the shared
prefix is provisioned once (the embodied-carbon lever: Eq. 2-4 charge per
request falls with deduplicated HBM). Release is decref-to-zero
(``release_slots``); a write into a page with refcount > 1 must first go
through copy-on-write (``cow_chunk_pages``): pop a fresh page, copy the
rows, swap the table entry, decref the original.

Page ``num_pages`` (the last row of the pools) is a TRASH page: writes
whose slot has no page mapped (finished slots coasting inside a fused
chunk, logical rows past the pool) land there, and gathers of unmapped
logical pages read from there — always masked because the *logical*
``pos_ids`` row is -1. Keeping positions logical (they cost W ints per
slot, not W*Hkv*hd) means a recycled physical page needs no scrubbing.

Allocator invariants (property-tested in tests/test_page_allocator.py and
tests/test_prefix_sharing.py):
  * ``ref[p]`` equals the number of live block-table entries mapping ``p``
    (writable pages have refcount exactly 1 — aliased WRITES are the bug
    class copy-on-write exists to prevent);
  * top + #uniquely-mapped == num_pages at every step (conservation:
    shared pages count once);
  * pages return to the free stack exactly at decref-to-zero, and are
    immediately reusable.

Alloc-on-write: ``alloc_decode_pages`` runs inside the fused decode scan
and pops a page only for ACTIVE slots crossing a page boundary
(``t % page_size == 0``); ``alloc_prefill_pages`` bulk-pops
ceil(len/page_size) pages per admitted request at insertion. The engine
admits by worst-case reservation (prompt + full decode budget), so the
device-side stack can never underflow mid-flight.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# layout ops live with the rest of the KV-cache code; re-exported here so
# serving code has one import surface for everything paged
from repro.models.attention import (PAGES, copy_page_rows,  # noqa: F401
                                    gather_pages, page_leaf_names,
                                    paged_decode_write)

# contiguous cache leaves that page, and the page leaf each becomes: an
# attention KV group's keys and values, an MLA group's latent and rope key
_PAGE_LEAF = {"k": "k_pages", "v": "v_pages", "ckv": "ckv_pages",
             "k_rope": "krope_pages"}
_SRC_LEAF = {v: k for k, v in _PAGE_LEAF.items()}
_CURSORS = {"pos_ids", "length"}
_KV_GROUPS = ({"k", "v"}, {"ckv", "k_rope"})


def _is_paged_group(d) -> bool:
    return (isinstance(d, dict) and _CURSORS <= set(d)
            and bool(page_leaf_names(d)))


# --------------------------------------------------------------- allocator


def init_allocator(max_batch: int, max_pages_per_slot: int,
                   num_pages: int) -> Dict[str, jax.Array]:
    return {
        "tbl": jnp.full((max_batch, max_pages_per_slot), -1, jnp.int32),
        "free": jnp.arange(num_pages, dtype=jnp.int32),
        "top": jnp.asarray(num_pages, jnp.int32),
        "ref": jnp.zeros((num_pages,), jnp.int32),
    }


def _set_ref(ref: jax.Array, pages: jax.Array, ok: jax.Array) -> jax.Array:
    """Mark freshly popped pages as singly referenced (scatter, drop-pad)."""
    P = ref.shape[0]
    idx = jnp.where(ok, pages, P).reshape(-1)
    return ref.at[idx].set(1, mode="drop")


def alloc_decode_pages(alloc: Dict, lengths: jax.Array, active: jax.Array,
                       page_size: int) -> Dict:
    """Pop one page for every ACTIVE slot whose next token starts a new
    logical page. lengths: (B,) tokens already cached; active: (B,) bool.
    Popped pages come off the free stack with refcount 0 and enter the
    table singly referenced — decode appends therefore never target a
    shared page (the engine's prefill CoW privatized any shared page the
    slot could still write; see cow_chunk_pages)."""
    tbl, free, top = alloc["tbl"], alloc["free"], alloc["top"]
    B, M = tbl.shape
    P = free.shape[0]
    lp = lengths // page_size
    need = active & (lengths % page_size == 0) & (lp < M)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1       # pop order (LIFO)
    take = top - 1 - rank
    pages = free[jnp.clip(take, 0, P - 1)]
    ok = need & (take >= 0)                             # guard underflow
    bidx = jnp.arange(B)
    lp_c = jnp.clip(lp, 0, M - 1)
    tbl = tbl.at[bidx, lp_c].set(
        jnp.where(ok, pages, tbl[bidx, lp_c]))
    return {"tbl": tbl, "free": free,
            "top": top - ok.astype(jnp.int32).sum(),
            "ref": _set_ref(alloc["ref"], pages, ok)}


def alloc_prefill_pages(alloc: Dict, slots: jax.Array,
                        n_pages: jax.Array) -> Dict:
    """Bulk-pop ``n_pages[i]`` pages for slot ``slots[i]`` and rewrite the
    slot's whole block-table row (stale entries from the previous tenant
    become -1). slots/n_pages: (n,) int32."""
    tbl, free, top = alloc["tbl"], alloc["free"], alloc["top"]
    M = tbl.shape[1]
    P = free.shape[0]
    need = jnp.arange(M)[None, :] < n_pages[:, None]    # (n, M)
    rank = jnp.cumsum(need.reshape(-1).astype(jnp.int32)) - 1
    take = (top - 1 - rank).reshape(need.shape)
    pages = free[jnp.clip(take, 0, P - 1)]
    ok = need & (take >= 0)
    tbl = tbl.at[slots].set(jnp.where(ok, pages, -1))
    return {"tbl": tbl, "free": free,
            "top": top - ok.astype(jnp.int32).sum(),
            "ref": _set_ref(alloc["ref"], pages, ok)}


def alloc_chunk_pages(alloc: Dict, slots: jax.Array, start_pg: jax.Array,
                      end_pg: jax.Array) -> Dict:
    """Pop pages for the logical page range [start_pg[i], end_pg[i]) of
    slot ``slots[i]``, preserving the slot's existing entries — the
    incremental counterpart of ``alloc_prefill_pages`` for chunked prefill
    (a prompt's pages materialize chunk by chunk instead of all at once).
    slots/start_pg/end_pg: (n,) int32. The engine admits by worst-case
    reservation, so the stack can never underflow mid-prompt."""
    tbl, free, top = alloc["tbl"], alloc["free"], alloc["top"]
    M = tbl.shape[1]
    P = free.shape[0]
    ar = jnp.arange(M)[None, :]
    need = (ar >= start_pg[:, None]) & (ar < end_pg[:, None])   # (n, M)
    rank = jnp.cumsum(need.reshape(-1).astype(jnp.int32)) - 1
    take = (top - 1 - rank).reshape(need.shape)
    pages = free[jnp.clip(take, 0, P - 1)]
    ok = need & (take >= 0)                             # guard underflow
    rows = jnp.where(ok, pages, tbl[slots])
    return {"tbl": tbl.at[slots].set(rows), "free": free,
            "top": top - ok.astype(jnp.int32).sum(),
            "ref": _set_ref(alloc["ref"], pages, ok)}


def map_shared_pages(alloc: Dict, slot: jax.Array,
                     pages: jax.Array) -> Dict:
    """Map an already-resident page run (``pages``: (max_pages,) physical
    ids, -1 padded) into logical pages 0.. of ``slot``'s block-table row,
    incrementing each page's refcount. The pages stay where their original
    owner popped them — this is the whole point: N slots, one copy."""
    tbl, free, top, ref = (alloc["tbl"], alloc["free"], alloc["top"],
                           alloc["ref"])
    P = free.shape[0]
    m = pages >= 0
    tbl = tbl.at[slot].set(jnp.where(m, pages, tbl[slot]))
    ref = ref.at[jnp.where(m, pages, P)].add(1, mode="drop")
    return {"tbl": tbl, "free": free, "top": top, "ref": ref}


def release_slots(alloc: Dict, released: jax.Array) -> Dict:
    """Decrement the refcount of every page mapped by the ``released``
    (B,) bool slots and clear their block-table rows; pages reaching
    refcount zero go back on the free stack (shared prefix pages survive
    until their LAST holder releases)."""
    tbl, free, top, ref = (alloc["tbl"], alloc["free"], alloc["top"],
                           alloc["ref"])
    P = free.shape[0]
    rel = released[:, None] & (tbl >= 0)
    pages = jnp.where(rel, tbl, P)                      # P = dropped
    drops = jnp.zeros((P,), jnp.int32).at[pages.reshape(-1)].add(
        1, mode="drop")                                 # decrefs per page
    ref = ref - drops
    freed = (drops > 0) & (ref <= 0)
    rank = jnp.cumsum(freed.astype(jnp.int32)) - 1
    dest = jnp.where(freed, top + rank, P)              # P = out of bounds
    free = free.at[dest].set(jnp.arange(P, dtype=jnp.int32), mode="drop")
    tbl = jnp.where(released[:, None], -1, tbl)
    return {"tbl": tbl, "free": free,
            "top": top + freed.astype(jnp.int32).sum(),
            "ref": jnp.maximum(ref, 0)}


def release_slots_keep(alloc: Dict, released: jax.Array,
                       n_keep: jax.Array) -> Dict:
    """Release the ``released`` (B,) bool slots but KEEP the refcounts of
    each slot's first ``n_keep[slot]`` logical pages — the
    release-for-preemption primitive. The kept pages' references are
    *transferred* to the engine's host-side pin (the evicted request's
    indexed prefix run must stay resident and adoptable for resume), so
    they are neither decrefed nor freed here; every later logical page
    (decode tail, unindexed chunk remainder) decrefs normally and returns
    to the stack at refcount zero. The whole block-table row is cleared
    either way — the slot is gone; only the pin (released via
    ``decref_pages`` after the resumed request re-adopts) still holds the
    kept pages. ``n_keep``: (B,) int32, 0 for slots not being preempted or
    with nothing indexed."""
    tbl, free, top, ref = (alloc["tbl"], alloc["free"], alloc["top"],
                           alloc["ref"])
    M = tbl.shape[1]
    P = free.shape[0]
    logical = jnp.arange(M)[None, :]
    rel = released[:, None] & (tbl >= 0) & (logical >= n_keep[:, None])
    pages = jnp.where(rel, tbl, P)                      # P = dropped
    drops = jnp.zeros((P,), jnp.int32).at[pages.reshape(-1)].add(
        1, mode="drop")
    ref = ref - drops
    freed = (drops > 0) & (ref <= 0)
    rank = jnp.cumsum(freed.astype(jnp.int32)) - 1
    dest = jnp.where(freed, top + rank, P)              # P = out of bounds
    free = free.at[dest].set(jnp.arange(P, dtype=jnp.int32), mode="drop")
    tbl = jnp.where(released[:, None], -1, tbl)
    return {"tbl": tbl, "free": free,
            "top": top + freed.astype(jnp.int32).sum(),
            "ref": jnp.maximum(ref, 0)}


def decref_pages(alloc: Dict, pages: jax.Array) -> Dict:
    """Drop one reference from each physical page in ``pages`` ((K,) int32,
    -1 padded); pages reaching refcount zero return to the free stack.
    This is how a preemption pin is released: the resumed request adopts
    the pinned run first (incref via ``map_shared_pages``), then the pin's
    transferred references are dropped here — or dropped without adoption
    when the preempted request is cancelled outright."""
    tbl, free, top, ref = (alloc["tbl"], alloc["free"], alloc["top"],
                           alloc["ref"])
    P = free.shape[0]
    pg = jnp.where(pages >= 0, pages, P)                # P = dropped
    drops = jnp.zeros((P,), jnp.int32).at[pg.reshape(-1)].add(
        1, mode="drop")
    ref = ref - drops
    freed = (drops > 0) & (ref <= 0)
    rank = jnp.cumsum(freed.astype(jnp.int32)) - 1
    dest = jnp.where(freed, top + rank, P)
    free = free.at[dest].set(jnp.arange(P, dtype=jnp.int32), mode="drop")
    return {"tbl": tbl, "free": free,
            "top": top + freed.astype(jnp.int32).sum(),
            "ref": jnp.maximum(ref, 0)}


def pages_needed(n_tokens: int, page_size: int) -> int:
    return -(-max(n_tokens, 0) // page_size)


def _walk_paged(leafgroup_fn, plain_fn, paged_fn, *trees):
    """Map parallel paged cache trees with one traversal skeleton.

    ``leafgroup_fn(stacked, *groups)`` handles paged leaf
    groups, ``plain_fn(stacked, *leaves)`` everything else (e.g. the
    position counter ``t``), ``paged_fn(*allocators)`` the shared
    allocator at key ``"paged"``. ``stacked`` is True under the scanned
    ``"unit"`` subtree, whose leaves carry batch on axis 1 instead of 0 —
    every chunked-prefill view/reset/freeze below shares this walk so a
    cache-layout change cannot drift between them.
    """
    def walk(nodes, stacked):
        n0 = nodes[0]
        if _is_paged_group(n0):
            return leafgroup_fn(stacked, *nodes)
        if isinstance(n0, dict):
            return {k: (paged_fn(*[nd[k] for nd in nodes]) if k == "paged"
                        else walk([nd[k] for nd in nodes],
                                  stacked or k == "unit"))
                    for k in n0}
        if isinstance(n0, (tuple, list)):
            return type(n0)(walk(list(vs), stacked) for vs in zip(*nodes))
        return plain_fn(stacked, *nodes)

    return walk(list(trees), False)


def freeze_inactive_cursors(old: Dict, new: Dict,
                            active: jax.Array) -> Dict:
    """Keep INACTIVE slots' per-slot write cursors (``t`` / ``pos_ids`` /
    ``length``) at their pre-step values after a fused decode micro-step.

    The fused step is batch-shape invariant: every slot writes a KV row per
    micro-step, active or not. Released slots' garbage lands in the trash
    page (block-table row cleared), but a slot that is mid-CHUNKED-PREFILL
    has mapped pages and a cursor pointing at its next prompt row — letting
    the decode write advance it would corrupt the chunk schedule. Freezing
    the cursor pins the garbage write to the slot's next-unwritten row
    (overwritten by the next real chunk/decode write before any query can
    unmask it) and keeps the logical position bookkeeping exact. Pool
    pages are taken from ``new`` untouched. Only reached from chunked
    engines (attention-only models), so every plain leaf is batch-leading.
    """
    def leafgroup(stacked, o, n):
        act = active[None, :, None] if stacked else active[:, None]
        actl = active[None, :] if stacked else active
        return {**n,
                "pos_ids": jnp.where(act, n["pos_ids"], o["pos_ids"]),
                "length": jnp.where(actl, n["length"], o["length"])}

    def plain(stacked, o, n):
        return jnp.where(active[None] if stacked else active, n, o)

    return _walk_paged(leafgroup, plain, lambda o, n: n, old, new)


# ----------------------------------------------------------- cache layout


def _data_leaves(d) -> list:
    """A contiguous cache group's leaves that page: (k, v) of attention,
    (ckv, k_rope) of MLA; [] for anything else."""
    if not (isinstance(d, dict) and _CURSORS <= set(d)):
        return []
    for names in _KV_GROUPS:
        if names <= set(d):
            return [n for n in d if n in names]
    return []


def _paginate_leafgroup(d: Dict, page_size: int, num_pages: int) -> Dict:
    out = {}
    lead = d["pos_ids"].shape[:-2]   # () or (repeats,)
    for name in _data_leaves(d):
        # ([R,] B, W, Hkv, hd), or ([R,] B, W, width) for the latent
        x = d[name]
        heads = x.shape[len(lead) + 2:-1]            # (Hkv,) or ()
        W = x.shape[len(lead) + 1]
        assert W % page_size == 0, "cache width must be a page multiple"
        out[_PAGE_LEAF[name]] = jnp.zeros(
            lead + (heads or (1,)) + (num_pages + 1, page_size, x.shape[-1]),
            x.dtype)
    out["pos_ids"] = d["pos_ids"]    # stays LOGICAL: ([R,] B, W)
    out["length"] = d["length"]
    return out


def _walk(node, fn):
    """Map ``fn`` over kv leaf-groups of a cache tree, preserving layout."""
    if _data_leaves(node):
        return fn(node)
    if isinstance(node, dict):
        return {k: _walk(v, fn) for k, v in node.items()}
    if isinstance(node, (tuple, list)):
        return type(node)(_walk(v, fn) for v in node)
    return node


def paginate_cache(cache: Dict, max_batch: int, page_size: int,
                   num_pages: int) -> Dict:
    """Convert a contiguous slot-pool cache (model.init_cache) into the
    paged layout and attach the shared allocator at cache['paged']."""
    widths = []
    _walk(cache, lambda d: (widths.append(d["pos_ids"].shape[-1]), d)[1])
    assert widths, "model has no attention KV caches to page"
    assert len(set(widths)) == 1, "paged pool needs uniform cache width"
    W = widths[0]
    paged = _walk(cache, lambda d: _paginate_leafgroup(d, page_size,
                                                       num_pages))
    paged["paged"] = init_allocator(max_batch, W // page_size, num_pages)
    return paged


# --------------------------------------------------------------- insertion


def insert_prefill_paged(pool, src, slots: jax.Array, cur_tokens: jax.Array,
                         first_tokens: jax.Array, state: Dict,
                         budgets: jax.Array, eos_ids: jax.Array,
                         page_size: int) -> Tuple:
    """Paged counterpart of ``sampling.insert_prefill``: bulk-allocate
    ceil(len/page_size) pages per admitted request, then scatter the
    contiguous prefill cache rows into the pages — one scatter per leaf
    for the whole admission batch, exactly like the contiguous path.

    pool: paged cache tree (with pool['paged']); src: contiguous prefill
    cache tree (batch >= n, leaves (n_pad, W, ...)); slots/budgets/eos_ids:
    (n,). Logical rows whose page is unmapped (past the request's length)
    scatter into the trash page.
    """
    n = slots.shape[0]
    true_len = src["t"][:n]
    n_pages = -(-true_len // page_size)
    alloc = alloc_prefill_pages(pool["paged"], slots, n_pages)

    # physical page per (request, logical page), shared by all layers;
    # logical pages past the request's allocation point at the trash page
    row_tbl = alloc["tbl"][slots]                        # (n, M)
    M = row_tbl.shape[1]

    def scatter_rows(pages, src, stacked):
        # page-granular scatter: pages ([R,] H, P+1, ps, hd)
        #                        <- src ([R,] n_pad, W, H, hd)
        if src.ndim == pages.ndim - 1:               # latent: one head
            src = src[..., None, :]
        trash = pages.shape[-3] - 1
        pg = jnp.where(row_tbl < 0, trash, row_tbl)      # (n, M)
        ps, hd = page_size, pages.shape[-1]
        if stacked:
            sv = jnp.moveaxis(src[:, :n], 3, 1)          # (R, H, n, W, hd)
            sv = sv.reshape(sv.shape[0], sv.shape[1], n, M, ps, hd)
            return pages.at[:, :, pg].set(sv.astype(pages.dtype))
        sv = jnp.moveaxis(src[:n], 2, 0)                 # (H, n, W, hd)
        sv = sv.reshape(sv.shape[0], n, M, ps, hd)
        return pages.at[:, pg].set(sv.astype(pages.dtype))

    def leafgroup(d: Dict, s: Dict, stacked: bool) -> Dict:
        if stacked:
            pos = d["pos_ids"].at[:, slots].set(s["pos_ids"][:, :n])
            ln = d["length"].at[:, slots].set(s["length"][:, :n])
        else:
            pos = d["pos_ids"].at[slots].set(s["pos_ids"][:n])
            ln = d["length"].at[slots].set(s["length"][:n])
        return {**{leaf: scatter_rows(d[leaf], s[_SRC_LEAF[leaf]], stacked)
                   for leaf in page_leaf_names(d)},
                "pos_ids": pos, "length": ln}

    def walk(p, s, stacked):
        if p is None:
            return None
        if _is_paged_group(p):
            return leafgroup(p, s, stacked)
        if isinstance(p, dict):
            return {k: (walk(v, s[k], stacked or k == "unit")
                        if k != "paged" else alloc)
                    for k, v in p.items()}
        if isinstance(p, (tuple, list)):
            return type(p)(walk(pv, sv, stacked) for pv, sv in zip(p, s))
        # plain leaf (e.g. the position counter "t"): slot scatter
        if stacked:
            return p.at[:, slots].set(s[:, :n].astype(p.dtype))
        return p.at[slots].set(s[:n].astype(p.dtype))

    pool = walk(pool, src, False)
    from repro.serving import sampling
    cur_tokens, state = sampling.arm_slots(cur_tokens, state, slots,
                                           first_tokens, budgets, eos_ids)
    return pool, cur_tokens, state


# ----------------------------------------------------- chunked prefill view


def begin_chunked_prefill(pool: Dict, slots: jax.Array) -> Dict:
    """Reset the admitted slots' per-slot cache rows for a fresh chunked
    prefill: logical positions all-empty, lengths/counters zero. Pool pages
    and block-table rows are untouched — a released tenant already cleared
    its table row, and its stale pool rows are unreachable behind
    ``pos_ids == -1``."""
    def rows(d, value, stacked):
        return (d.at[:, slots].set(value) if stacked
                else d.at[slots].set(value))

    def leafgroup(stacked, p):
        return {**p, "pos_ids": rows(p["pos_ids"], -1, stacked),
                "length": rows(p["length"], 0, stacked)}

    return _walk_paged(leafgroup,
                       lambda stacked, p: rows(p, 0, stacked),
                       lambda p: p, pool)


def quarantine_table(alloc: Dict, do: jax.Array) -> Dict:
    """Route-invalidate a DEAD lane's pool when ``do`` (scalar bool) is
    set: clear every block-table row to -1 so the batch-shape-invariant
    decode/prefill writes that keep riding the SPMD programs land in the
    trash page — exactly like a released slot — instead of real pages.

    This is deliberately NOT a release: refcounts, the free stack, the
    top cursor, and every KV payload page stay bit-identical. The dead
    pool is unreachable, never mutated; ``scrub_pool`` rebuilds it from
    nothing at rejoin. (Without this, a dead lane's disarmed-but-mapped
    slots would keep scattering garbage into pages the shard still
    formally owns — the no-dead-pool-touch contract pins that down.)"""
    return dict(alloc, tbl=jnp.where(do, -1, alloc["tbl"]))


def scrub_pool(pool: Dict, do: jax.Array) -> Dict:
    """Rebuild a pool to its virgin post-``paginate_cache`` state when
    ``do`` (scalar bool) is set; return it untouched otherwise.

    This is the REJOIN primitive for shard recovery: a dead shard's pool
    contents are untrusted, so re-entry starts from nothing — allocator
    reset to the full free stack (``init_allocator`` layout: table all
    -1, free = arange, top = P, ref = 0) and every slot's cursors
    cleared (``pos_ids`` = -1, ``length``/``t`` = 0). KV page payloads
    are NOT zeroed: positions are logical, so stale rows are unreachable
    behind ``pos_ids == -1`` exactly as after an ordinary release — the
    same argument ``begin_chunked_prefill`` relies on. The ``do`` flag
    makes this safe inside a fleet-wide ``shard_map`` program where only
    the rejoining lane scrubs and every other lane keeps its pool."""
    def leafgroup(stacked, p):
        return {**p,
                "pos_ids": jnp.where(do, -1, p["pos_ids"]),
                "length": jnp.where(do, 0, p["length"])}

    def plain(stacked, p):
        return jnp.where(do, jnp.zeros_like(p), p)

    def alloc(a):
        P = a["free"].shape[0]
        return {
            "tbl": jnp.where(do, -1, a["tbl"]),
            "free": jnp.where(do, jnp.arange(P, dtype=jnp.int32),
                              a["free"]),
            "top": jnp.where(do, jnp.asarray(P, jnp.int32), a["top"]),
            "ref": jnp.where(do, 0, a["ref"]),
        }

    return _walk_paged(leafgroup, plain, alloc, pool)


def map_shared_prefix(pool: Dict, slot: jax.Array, pages: jax.Array,
                      n_shared: jax.Array, start_tok: jax.Array) -> Dict:
    """Adopt an already-resident prefix into a freshly admitted slot.

    ``pages``: (max_pages,) physical page ids from the engine's prefix
    index, -1 padded; they cover logical tokens [0, n_shared). The run is
    increfed into the slot's block table (``map_shared_pages``), the
    slot's logical rows [0, n_shared) are marked as valid history
    (``pos_ids`` = 0..n_shared-1 — the shared pool rows already hold the
    prefix KV, so they unmask immediately), and the write cursors
    (``length`` / ``t``) are set to ``start_tok``, the first token the
    slot will actually COMPUTE. ``start_tok`` < ``n_shared`` only when
    the whole prompt is shared: the last prompt token is recomputed to
    produce first-token logits, and that write lands in a shared page —
    which is exactly what ``cow_chunk_pages`` privatizes first."""
    alloc = map_shared_pages(pool["paged"], slot, pages)

    def rows(d, value, stacked):
        if stacked:
            value = jnp.broadcast_to(value, d.shape[:1] + jnp.shape(value))
            return d.at[:, slot].set(value)
        return d.at[slot].set(value)

    def leafgroup(stacked, p):
        W = p["pos_ids"].shape[-1]
        posrow = jnp.where(jnp.arange(W) < n_shared, jnp.arange(W), -1)
        return {**p, "pos_ids": rows(p["pos_ids"], posrow, stacked),
                "length": rows(p["length"], start_tok, stacked)}

    def plain(stacked, p):
        return rows(p, start_tok.astype(p.dtype), stacked)

    return _walk_paged(leafgroup, plain, lambda a: alloc, pool)


def cow_chunk_pages(pool: Dict, slots: jax.Array, start_tok: jax.Array,
                    n_tok: jax.Array, page_size: int, span: int) -> Dict:
    """Copy-on-write for the logical pages the next chunk write touches.

    slots/start_tok/n_tok: (n,) int32 — the chunk writes tokens
    [start_tok, start_tok + n_tok) of each slot. ``span`` (static) bounds
    the pages one chunk can touch (chunk_tokens // page_size + 1). Any
    touched page mapped with refcount > 1 is privatized BEFORE the write:
    pop a fresh page, copy its rows in every KV leaf, swap the table
    entry, decref the original. Sole-owner pages (refcount 1) are written
    in place. The engine's worst-case reservation covers these pops, so
    the stack cannot underflow."""
    alloc = pool["paged"]
    tbl, free, top, ref = (alloc["tbl"], alloc["free"], alloc["top"],
                           alloc["ref"])
    M = tbl.shape[1]
    P = free.shape[0]
    lp = start_tok[:, None] // page_size + jnp.arange(span)[None, :]
    last = (start_tok + jnp.maximum(n_tok, 1) - 1) // page_size
    valid = (n_tok[:, None] > 0) & (lp <= last[:, None]) & (lp < M)
    phys = tbl[slots[:, None], jnp.clip(lp, 0, M - 1)]   # (n, span)
    do = valid & (phys >= 0) & (ref[jnp.clip(phys, 0, P - 1)] > 1)
    rank = jnp.cumsum(do.reshape(-1).astype(jnp.int32)) - 1
    take = (top - 1 - rank).reshape(do.shape)
    fresh = free[jnp.clip(take, 0, P - 1)]
    ok = do & (take >= 0)                                # guard underflow
    tbl = tbl.at[slots[:, None], lp].set(jnp.where(ok, fresh, phys),
                                         mode="drop")
    dec = jnp.zeros((P,), jnp.int32).at[
        jnp.where(ok, phys, P).reshape(-1)].add(1, mode="drop")
    ref = ref - dec
    ref = ref.at[jnp.where(ok, fresh, P).reshape(-1)].set(1, mode="drop")
    # two slots CoW-ing the SAME page in one call each decref it: a page
    # dropping to zero here has no holders left and must return to the
    # stack (conservation), exactly as in release_slots
    new_top = top - ok.astype(jnp.int32).sum()
    freed = (dec > 0) & (ref <= 0)
    rank_f = jnp.cumsum(freed.astype(jnp.int32)) - 1
    dest = jnp.where(freed, new_top + rank_f, P)
    free = free.at[dest].set(jnp.arange(P, dtype=jnp.int32), mode="drop")
    alloc = {"tbl": tbl, "free": free,
             "top": new_top + freed.astype(jnp.int32).sum(),
             "ref": jnp.maximum(ref, 0)}
    src_pg = jnp.where(ok, phys, 0).reshape(-1)
    dst_pg = jnp.where(ok, fresh, -1).reshape(-1)        # -1 = dropped

    def leafgroup(stacked, d):
        return {**d, **{n: copy_page_rows(d[n], src_pg, dst_pg)
                        for n in page_leaf_names(d)}}

    return _walk_paged(leafgroup, lambda stacked, x: x,
                       lambda a: alloc, pool)


def _rows_key(page_leaf: str) -> str:
    """An exported payload's key for a page leaf: ``k_pages`` ->
    ``k_rows``."""
    return page_leaf[:-len(PAGES)] + "_rows"


def export_slot(pool: Dict, slot: jax.Array, src_pg: jax.Array) -> Dict:
    """Dense, pool-independent payload of one slot's cache state — the
    SEND half of a cross-pool page migration.

    ``src_pg``: (M,) int32, the slot's physical pages in logical order,
    -1 padded (its block-table row). Every page leaf contributes the
    page rows at those physical ids (``<name>_rows`` for
    ``<name>_pages``, e.g. ``k_rows``/``v_rows``: ([R,] H, M, ps, hd) —
    padded entries gather the trash page, whose contents are never read
    back), plus the slot's cursors (``pos_ids``/``length``/``t``).
    The payload mirrors the cache tree's structure, so ``migrate_pages``
    can walk both in lockstep. An out-of-range ``slot`` (the fleet
    sentinel ``B``) clamps — callers mask the result before use."""
    def leafgroup(stacked, p):
        names = page_leaf_names(p)
        pg = jnp.where(src_pg < 0, p[names[0]].shape[-3] - 1, src_pg)
        return {
            **{_rows_key(n): jnp.take(p[n], pg, axis=-3) for n in names},
            "pos_ids": (p["pos_ids"][:, slot] if stacked
                        else p["pos_ids"][slot]),
            "length": (p["length"][:, slot] if stacked
                       else p["length"][slot]),
        }

    def plain(stacked, p):
        return p[:, slot] if stacked else p[slot]

    return _walk_paged(leafgroup, plain, lambda a: None, pool)


def migrate_pages(pool: Dict, slot: jax.Array, payload: Dict,
                  n_pages: jax.Array) -> Dict:
    """RECEIVE half of a cross-pool page migration: pop ``n_pages`` fresh
    pages off THIS pool's free stack, rewrite ``slot``'s whole block-table
    row to them (stale entries become -1), scatter the payload's KV rows
    into the popped pages, and restore the slot's cursors — the migrated
    slot is bit-identical to the source slot, on private pages.

    ``payload`` is an ``export_slot`` tree (typically transferred across
    shards by the caller). Popped pages enter the table singly referenced
    — shared-prefix runs arrive as private COPIES; re-registering them in
    the destination's prefix index is host-side policy (the
    copy-then-reindex handoff). A sentinel ``slot`` (one past the batch)
    with ``n_pages`` = 0 makes the whole call a provable no-op lane: no
    pops, every scatter drops — the fleet program needs no per-lane
    control flow."""
    alloc = pool["paged"]
    tbl, free, top, ref = (alloc["tbl"], alloc["free"], alloc["top"],
                           alloc["ref"])
    M = tbl.shape[1]
    P = free.shape[0]
    need = jnp.arange(M) < n_pages                      # (M,)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    take = top - 1 - rank
    pages = free[jnp.clip(take, 0, P - 1)]
    ok = need & (take >= 0)                             # guard underflow
    tbl = tbl.at[slot].set(jnp.where(ok, pages, -1), mode="drop")
    alloc = {"tbl": tbl, "free": free,
             "top": top - ok.astype(jnp.int32).sum(),
             "ref": _set_ref(ref, pages, ok)}

    def scatter_rows(pages_leaf, rows):
        # popped physical page per logical page; not-ok -> P+1, dropped
        dst = jnp.where(ok, pages, pages_leaf.shape[-3])
        if pages_leaf.ndim == 4:
            return pages_leaf.at[:, dst].set(rows.astype(pages_leaf.dtype),
                                             mode="drop")
        return pages_leaf.at[:, :, dst].set(rows.astype(pages_leaf.dtype),
                                            mode="drop")

    def rows_at(d, value, stacked):
        if stacked:
            return d.at[:, slot].set(value.astype(d.dtype), mode="drop")
        return d.at[slot].set(value.astype(d.dtype), mode="drop")

    def leafgroup(stacked, p, pl):
        return {**{n: scatter_rows(p[n], pl[_rows_key(n)])
                   for n in page_leaf_names(p)},
                "pos_ids": rows_at(p["pos_ids"], pl["pos_ids"], stacked),
                "length": rows_at(p["length"], pl["length"], stacked)}

    return _walk_paged(leafgroup,
                       lambda stacked, p, pl: rows_at(p, pl, stacked),
                       lambda a, b: alloc, pool, payload)


def gather_slot_view(pool: Dict, slots: jax.Array) -> Dict:
    """Batch-n view of the paged cache tree for a chunked-prefill step:
    per-slot leaves (``pos_ids``/``length``/``t``) are gathered to rows
    ``slots``, the shared page pools ride through whole, and the allocator
    is reduced to the slots' block-table rows (all a forward pass needs).
    ``scatter_slot_view`` writes the per-slot rows back afterwards."""
    def rows(d, stacked):
        return d[:, slots] if stacked else d[slots]

    def leafgroup(stacked, p):
        return {**p, "pos_ids": rows(p["pos_ids"], stacked),
                "length": rows(p["length"], stacked)}

    return _walk_paged(leafgroup, lambda stacked, p: rows(p, stacked),
                       lambda p: {"tbl": p["tbl"][slots]}, pool)


def scatter_slot_view(pool: Dict, view: Dict, slots: jax.Array) -> Dict:
    """Fold a chunk-updated ``gather_slot_view`` tree back into the full
    cache: shared pools are taken from the view (the chunk wrote them),
    per-slot rows scatter into ``slots``, and the allocator stays the
    pool's (the view only carried read-only table rows)."""
    def rows(d, s, stacked):
        return d.at[:, slots].set(s) if stacked else d.at[slots].set(s)

    def leafgroup(stacked, p, v):
        return {**{n: v[n] for n in page_leaf_names(p)},
                "pos_ids": rows(p["pos_ids"], v["pos_ids"], stacked),
                "length": rows(p["length"], v["length"], stacked)}

    return _walk_paged(leafgroup,
                       lambda stacked, p, v: rows(p, v, stacked),
                       lambda p, v: p, pool, view)
