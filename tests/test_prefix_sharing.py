"""Page-level prefix sharing with copy-on-write: token-for-token parity,
refcounted-allocator invariants, and the CoW edge cases.

The sharing engine adopts resident pages by refcount and computes only the
unshared suffix, so greedy decoding must be EXACTLY equal to the unshared
paged engine — any drift means a shared page was written without CoW, a
stale index entry mapped a recycled page, or the adopted history unmasked
wrong rows. The invariants the design rests on:

  * ``ref[p]`` == number of live block-table entries mapping ``p``;
  * ``top`` + #uniquely-mapped pages == ``num_pages`` (shared pages
    conserve ONCE — the embodied-carbon dedup);
  * no write (prefill chunk or decode append) ever lands in a page with
    refcount > 1 — copy-on-write privatizes first;
  * pages return to the free stack exactly at decref-to-zero, whichever
    sibling releases last.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_model
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving import paged as PG

PS = 4                                 # page size exercised in the suite
CH = 8                                 # prefill chunk size
# dense KV pages and MLA's latent pages through the same sharing paths
KINDS = pytest.mark.parametrize("parts", ["dense", "latent"], indirect=True)


@pytest.fixture(scope="module")
def parts(request):
    return tiny_model(getattr(request, "param", "dense"), "tiny-prefix")


class CheckedEngine(ServingEngine):
    """ServingEngine with the refcounted invariants asserted at every
    scheduling quantum (device state is fetched and cross-checked — slow,
    test-only)."""

    def _alloc_state(self):
        a = jax.device_get(self.caches["paged"])
        return (np.asarray(a["tbl"]), np.asarray(a["free"]),
                int(a["top"]), np.asarray(a["ref"]))

    def check_alloc(self):
        tbl, free, top, ref = self._alloc_state()
        P = ref.shape[0]
        counts = np.zeros((P,), int)
        for row in tbl:
            for p in row[row >= 0]:
                counts[p] += 1
        assert (ref == counts).all(), "device refcounts != mapping counts"
        unique = int((counts > 0).sum())
        assert top + unique == P, "page conservation (shared counted once)"
        stack = free[:top].tolist()
        assert len(set(stack)) == top, "free stack duplicate"
        assert not set(stack) & set(np.flatnonzero(counts).tolist()), \
            "mapped page on the free stack"

    def _prefill_quantum(self):
        head = self._prefilling[0][0] if self._prefilling[0] else None
        pos0 = head[0].prefill_pos if head else 0
        ran = super()._prefill_quantum()
        if head and ran:
            req, slot = head
            nv = max(req.prefill_pos - pos0, 1)
            if self.slot_rid[0][slot] == req.rid or req.prefill_pos < len(
                    req.prompt):
                tbl, _, _, ref = self._alloc_state()
                for lp in range(pos0 // PS, (pos0 + nv - 1) // PS + 1):
                    p = int(tbl[slot, lp])
                    if p >= 0:
                        assert ref[p] == 1, \
                            "chunk wrote a page with refcount > 1 (no CoW)"
        self.check_alloc()
        return ran

    def _decode_chunk(self, max_steps):
        # every page a slot can write during this chunk must be private
        tbl, _, _, ref = self._alloc_state()
        for s in range(self.cfg.max_batch):
            if self._slot_armed[0][s]:
                t = int(self._slot_ctx[0][s])
                for lp in range(t // PS,
                                min((t + self.cfg.sync_every - 1) // PS,
                                    tbl.shape[1] - 1) + 1):
                    p = int(tbl[s, lp])
                    if p >= 0:
                        assert ref[p] <= 1, \
                            "decode would append into a shared page"
        ran = super()._decode_chunk(max_steps)
        self.check_alloc()
        return ran


def run_engine(m, params, reqs, sharing, checked=True, **kw):
    args = dict(max_batch=4, max_len=64, sync_every=4, paged=True,
                page_size=PS, prefill_chunk=CH, prefix_sharing=sharing)
    args.update(kw)
    cls = CheckedEngine if checked else ServingEngine
    eng = cls(m, params, EngineConfig(**args))
    for r in reqs:
        eng.submit(Request(**r))
    resps = {r.rid: r for r in eng.run()}
    return resps, eng


def assert_parity(m, params, reqs, **kw):
    want, _ = run_engine(m, params, reqs, sharing=False, checked=False, **kw)
    got, eng = run_engine(m, params, reqs, sharing=True, **kw)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished == want[rid].finished
        assert got[rid].rejected == want[rid].rejected
    return got, eng


def assert_pool_clean(eng):
    alloc = jax.device_get(eng.caches["paged"])
    P = alloc["free"].shape[0]
    assert int(alloc["top"]) == P
    assert (np.asarray(alloc["tbl"]) == -1).all()
    assert (np.asarray(alloc["ref"]) == 0).all()
    assert sorted(np.asarray(alloc["free"]).tolist()) == list(range(P))
    assert eng.free_pages == [eng.num_pages]
    assert not eng._prefix_index[0] and not eng._page_key[0] \
        and not eng._page_ref[0]


# ------------------------------------------------------------------ parity

# the equal-memory pool, and one small enough that pages are recycled
POOLS = pytest.mark.parametrize("num_pages", [None, 20],
                                ids=["equal_memory_pool", "20_pages"])


def test_no_common_prefix_is_inert(parts):
    """Distinct prompts: the sharing machinery must change nothing —
    token-for-token with the unshared engine, zero index hits."""
    _, m, params = parts
    rng = np.random.default_rng(11)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=7)
            for i, n in enumerate((3, 6, 9, 13, 5))]
    _, eng = assert_parity(m, params, reqs)
    assert eng.prefix_hit_tokens == 0
    assert_pool_clean(eng)


@KINDS
@POOLS
def test_shared_system_prompt_parity_and_hits(parts, num_pages):
    """A long-lived donor plus followers repeating its 2-page system
    prompt: followers admitted after the donor registered must adopt the
    prefix (hits > 0) and still decode token-for-token."""
    _, m, params = parts
    rng = np.random.default_rng(3)
    prefix = list(rng.integers(0, 256, 2 * PS))
    reqs = [dict(rid=0, prompt=prefix + [7, 9, 11], max_new_tokens=30)]
    reqs += [dict(rid=i, prompt=prefix + list(rng.integers(0, 256, 2 + i)),
                  max_new_tokens=5) for i in range(1, 4)]
    got, eng = assert_parity(m, params, reqs, max_batch=2,
                             num_pages=num_pages)
    # rid 1 rides with the donor (no index yet); rids 2-3 enter later,
    # while the donor still decodes, and hit its registered prefix
    assert eng.prefix_shared_requests >= 2
    assert eng.prefix_hit_tokens >= 2 * (2 * PS)
    assert_pool_clean(eng)


@POOLS
def test_three_requests_share_then_diverge(parts, num_pages):
    """Chain-keyed matching: a follower matching 2 pages then diverging
    adopts exactly 2; one diverging inside page 1 adopts exactly 1 (rid 1
    rides the donor's admission wave so rids 2-3 enter one at a time
    against a registered index)."""
    _, m, params = parts
    rng = np.random.default_rng(5)
    base = list(rng.integers(0, 256, 3 * PS))
    two_pages = base[:2 * PS] + [251, 252, 253, 254, 250]  # diverges at pg 2
    one_page = base[:PS + 2] + [249] * 6                   # diverges in pg 1
    reqs = [dict(rid=0, prompt=base + [1, 2], max_new_tokens=40),
            dict(rid=1, prompt=[99, 98, 97], max_new_tokens=2),
            dict(rid=2, prompt=two_pages, max_new_tokens=5),
            dict(rid=3, prompt=one_page, max_new_tokens=5)]
    got, eng = assert_parity(m, params, reqs, max_batch=2,
                             num_pages=num_pages)
    assert eng.prefix_hit_tokens == 2 * PS + PS
    assert_pool_clean(eng)


@POOLS
def test_prefix_ends_mid_page_tail_is_private(parts, num_pages):
    """A follower whose prompt extends past the shared pages mid-page:
    only whole pages are adopted; the partial tail is computed into a
    private page (no aliased writes — the checked engine asserts it)."""
    _, m, params = parts
    rng = np.random.default_rng(9)
    prefix = list(rng.integers(0, 256, 2 * PS))
    reqs = [dict(rid=0, prompt=prefix + [3], max_new_tokens=40),
            dict(rid=1, prompt=prefix + [17, 19], max_new_tokens=6),
            dict(rid=2, prompt=prefix + [17, 19, 23], max_new_tokens=6)]
    got, eng = assert_parity(m, params, reqs, max_batch=2,
                             num_pages=num_pages)
    assert eng.prefix_hit_tokens >= 2 * PS
    assert_pool_clean(eng)


# ------------------------------------------------- whole-prompt share + CoW


@KINDS
def test_whole_prompt_shared_triggers_cow(parts):
    """Follower prompt == 3 whole shared pages: the last token is
    recomputed for first-token logits, which writes into the shared tail
    page — copy-on-write must privatize it (fresh physical page for the
    follower, donor's page back to refcount 1), and decoding must match
    the unshared engine token-for-token."""
    _, m, params = parts
    rng = np.random.default_rng(13)
    prompt = list(rng.integers(0, 256, 3 * PS))
    donor = dict(rid=0, prompt=prompt, max_new_tokens=10)
    follower = dict(rid=1, prompt=list(prompt), max_new_tokens=4)

    eng = CheckedEngine(m, params, EngineConfig(
        max_batch=2, max_len=64, sync_every=4, paged=True, page_size=PS,
        prefill_chunk=CH, prefix_sharing=True, num_pages=8))
    eng.submit(Request(**donor))
    eng._admit()
    while eng._prefilling[0]:
        eng._prefill_quantum()
    assert len(eng._prefix_index[0]) == 3   # donor registered 3 pages
    d_row = np.asarray(jax.device_get(eng.caches["paged"]["tbl"]))[0]

    eng.submit(Request(**follower))
    eng._admit()
    tbl, _, _, ref = eng._alloc_state()
    f_slot = eng.slot_rid[0].index(1)
    assert tbl[f_slot, :3].tolist() == d_row[:3].tolist()   # fully adopted
    assert all(ref[p] == 2 for p in d_row[:3])
    assert eng._prefilling[0][0][0].prefill_pos == 3 * PS - 1  # recompute tail

    eng._prefill_quantum()                  # the 1-token CoW chunk
    tbl, _, _, ref = eng._alloc_state()
    assert tbl[f_slot, :2].tolist() == d_row[:2].tolist()   # still shared
    assert tbl[f_slot, 2] != d_row[2], "tail page was not copied"
    assert ref[d_row[2]] == 1 and ref[tbl[f_slot, 2]] == 1

    resps = {r.rid: r for r in eng.run()}
    want, _ = run_engine(m, params, [donor, follower], sharing=False,
                         checked=False, max_batch=2, num_pages=8)
    for rid in want:
        assert resps[rid].tokens == want[rid].tokens
    assert_pool_clean(eng)


@pytest.mark.parametrize("leaves", [("k_pages", "v_pages"),
                                    ("ckv_pages", "krope_pages")],
                         ids=["kv", "latent"])
def test_cow_copies_page_rows_exactly(leaves):
    """Allocator+pool level: cow_chunk_pages must copy the page's rows in
    every page leaf bit-for-bit into the fresh page and leave the original
    untouched (a KV group's keys and values, a latent group's latent and
    rope key)."""
    P, B, M = 6, 2, 3
    alloc = PG.init_allocator(B, M, P)
    alloc = PG.alloc_prefill_pages(alloc, jnp.asarray([0]),
                                   jnp.asarray([2]))
    pages = jnp.asarray([-1] * M).at[:2].set(alloc["tbl"][0, :2])
    alloc = PG.map_shared_pages(alloc, jnp.asarray(1), pages)
    rng = np.random.default_rng(0)
    kv = jnp.asarray(rng.normal(size=(1, P + 1, PS, 2)), jnp.float32)
    first, second = leaves
    tree = {"layer": {first: kv, second: kv + 1.0,
                      "pos_ids": jnp.full((B, M * PS), -1, jnp.int32),
                      "length": jnp.zeros((B,), jnp.int32)},
            "t": jnp.zeros((B,), jnp.int32), "paged": alloc}
    # slot 1 writes token 2*PS-1 (inside shared page 1) -> CoW page 1
    out = PG.cow_chunk_pages(tree, jnp.asarray([1]),
                             jnp.asarray([2 * PS - 1]), jnp.asarray([1]),
                             PS, span=2)
    a = jax.device_get(out["paged"])
    old = int(jax.device_get(alloc["tbl"])[0, 1])
    new = int(np.asarray(a["tbl"])[1, 1])
    assert new != old
    assert int(np.asarray(a["ref"])[old]) == 1
    assert int(np.asarray(a["ref"])[new]) == 1
    assert int(np.asarray(a["tbl"])[1, 0]) == int(np.asarray(a["tbl"])[0, 0])
    for name, src in ((first, kv), (second, kv + 1.0)):
        got = np.asarray(jax.device_get(out["layer"][name]))
        np.testing.assert_array_equal(got[:, new], got[:, old])
        np.testing.assert_array_equal(got[:, old],
                                      np.asarray(jax.device_get(src))[:, old])
    # untouched pages are bit-identical
    assert int(a["top"]) == P - 3


def test_cow_same_page_two_slots_one_call_conserves():
    """Two slots CoW-ing the SAME shared page in one batched call (the
    future batched-chunk quantum) must each get a private copy AND return
    the orphaned original to the free stack — not leak it at refcount 0."""
    P, B, M = 8, 3, 2
    alloc = PG.init_allocator(B, M, P)
    alloc = PG.alloc_prefill_pages(alloc, jnp.asarray([0]), jnp.asarray([1]))
    page = alloc["tbl"][0, :1]
    run = jnp.full((M,), -1, jnp.int32).at[:1].set(page)
    alloc = PG.map_shared_pages(alloc, jnp.asarray(1), run)
    alloc = PG.map_shared_pages(alloc, jnp.asarray(2), run)
    # slot 0 releases: page survives on refcount 2 (slots 1 and 2)
    alloc = PG.release_slots(alloc, jnp.asarray([True, False, False]))
    kv = jnp.zeros((1, P + 1, PS, 2))
    tree = {"layer": {"k_pages": kv, "v_pages": kv,
                      "pos_ids": jnp.full((B, M * PS), -1, jnp.int32),
                      "length": jnp.zeros((B,), jnp.int32)},
            "t": jnp.zeros((B,), jnp.int32), "paged": alloc}
    out = PG.cow_chunk_pages(tree, jnp.asarray([1, 2]),
                             jnp.asarray([PS - 1, PS - 1]),
                             jnp.asarray([1, 1]), PS, span=1)
    a = jax.device_get(out["paged"])
    p0 = int(jax.device_get(page)[0])
    p1, p2 = int(np.asarray(a["tbl"])[1, 0]), int(np.asarray(a["tbl"])[2, 0])
    assert len({p0, p1, p2}) == 3, "each writer needs a private copy"
    assert int(np.asarray(a["ref"])[p0]) == 0
    # conservation: 2 pages mapped, 6 free — the orphan came back
    assert int(a["top"]) == P - 2
    stack = np.asarray(a["free"])[:int(a["top"])].tolist()
    assert p0 in stack, "orphaned original must return to the free stack"
    assert sorted(stack + [p1, p2]) == list(range(P))


# ----------------------------------------------------- release ordering


def test_donor_finishes_first_pages_survive(parts):
    """Donor releases while a follower still decodes over the adopted
    pages: decref leaves them resident (refcount 1), the follower's
    attention stays exact, and the pool drains clean afterwards."""
    _, m, params = parts
    rng = np.random.default_rng(17)
    prefix = list(rng.integers(0, 256, 2 * PS))
    reqs = [dict(rid=0, prompt=prefix + [5], max_new_tokens=30),
            dict(rid=1, prompt=prefix + [5], max_new_tokens=2),  # twin wave
            dict(rid=2, prompt=prefix + [8, 9], max_new_tokens=25)]
    got, eng = assert_parity(m, params, reqs, max_batch=2)
    assert eng.prefix_shared_requests >= 1
    assert_pool_clean(eng)


def test_follower_finishes_first_then_donor(parts):
    """Reverse order: the short follower decrefs and exits first; the
    donor keeps its pages to the end. Both orders must leave zero refs."""
    _, m, params = parts
    rng = np.random.default_rng(19)
    prefix = list(rng.integers(0, 256, 2 * PS))
    reqs = [dict(rid=0, prompt=prefix + [5, 6], max_new_tokens=30),
            dict(rid=1, prompt=prefix + [5], max_new_tokens=3),
            dict(rid=2, prompt=prefix + [4, 2, 1], max_new_tokens=3)]
    got, eng = assert_parity(m, params, reqs, max_batch=2)
    assert eng.prefix_shared_requests >= 1
    assert_pool_clean(eng)


# ------------------------------------------------------ capacity + config


def test_shared_prefix_multiplies_concurrency(parts):
    """Equal pool bytes, prefix-heavy workload: sharing must pack >= 2x
    the concurrent requests (the embodied-carbon claim), because only the
    unshared worst case is reserved."""
    _, m, params = parts
    rng = np.random.default_rng(23)
    prefix = list(rng.integers(0, 256, 4 * PS))          # 16-token prefix
    reqs = [dict(rid=i, prompt=prefix + list(rng.integers(0, 256, 2)),
                 max_new_tokens=4) for i in range(5)]
    reqs[0]["max_new_tokens"] = 12                       # donor outlives
    # donor reserves 8 pages, each follower needs 6 unshared but only 2
    # (suffix + decode budget + CoW allowance) once the prefix is resident
    kw = dict(max_batch=4, num_pages=10)
    base, b_eng = run_engine(m, params, reqs, sharing=False, checked=False,
                             **kw)
    got, eng = assert_parity(m, params, reqs, **kw)
    assert b_eng.peak_active == 1                         # page-limited
    assert eng.peak_active >= 2 * b_eng.peak_active
    st = eng.stats()
    assert st["shared_pages"] >= 4
    assert st["unique_pages"] == st["peak_pages_reserved"]
    assert st["peak_kv_rows_reserved"] <= eng.num_pages * PS
    assert_pool_clean(eng)


def test_sharing_requires_chunked_prefill(parts):
    _, m, params = parts
    with pytest.raises(ValueError, match="prefix_sharing"):
        ServingEngine(m, params, EngineConfig(
            max_batch=2, max_len=64, paged=True, page_size=PS,
            prefix_sharing=True))


# ------------------------------------------- allocator-level refcounting


def test_refcounted_release_frees_at_zero():
    """map_shared_pages / release_slots at allocator level: pages free
    exactly when the LAST holder decrefs, in either release order."""
    for order in ((0, 1), (1, 0)):
        alloc = PG.init_allocator(3, 4, 8)
        alloc = PG.alloc_prefill_pages(alloc, jnp.asarray([0]),
                                       jnp.asarray([3]))
        shared = jax.device_get(alloc["tbl"])[0, :2]
        pages = jnp.full((4,), -1, jnp.int32).at[:2].set(jnp.asarray(shared))
        alloc = PG.map_shared_pages(alloc, jnp.asarray(1), pages)
        a = jax.device_get(alloc)
        assert [int(a["ref"][p]) for p in shared] == [2, 2]
        assert int(a["top"]) == 8 - 3                 # shared conserve once
        first, second = order
        mask = np.zeros((3,), bool)
        mask[first] = True
        alloc = PG.release_slots(alloc, jnp.asarray(mask))
        a = jax.device_get(alloc)
        assert [int(a["ref"][p]) for p in shared] == [1, 1]
        # slot 0's private 3rd page frees with slot 0, not before
        assert int(a["top"]) == (6 if first == 0 else 5)
        mask = np.zeros((3,), bool)
        mask[second] = True
        alloc = PG.release_slots(alloc, jnp.asarray(mask))
        a = jax.device_get(alloc)
        assert int(a["top"]) == 8
        assert (np.asarray(a["ref"]) == 0).all()
        assert sorted(np.asarray(a["free"]).tolist()) == list(range(8))
