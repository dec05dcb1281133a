"""Paged KV pool vs contiguous slot pool: token-for-token engine parity.

The paged engine reuses the contiguous prefill verbatim and feeds the same
attention math through block-table indirection, so greedy decoding must be
EXACTLY equal — any drift means a page aliased, a stale row unmasked, or a
boundary crossed wrong. Cases cover mixed prompt-length buckets, a slot
exhausting max_new_tokens mid-chunk, a page boundary crossed inside a
sync_every scan chunk, EOS stops, pool-pressure queueing, and a hybrid
model whose mamba2 state stays slot-addressed while attention KV pages.
"""
import jax
import numpy as np
import pytest

from repro.models import Model, ModelConfig
from repro.models.config import SSMConfig, repeat_pattern
from repro.serving import EngineConfig, Request, ServingEngine

PS = 8                                 # page size exercised in the suite


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-paged", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def run_engine(m, params, reqs, paged, **kw):
    args = dict(max_batch=4, max_len=64, sync_every=8,
                paged=paged, page_size=PS)
    args.update(kw)
    eng = ServingEngine(m, params, EngineConfig(**args))
    for r in reqs:
        eng.submit(Request(**r))
    resps = {r.rid: r for r in eng.run()}
    return resps, eng


def assert_parity(m, params, reqs, **kw):
    want, _ = run_engine(m, params, reqs, paged=False, **kw)
    got, eng = run_engine(m, params, reqs, paged=True, **kw)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished == want[rid].finished
    return eng


def assert_pool_clean(eng):
    """After a drained run every page is back on the stack, exactly once."""
    alloc = jax.device_get(eng.caches["paged"])
    P = alloc["free"].shape[0]
    assert int(alloc["top"]) == P
    assert (np.asarray(alloc["tbl"]) == -1).all()
    assert (np.asarray(alloc["ref"]) == 0).all()
    assert sorted(np.asarray(alloc["free"]).tolist()) == list(range(P))
    assert eng.free_pages == eng.num_pages


@pytest.mark.parametrize("num_pages", [None, 12],
                         ids=["equal_memory_pool", "12_pages"])
def test_mixed_prompt_lengths_token_for_token(parts, num_pages):
    """More requests than slots, prompts across several pow2 buckets and
    page counts; continuous batching with slot + page reuse throughout,
    on the equal-memory pool and on one small enough that recycled pages
    carry stale rows into decode's scoring of the pool."""
    _, m, params = parts
    rng = np.random.default_rng(7)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=9)
            for i, n in enumerate((3, 5, 8, 11, 16, 21, 4, 30))]
    eng = assert_parity(m, params, reqs, num_pages=num_pages)
    assert_pool_clean(eng)


def test_budget_exhausted_mid_chunk(parts):
    """max_new_tokens=5 dies on step 4 of an 8-step chunk: the slot must
    coast to the chunk boundary (trash page, no new allocations) and its
    pages must be reclaimed, while a long request rides the same chunks."""
    _, m, params = parts
    reqs = [dict(rid=0, prompt=[9, 8, 7], max_new_tokens=5),
            dict(rid=1, prompt=[1, 2, 3, 4], max_new_tokens=20)]
    eng = assert_parity(m, params, reqs)
    assert_pool_clean(eng)


def test_page_boundary_inside_sync_chunk(parts):
    """Prompt length 6 with page_size 8: the append at t=8 allocates a new
    page on micro-step 3 INSIDE the fused lax.scan chunk — alloc-on-write
    happens under jit, not at a host sync."""
    _, m, params = parts
    reqs = [dict(rid=0, prompt=[5, 4, 3, 2, 1, 6], max_new_tokens=12)]
    eng = assert_parity(m, params, reqs, sync_every=8)
    assert eng.stats()["peak_pages_reserved"] >= 3   # 6+11 tokens -> 3 pages
    assert_pool_clean(eng)


def test_eos_stop_matches_contiguous(parts):
    """EOS raised on device mid-chunk stops the paged slot exactly where
    the contiguous engine stops it."""
    _, m, params = parts
    probe, _ = run_engine(m, params,
                          [dict(rid=0, prompt=[9, 8, 7, 6, 5],
                                max_new_tokens=12)], paged=False)
    eos = probe[0].tokens[4]
    reqs = [dict(rid=0, prompt=[9, 8, 7, 6, 5], max_new_tokens=12,
                 eos_id=eos)]
    eng = assert_parity(m, params, reqs)
    assert_pool_clean(eng)


def test_pool_pressure_queues_and_completes(parts):
    """A pool much smaller than slots*max_len forces requests to wait for
    reclaimed pages; everyone still finishes with exact parity."""
    _, m, params = parts
    rng = np.random.default_rng(3)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, 10)),
                 max_new_tokens=8)
            for i in range(6)]
    # 10+7 tokens -> 3 pages reserved per request; 7 pages ~ 2 at a time
    eng = assert_parity(m, params, reqs, num_pages=7)
    assert eng.stats()["peak_pages_reserved"] <= 7
    assert_pool_clean(eng)


def test_hybrid_mamba2_state_stays_slot_addressed():
    """Hybrid mamba2+attention model: recurrent state rides the slot pool
    untouched while attention KV lives in pages — still token-for-token."""
    cfg = ModelConfig(
        name="tiny-hybrid", family="hybrid", n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab=128, dtype="float32",
        block_pattern=repeat_pattern(("mamba2", "dense"), 2),
        ssm=SSMConfig(state_dim=16, head_dim=16, chunk=4),
        vocab_pad_multiple=8)
    m = Model(cfg)
    assert m.supports_paged_decode
    params = m.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 128, int(n))),
                 max_new_tokens=7)
            for i, n in enumerate((4, 9, 13))]
    eng = assert_parity(m, params, reqs, max_batch=2)
    assert_pool_clean(eng)


def test_allocator_invariants_deterministic():
    """Always-on allocator check (the hypothesis sweep in
    test_page_allocator.py needs hypothesis installed): a fixed prefill /
    decode-growth / release interleaving preserves no-aliasing and page
    conservation, and reclaimed pages are reused."""
    from repro.serving import paged as PG
    B_, M_, P_ = 3, 4, 8
    alloc = PG.init_allocator(B_, M_, P_)

    def mapped():
        tbl = np.asarray(jax.device_get(alloc["tbl"]))
        return [tbl[b][tbl[b] >= 0].tolist() for b in range(B_)]

    def check():
        m = mapped()
        flat = sum(m, [])
        assert len(flat) == len(set(flat))              # no aliasing
        free = np.asarray(jax.device_get(alloc["free"]))
        top = int(jax.device_get(alloc["top"]))
        stack = free[:top].tolist()
        assert sorted(stack + flat) == list(range(P_))  # conservation
        return m

    alloc = PG.alloc_prefill_pages(alloc, np.asarray([0, 1]),
                                   np.asarray([2, 3]))   # 5 pages out
    assert [len(x) for x in check()] == [2, 3, 0]
    # slot 0 at a page boundary grows, inactive slot 1 must not
    alloc = PG.alloc_decode_pages(alloc, np.asarray([8, 9, 0]),
                                  np.asarray([True, False, False]), 4)
    assert [len(x) for x in check()] == [3, 3, 0]
    held = set(sum(mapped(), []))
    alloc = PG.release_slots(alloc, np.asarray([False, True, False]))
    assert [len(x) for x in check()] == [3, 0, 0]
    # reclaimed pages immediately back a new tenant
    alloc = PG.alloc_prefill_pages(alloc, np.asarray([2]), np.asarray([4]))
    assert [len(x) for x in check()] == [3, 0, 4]
    assert set(sum(mapped(), [])) <= held | set(range(P_))
    assert int(jax.device_get(alloc["top"])) == P_ - 7


def test_windowed_model_rejects_paged_mode(parts):
    """Ring eviction doesn't translate to pages: paged mode must refuse
    sliding-window configs instead of silently corrupting context."""
    cfg = ModelConfig(
        name="tiny-windowed", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), sliding_window=16,
        vocab_pad_multiple=8)
    m = Model(cfg)
    assert not m.supports_paged_decode
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(m, params, EngineConfig(max_batch=2, max_len=64,
                                              paged=True, page_size=PS))


def test_trash_page_writes_stay_shard_local():
    """Shard-stacked pools (the mesh-sharded fleet's layout): a slot with
    no mapped page (finished, coasting inside a fused chunk) writes its
    garbage into ITS shard's trash page — no other shard's pool leaf
    changes by a single byte. Lives here rather than the allocator
    property suite so it runs even without hypothesis installed."""
    import jax.numpy as jnp
    from repro.models.attention import paged_decode_write

    S, B, M, P, H, hd = 3, 4, 4, 10, 2, 4
    cache = {
        "k_pages": jnp.zeros((S, H, P + 1, PS, hd)),
        "v_pages": jnp.zeros((S, H, P + 1, PS, hd)),
        "pos_ids": jnp.full((S, B, M * PS), -1, jnp.int32),
        "length": jnp.zeros((S, B), jnp.int32),
    }
    tbl = jnp.full((S, B, M), -1, jnp.int32)   # nobody owns pages
    k1 = jnp.ones((S, B, 1, H, hd))
    out = jax.jit(jax.vmap(paged_decode_write))(cache, tbl, k1, k1)
    kp = np.asarray(out["k_pages"])
    for s in range(S):
        assert (kp[s, :, P] != 0).any(), "trash write missing on own shard"
        assert (kp[s, :, :P] == 0).all(), "write leaked into a real page"
