"""Paged KV pool vs contiguous slot pool: token-for-token engine parity.

The paged engine reuses the contiguous prefill verbatim and feeds the same
attention math through block-table indirection, so greedy decoding must be
EXACTLY equal — any drift means a page aliased, a stale row unmasked, or a
boundary crossed wrong. Cases cover mixed prompt-length buckets, a slot
exhausting max_new_tokens mid-chunk, a page boundary crossed inside a
sync_every scan chunk, EOS stops, pool-pressure queueing, and a hybrid
model whose mamba2 state stays slot-addressed while attention KV pages.

The stacked pool (page leaves carried through the layer scan, written in
place and donated to the served programs) has its own cases below, each
for the GQA and the latent (MLA) tiny model: the scan's structure and the
programs' donation, and decode parity across a page boundary inside an
8-step fused scan and for a finished slot writing to the trash page.
"""
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_model
from repro.models import Model, ModelConfig
from repro.models.config import SSMConfig, repeat_pattern
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving import engine as E
from repro.serving import paged as PG
from repro.serving import sampling

PS = 8                                 # page size exercised in the suite
KINDS = ("dense", "latent")            # GQA and MLA tiny models


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
    assert eng.free_pages == [eng.num_pages]


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


# ------------------------------------------------------- the stacked pool


@pytest.fixture(scope="module")
def kind_parts(request):
    return tiny_model(request.param, f"tiny-stacked-{request.param}")


def _scans(jaxpr):
    """Every scan equation in ``jaxpr`` and the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _scans(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _scans(sub)


def _aliased_outputs(lowered) -> set:
    """``jax.result_info`` of every output of the lowered program's main
    function that an input is donated to (``tf.aliasing_output``)."""
    text = lowered.as_text()
    main = next(l for l in text.splitlines() if "func.func public @main" in l)
    args, results = main.split(") -> (", 1)
    info = re.findall(r'jax.result_info = "([^"]*)"', results)
    return {info[int(n)] for n in
            re.findall(r"tf.aliasing_output = (\d+)", args)}


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("kind_parts", KINDS, indirect=True)
def test_stacked_pool_rides_the_carry_and_is_donated(kind_parts, program):
    """No layer scan of the decode step or the prompt chunk takes a page
    leaf of the stacked pool among its xs or hands one back among its ys
    (it rides the carry, written in place), and the served program
    (``_FUSED_STEPS`` / ``_CHUNK_PREFILL``) donates every page leaf to
    the output it becomes."""
    _, m, params = kind_parts
    B, W, P = 2, 32, 10
    caches = PG.paginate_cache(m.init_cache(B, W), B, PS, P)
    stacked = {tuple(g[n].shape) for g in caches["unit"].values()
               for n in g if n.endswith("_pages")}
    assert stacked, "the model scans no paged group"
    if program == "decode":
        jaxpr = jax.make_jaxpr(m.decode_step)(
            params, caches, jnp.zeros((B, 1), jnp.int32)).jaxpr
    else:
        view = PG.gather_slot_view(caches, jnp.zeros((1,), jnp.int32))
        jaxpr = jax.make_jaxpr(m.prefill_chunk)(
            params, view, jnp.zeros((1, 8), jnp.int32),
            jnp.ones((1, 8), jnp.int32)).jaxpr
    carried = False
    for eqn in _scans(jaxpr):
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = [v.aval.shape for v in eqn.invars[nc + nk:]]
        ys = [v.aval.shape for v in eqn.outvars[nk:]]
        assert not stacked & set(xs + ys), "a page leaf rides xs/ys"
        carried |= bool(stacked & {v.aval.shape
                                   for v in eqn.invars[nc:nc + nk]})
    assert carried, "no scan carries the stacked pool"

    key = jax.random.PRNGKey(0)
    if program == "decode":
        lowered = E._FUSED_STEPS.lower(
            m, params, caches, jnp.zeros((B, 1), jnp.int32),
            sampling.init_slot_state(B), key, n_steps=8, temperature=0.0,
            page_size=PS, freeze_inactive=True)
        out = "result[0]"
    else:
        tok = jnp.zeros((1, 8), jnp.int32)
        lowered = E._CHUNK_PREFILL.lower(
            m, params, caches, tok, tok, jnp.zeros((1,), jnp.int32), key,
            vocab=m.cfg.vocab, temperature=0.0, page_size=PS,
            sharing=True)
        out = "result[3]"
    # the cache tree: argument 2, the second of the non-static arguments
    donated = lowered.args_info[0][1]
    pages = {jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(caches)
             if jax.tree_util.keystr(path).endswith("_pages']")}
    assert all(a.donated for a in jax.tree_util.tree_leaves(donated))
    assert {out + p for p in pages} <= _aliased_outputs(lowered)


def _contiguous_parity(m, params, reqs, **kw):
    """Serve ``reqs`` on the contiguous cache and on the paged pool with
    the same settings; every request's tokens must agree."""
    want, _ = run_engine(m, params, reqs, paged=False, **kw)
    got, eng = run_engine(m, params, reqs, paged=True, **kw)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished == want[rid].finished
    return eng


@pytest.mark.parametrize("kind_parts", KINDS, indirect=True)
def test_page_boundary_inside_8_step_scan_stacked(kind_parts):
    """A 6-token prompt with 8-token pages: the fused 8-micro-step scan
    allocates and writes the slot's second page at t = 8 inside the scan,
    on the stacked pool of either attention kind."""
    _, m, params = kind_parts
    reqs = [dict(rid=0, prompt=[5, 4, 3, 2, 1, 6], max_new_tokens=12),
            dict(rid=1, prompt=[7, 1, 7], max_new_tokens=14)]
    eng = _contiguous_parity(m, params, reqs, sync_every=8)
    assert eng.stats()["peak_pages_reserved"] >= 5
    assert_pool_clean(eng)


@pytest.mark.parametrize("kind_parts", KINDS, indirect=True)
def test_finished_slot_writes_the_trash_page_stacked(kind_parts):
    """A request whose budget ends at micro-step 2 of an 8-step scan
    coasts to the scan's end writing into the trash page of every
    layer's slice of the stacked pool; the request beside it, and the
    next tenant of the slot, read none of it."""
    _, m, params = kind_parts
    reqs = [dict(rid=0, prompt=[9, 8, 7], max_new_tokens=3),
            dict(rid=1, prompt=[1, 2, 3, 4], max_new_tokens=20),
            dict(rid=2, prompt=[4, 4, 2], max_new_tokens=9)]
    eng = _contiguous_parity(m, params, reqs, sync_every=8, max_batch=2)
    trash = eng.num_pages
    for g in eng.caches["unit"].values():
        for n in g:
            if n.endswith("_pages"):
                rows = np.asarray(g[n])[:, :, trash]
                assert (rows != 0).any(axis=(1, 2, 3)).all(), \
                    f"{n}: a layer's trash page was never written"
    assert_pool_clean(eng)
