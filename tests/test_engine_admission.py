"""Admission under pool-capacity limits (regression: the paged engine used
to be able to admit a request whose prompt could not fit the page pool and
fail mid-prefill; it must instead keep the request waiting until pages free
up, or reject it outright when it can NEVER fit)."""
import jax
import numpy as np
import pytest

from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern
from repro.serving import EngineConfig, Request, ServingEngine


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-admit", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def paged_engine(m, params, **kw):
    args = dict(max_batch=4, max_len=64, sync_every=8, paged=True,
                page_size=8)
    args.update(kw)
    return ServingEngine(m, params, EngineConfig(**args))


def test_oversized_prompt_rejected_not_admitted(parts):
    """Prompt needs more pages than the TOTAL pool: rejected without a
    prefill; concurrent fitting requests are unaffected."""
    m, params = parts
    eng = paged_engine(m, params, num_pages=4)       # 32-token pool
    eng.submit(Request(rid=0, prompt=list(range(1, 41)), max_new_tokens=5))
    eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=5))
    resps = {r.rid: r for r in eng.run()}
    assert resps[0].rejected and resps[0].finished and not resps[0].tokens
    assert not resps[1].rejected and len(resps[1].tokens) == 5
    assert eng.prefill_batches == 1                  # rid 0 never prefilled
    assert eng.free_pages == [eng.num_pages]           # nothing leaked


def test_request_waits_for_free_pages_then_completes(parts):
    """Reservation exceeds the REMAINING pool while another request holds
    pages: the newcomer must wait (not fail), then run to completion once
    reclamation frees capacity."""
    m, params = parts
    # 6 pages; each request reserves ceil((10+7)/8) = 3 -> two at a time
    eng = paged_engine(m, params, num_pages=6)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 256, 10)),
                           max_new_tokens=8))
    resps = {r.rid: r for r in eng.run()}
    assert all(not r.rejected and r.finished and len(r.tokens) == 8
               for r in resps.values())
    assert eng.stats()["peak_pages_reserved"] <= 6
    assert eng.free_pages == [eng.num_pages]


def test_fcfs_no_overtaking_under_pressure(parts):
    """A big request at the head must not be starved by small ones slipping
    past it: admission stops at the first request that doesn't fit."""
    m, params = parts
    eng = paged_engine(m, params, num_pages=8)
    eng.submit(Request(rid=0, prompt=list(range(1, 31)),  # 30+9 -> 5 pages
                       max_new_tokens=10))
    eng.submit(Request(rid=1, prompt=list(range(1, 25)),  # 24+9 -> 5 pages
                       max_new_tokens=10))
    eng.submit(Request(rid=2, prompt=[1, 2], max_new_tokens=2))
    eng.run()
    resps = eng.responses
    assert all(r.finished and not r.rejected for r in resps.values())
    # rid 1 did not fit next to rid 0 (5+5 > 8) and rid 2 must not have
    # jumped the queue: peak concurrency stays 1 until rid 0 finishes
    assert eng.stats()["peak_active"] <= 2


def test_decode_budget_past_max_len_rejected_in_paged_mode(parts):
    """Pages have no ring eviction: a request whose prompt + decode budget
    exceeds max_len cannot be represented in the block table and must be
    rejected up front — NOT admitted into silent context loss (the
    contiguous engine ring-wraps the same request and still serves it)."""
    m, params = parts
    eng = paged_engine(m, params, max_len=32)        # 4 pages of 8 per slot
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5, 6, 7, 8],
                       max_new_tokens=64))           # 8 + 63 >> 32
    eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=4))
    resps = {r.rid: r for r in eng.run()}
    assert resps[0].rejected and not resps[0].tokens
    assert resps[1].finished and len(resps[1].tokens) == 4
    # the contiguous engine still accepts it (ring keeps the last W tokens)
    ceng = ServingEngine(m, params, EngineConfig(max_batch=4, max_len=32))
    ceng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5, 6, 7, 8],
                        max_new_tokens=64))
    cresps = {r.rid: r for r in ceng.run()}
    assert cresps[0].finished and len(cresps[0].tokens) == 64


def test_prompt_exactly_at_capacity_is_admitted(parts):
    """Boundary: a request whose worst-case reservation equals the whole
    pool is legal and must be admitted alone."""
    m, params = parts
    eng = paged_engine(m, params, num_pages=5)
    eng.submit(Request(rid=0, prompt=list(range(1, 33)),  # 32+8 = 40 -> 5
                       max_new_tokens=9))
    resps = {r.rid: r for r in eng.run()}
    assert resps[0].finished and not resps[0].rejected
    assert len(resps[0].tokens) == 9
    assert eng.free_pages == [eng.num_pages]


# ----------------------------------------------- per-tenant rate limiting


def test_rate_limit_hard_budget_sheds_over_quota(parts):
    """refill=0 makes the bucket a hard budget: capacity submissions per
    tenant pass, the rest come back as terminal ``rate_limited``
    responses without ever touching the queue."""
    m, params = parts
    eng = paged_engine(m, params,
                       tenant_quota={"acme": (2, 0.0), "*": (1, 0.0)})
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[1, 2, 3], max_new_tokens=4,
                           tenant="acme"))
    # unknown tenant falls back to the "*" default bucket
    for i in range(4, 6):
        eng.submit(Request(rid=i, prompt=[1, 2, 3], max_new_tokens=4,
                           tenant="zorg"))
    # untracked submissions are never limited
    eng.submit(Request(rid=9, prompt=[1, 2, 3], max_new_tokens=4))
    shed = [r for r in eng.responses.values()
            if r.finish_reason == "rate_limited"]
    assert sorted(r.rid for r in shed) == [2, 3, 5]
    assert all(r.finished and not r.tokens for r in shed)
    assert eng.stats()["rate_limited"] == 3
    got = {r.rid: r for r in eng.run()}
    for rid in (0, 1, 4, 9):
        assert got[rid].finished and got[rid].finish_reason != "rate_limited"
        assert len(got[rid].tokens) == 4


def test_rate_limit_bucket_refills_over_wall_clock(parts):
    """Continuous refill: after the bucket drains, waiting refill_per_s
    wall-clock restores admission (capped at capacity)."""
    import time as _time
    m, params = parts
    eng = paged_engine(m, params, tenant_quota={"acme": (1, 50.0)})
    eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2,
                       tenant="acme"))
    eng.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=2,
                       tenant="acme"))
    assert eng.responses[1].finish_reason == "rate_limited"
    _time.sleep(0.05)                  # 50 tokens/s * 0.05s >= 1 token
    eng.submit(Request(rid=2, prompt=[1, 2], max_new_tokens=2,
                       tenant="acme"))
    assert eng.responses[2].finish_reason != "rate_limited"
    assert eng.stats()["rate_limited"] == 1


def test_tenant_quota_validation(parts):
    m, params = parts
    with pytest.raises(ValueError, match="capacity"):
        paged_engine(m, params, tenant_quota={"a": (0, 1.0)})
    with pytest.raises(ValueError, match="refill"):
        paged_engine(m, params, tenant_quota={"a": (1, -1.0)})
