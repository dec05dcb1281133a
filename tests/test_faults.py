"""Fault injection and recovery: every launch site, rollback exactness,
exponential backoff, retry exhaustion, and allocator invariants under
faults.

The recovery contract (serving/faults.py): a faulted launch never ran, so
the engine must release that quantum's reservations, keep (or re-queue)
the in-flight requests, retry after exponential backoff, and end with the
SAME tokens as a fault-free run — faults may only cost time, never
correctness, and never leak a page."""
import jax
import numpy as np
import pytest

from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern
from repro.serving import (EngineConfig, FaultError, FaultInjector,
                           FaultPlan, HealthMonitor, Request, ServingEngine)

PS = 4
CH = 8

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-faults", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def make_engine(m, params, **kw):
    args = dict(max_batch=2, max_len=64, sync_every=4, paged=True,
                page_size=PS, prefill_chunk=CH)
    args.update(kw)
    return ServingEngine(m, params, EngineConfig(**args))


def _reqs(n=3, max_new=8):
    return [dict(rid=i, prompt=list(RNG.integers(0, 256, 6 + 2 * i)),
                 max_new_tokens=max_new) for i in range(n)]


def run_with_faults(m, params, reqs, plans, **kw):
    eng = make_engine(m, params, **kw)
    eng.faults = FaultInjector(plans)
    for r in reqs:
        eng.submit(Request(**r))
    got = {r.rid: r for r in eng.run()}
    return got, eng


def assert_pool_clean(eng):
    alloc = jax.device_get(eng.caches["paged"])
    P = alloc["free"].shape[0]
    assert int(alloc["top"]) == P
    assert (np.asarray(alloc["tbl"]) == -1).all()
    assert (np.asarray(alloc["ref"]) == 0).all()
    assert eng.free_pages == [eng.num_pages]


# ----------------------------------------------------------- site-by-site


@pytest.mark.parametrize("site,at", [
    ("page_alloc", 1),      # first admission pass
    ("prefill_chunk", 2),   # mid-prefill
    ("prefill_chunk", 1),   # the very first chunk
    ("decode_scan", 4),     # mid-decode
])
def test_single_fault_full_recovery(parts, site, at):
    """One injected fault at each site: the run completes with tokens
    identical to the fault-free run, the fault actually fired, at least
    one retry was burned, and the pool drains clean."""
    _, m, params = parts
    reqs = _reqs()
    want, _ = run_with_faults(m, params, reqs, [])
    got, eng = run_with_faults(m, params, reqs,
                               [FaultPlan(site, at_quantum=at)])
    assert eng.faults.fired, f"planned fault at {site} q{at} never fired"
    assert eng.fault_retries >= 1
    assert not eng._backoff            # recovered, nothing backing off
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished
    assert_pool_clean(eng)


def test_page_alloc_rollback_exact(parts):
    """An admission fault returns EVERY page of the quantum's reservations
    and restores the takes at the queue head in order."""
    _, m, params = parts
    eng = make_engine(m, params)
    eng.faults = FaultInjector([FaultPlan("page_alloc", at_quantum=1)])
    reqs = _reqs(2)
    for r in reqs:
        eng.submit(Request(**r))
    free0 = list(eng.free_pages)
    order0 = [r.rid for r in eng.queue]
    assert eng.step() == 0             # the faulted quantum: no progress
    assert eng.free_pages == free0, "rollback leaked reservation pages"
    assert [r.rid for r in eng.queue] == order0, "rollback reordered queue"
    assert not eng._resv
    assert eng.peak_pages_reserved == [0], \
        "faulted reservations must not count as provisioned peak"
    got = {r.rid: r for r in eng.run()}
    assert all(r.finished for r in got.values())
    assert_pool_clean(eng)


def test_consecutive_faults_backoff_schedule(parts):
    """Consecutive faults retry at exponentially growing quantum gaps
    (2**fails); the retry past max_retries is the straw that raises."""
    _, m, params = parts
    eng = make_engine(m, params, max_retries=3)
    eng.faults = FaultInjector(
        [FaultPlan("prefill_chunk", at_quantum=1, count=30)])
    for r in _reqs(1):
        eng.submit(Request(**r))
    with pytest.raises(FaultError, match="prefill_chunk"):
        eng.run()
    fired = [q for s, q in eng.faults.fired]
    assert len(fired) == 3 + 1         # max_retries retries + final straw
    gaps = np.diff(fired)
    assert gaps.tolist() == [2, 4, 8], f"backoff gaps {gaps}"
    # a transient window shorter than the cumulative backoff recovers:
    # fires at rel q 1, 3, 7 — the retry at 15 lands past the window
    got, eng2 = run_with_faults(
        m, params, _reqs(1),
        [FaultPlan("prefill_chunk", at_quantum=1, count=7)],
        max_retries=3)
    assert len(eng2.faults.fired) == 3
    assert all(r.finished for r in got.values())
    assert_pool_clean(eng2)


def test_retry_exhaustion_raises_fault_error_state_consistent(parts):
    """A permanently failing site raises FaultError out of run(); the
    engine state is still consistent (reservations returned for the
    admission site, nothing double-freed) and — the recovery guarantee —
    clearing the injector lets the SAME engine finish correctly."""
    _, m, params = parts
    reqs = _reqs(2)
    want, _ = run_with_faults(m, params, reqs, [])
    eng = make_engine(m, params, max_retries=2)
    eng.faults = FaultInjector([FaultPlan("page_alloc", at_quantum=0,
                                          count=100)])
    for r in reqs:
        eng.submit(Request(**r))
    with pytest.raises(FaultError, match="page_alloc"):
        eng.run()
    assert eng.free_pages == [eng.num_pages]   # reservations all returned
    assert len(eng.queue) == len(reqs)       # nothing dropped
    eng.faults = None                        # "the device came back"
    eng._backoff.clear()
    got = {r.rid: r for r in eng.run()}
    for rid in want:
        assert got[rid].tokens == want[rid].tokens
    assert_pool_clean(eng)


def test_decode_fault_never_double_emits(parts):
    """A decode-scan fault relaunches the identical chunk: no token is
    lost or emitted twice even with EOS terminations mid-chunk."""
    _, m, params = parts
    probe, _ = run_with_faults(m, params, [dict(rid=0, prompt=[9, 8, 7],
                                                max_new_tokens=12)], [])
    eos = probe[0].tokens[5]
    reqs = [dict(rid=0, prompt=[9, 8, 7], max_new_tokens=12, eos_id=eos),
            dict(rid=1, prompt=[1, 2, 3, 4], max_new_tokens=10)]
    want, _ = run_with_faults(m, params, reqs, [])
    got, eng = run_with_faults(
        m, params, reqs,
        [FaultPlan("decode_scan", at_quantum=3, count=3)])
    # fires at rel q 3, then the backoff retry at rel q 5 (still inside
    # the 3-quantum window) fires again; the next retry at rel 9 succeeds
    assert len(eng.faults.fired) == 2
    for rid in want:
        assert got[rid].tokens == want[rid].tokens
    assert_pool_clean(eng)


def test_faults_at_every_site_same_run(parts):
    """All three sites fault in one run (disjoint quanta): recovery
    composes."""
    _, m, params = parts
    reqs = _reqs(3, max_new=10)
    want, _ = run_with_faults(m, params, reqs, [])
    got, eng = run_with_faults(m, params, reqs, [
        FaultPlan("page_alloc", at_quantum=1),
        FaultPlan("prefill_chunk", at_quantum=4),
        FaultPlan("decode_scan", at_quantum=8),
    ])
    sites = {s for s, _ in eng.faults.fired}
    assert sites == {"page_alloc", "prefill_chunk", "decode_scan"}
    for rid in want:
        assert got[rid].tokens == want[rid].tokens
    assert_pool_clean(eng)
    assert eng.stats()["fault_retries"] == eng.fault_retries >= 3


def test_faults_with_sharing_and_preemption(parts):
    """Faults during a preemption-heavy sharing run: the composed
    machinery (pins, CoW, rollback) still ends token-exact and clean."""
    _, m, params = parts
    common = list(RNG.integers(0, 256, 8))
    reqs = [dict(rid=0, prompt=common + [3, 1], max_new_tokens=24),
            dict(rid=1, prompt=common + [4, 1, 5], max_new_tokens=24)]
    high = dict(rid=2, prompt=[6, 2, 8], max_new_tokens=4, priority=1)
    want_all, _ = run_with_faults(
        m, params, reqs + [dict(**high)], [], max_batch=4,
        prefix_sharing=True)
    eng = make_engine(m, params, prefix_sharing=True, preemption=True)
    eng.faults = FaultInjector([
        FaultPlan("decode_scan", at_quantum=5),
        FaultPlan("prefill_chunk", at_quantum=8),
    ])
    for r in reqs:
        eng.submit(Request(**r))
    for _ in range(6):
        eng.step()
    eng.submit(Request(**high))
    got = {r.rid: r for r in eng.run()}
    assert eng.faults.fired
    for rid in want_all:
        assert got[rid].tokens == want_all[rid].tokens, \
            f"request {rid} diverged"
    assert_pool_clean(eng)


def _watch_donation(monkeypatch):
    """Wrap the two launches that donate the cache tree: count launches,
    and those after which every page leaf handed in is deleted (the pool
    went to the output in place, so nothing may read the old tree)."""
    from repro.serving import engine as E
    seen = {"launches": 0, "donated": 0}

    def watch(real):
        def launch(model, params, caches, *a, **kw):
            pages = [x for path, x in
                     jax.tree_util.tree_leaves_with_path(caches)
                     if jax.tree_util.keystr(path).endswith("_pages']")]
            out = real(model, params, caches, *a, **kw)
            seen["launches"] += 1
            seen["donated"] += all(x.is_deleted() for x in pages)
            return out
        return launch
    for name in ("_FUSED_STEPS", "_CHUNK_PREFILL"):
        monkeypatch.setattr(E, name, watch(getattr(E, name)))
    return seen


@pytest.mark.parametrize("site", ["prefill_chunk", "decode_scan"])
def test_donated_pool_through_retry_preemption_and_stats(parts, site,
                                                         monkeypatch):
    """The decode scan and the chunk program donate the pool: a launch
    that follows a faulted one (the fault fires before the launch, so
    the engine still holds the live tree), a preemption that pins the
    victim's prefix and re-prefills it, and ``stats()`` all run on the
    tree the last launch returned; no host path reads a deleted array,
    and the tokens are the fault-free run's."""
    _, m, params = parts
    common = list(RNG.integers(0, 256, 8))
    reqs = [dict(rid=0, prompt=common + [3, 1], max_new_tokens=24),
            dict(rid=1, prompt=common + [4, 1, 5], max_new_tokens=24)]
    high = dict(rid=2, prompt=[6, 2, 8], max_new_tokens=4, priority=1)
    want, _ = run_with_faults(m, params, reqs + [dict(**high)], [],
                              max_batch=4, prefix_sharing=True)
    seen = _watch_donation(monkeypatch)
    eng = make_engine(m, params, prefix_sharing=True, preemption=True)
    eng.faults = FaultInjector([FaultPlan(site, at_quantum=q)
                                for q in (2, 7)])
    for r in reqs:
        eng.submit(Request(**r))
    for _ in range(6):
        eng.step()
    eng.submit(Request(**high))
    got = {r.rid: r for r in eng.run()}
    assert {s for s, _ in eng.faults.fired} == {site}
    assert eng.stats()["fault_retries"] >= 1
    assert eng.stats()["preemption_count"] >= 1
    assert seen["launches"] > 0 and seen["donated"] == seen["launches"]
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(eng.caches))
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
    assert_pool_clean(eng)


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan("warp_core", at_quantum=0)
    with pytest.raises(ValueError, match="at_quantum"):
        FaultPlan("decode_scan", at_quantum=-1)
    with pytest.raises(ValueError, match="count"):
        FaultPlan("decode_scan", at_quantum=0, count=0)


def test_fault_plan_shard_validation():
    with pytest.raises(ValueError, match="shard >= 0"):
        FaultPlan("shard_down", at_quantum=1)
    with pytest.raises(ValueError, match="only apply to shard_down"):
        FaultPlan("decode_scan", at_quantum=1, shard=0)
    p = FaultPlan("shard_down", at_quantum=1, shard=2)
    assert p.shard == 2


def test_fault_plan_admin_validation():
    """Admin plans (drain / power_cap) validate like shard_down — they
    name a shard — and ``watts`` is power_cap-only and positive."""
    with pytest.raises(ValueError, match="shard >= 0"):
        FaultPlan("drain", at_quantum=1)
    with pytest.raises(ValueError, match="shard >= 0"):
        FaultPlan("power_cap", at_quantum=1)
    with pytest.raises(ValueError, match="watts only applies"):
        FaultPlan("drain", at_quantum=1, shard=0, watts=150.0)
    with pytest.raises(ValueError, match="watts must be > 0"):
        FaultPlan("power_cap", at_quantum=1, shard=0, watts=0.0)
    p = FaultPlan("power_cap", at_quantum=1, shard=1, watts=120.0)
    assert p.shard == 1 and p.watts == 120.0
    assert FaultPlan("drain", at_quantum=0, shard=0).watts is None


def test_injector_admin_fires_schedule():
    """Admin plans fire through the non-raising admin hook, log to
    .fired, and never enter the raising launch-site path. The default
    random draw (admin off) keeps the pre-admin site universe."""
    inj = FaultInjector([
        FaultPlan("drain", at_quantum=2, shard=1),
        FaultPlan("power_cap", at_quantum=3, shard=0, watts=100.0),
        FaultPlan("decode_scan", at_quantum=2),
    ])
    assert inj.admin_fires(1) == []
    fired = inj.admin_fires(2)
    assert [p.site for p in fired] == ["drain"]
    assert [p.site for p in inj.admin_fires(3)] == ["power_cap"]
    assert ("drain", 2) in inj.fired and ("power_cap", 3) in inj.fired
    inj.check("page_alloc", 2, 0)       # admin sites never raise here
    from repro.serving.faults import ADMIN_SITES
    assert all(p.site not in ADMIN_SITES
               for p in FaultPlan.random(42, n=20, shards=4))
    with pytest.raises(ValueError, match="shards"):
        FaultPlan.random(1, sites=("drain",))
    # admin without a fleet size is a no-op on the draw, not an error
    assert all(p.site not in ADMIN_SITES
               for p in FaultPlan.random(1, n=6, admin=True))


def test_fault_plan_random_reproducible_and_valid():
    """Same seed, same campaign — and every drawn plan passes the
    constructor's own validation (shard_down plans carry a shard in
    range, launch plans carry none)."""
    a = FaultPlan.random(42, n=10, max_quantum=8, max_count=3, shards=4)
    assert a == FaultPlan.random(42, n=10, max_quantum=8, max_count=3,
                                 shards=4)
    assert len(a) == 10
    for p in a:
        assert 0 <= p.at_quantum <= 8
        if p.site == "shard_down":
            assert p.count == 1 and 0 <= p.shard < 4
        else:
            assert 1 <= p.count <= 3 and p.shard is None
    # different seeds diverge (overwhelmingly)
    assert a != FaultPlan.random(43, n=10, max_quantum=8, max_count=3,
                                 shards=4)
    # without a fleet size, shard_down never enters the draw
    assert all(p.site != "shard_down"
               for p in FaultPlan.random(42, n=20))
    with pytest.raises(ValueError, match="shards"):
        FaultPlan.random(1, sites=("shard_down",))


def test_injector_shard_down_fires_schedule():
    """shard_down plans fire through the dedicated non-raising hook, log
    to .fired, and respect the relative/absolute time base."""
    inj = FaultInjector([
        FaultPlan("shard_down", at_quantum=2, shard=1),
        FaultPlan("shard_down", at_quantum=2, shard=0, absolute=True),
        FaultPlan("decode_scan", at_quantum=2),
    ])
    assert inj.shard_down_fires(1, run_start=0) == []
    assert inj.shard_down_fires(2, run_start=0) == [0, 1]
    assert inj.shard_down_fires(7, run_start=5) == [1]
    assert inj.fired.count(("shard_down", 2)) == 2
    # the raising path never matches shard_down plans
    inj.check("page_alloc", 2, 0)


def test_health_monitor_watchdog_contract():
    """Consecutive-fault counting, reset-on-success, the max_retries
    threshold, and the down/up event log."""
    hm = HealthMonitor(3, max_retries=2)
    assert hm.live == [0, 1, 2]
    assert hm.record_fault([0, 1]) == []
    assert hm.record_fault([0, 1]) == []
    hm.record_ok([1])                       # shard 1's chain breaks
    assert hm.record_fault([0, 1]) == [0]   # 0 crosses, 1 back to one
    hm.declare_down(0, quantum=7)
    assert hm.is_dead(0) and hm.live == [1, 2]
    assert hm.record_fault([0, 1]) == []    # dead shards stop counting
    hm.declare_up(0, quantum=9)
    assert hm.live == [0, 1, 2] and hm.fails[0] == 0
    assert hm.events == [(7, "down", 0), (9, "up", 0)]
    with pytest.raises(ValueError, match="out of range"):
        hm.declare_down(3, quantum=0)
    with pytest.raises(ValueError, match="n_shards"):
        HealthMonitor(0)
