"""Priority preemption: token-for-token parity with the unpreempted
oracle, pin/refcount invariants at every quantum, recompute metering, and
the sharded twin.

Greedy decoding depends only on the context, so an evicted-and-resumed
request MUST emit exactly the tokens it would have emitted uninterrupted
— the unpreempted engine is a token-for-token oracle. Divergence means
the fold-into-prompt lost or duplicated a token, the resumed prefill
skewed positions, or a pinned page served stale KV.

The pin invariant extends the sharing suite's allocator checks: device
``ref[p]`` == block-table mapping count PLUS the host pins holding ``p``
— pinned pages are referenced-but-unmapped by design, and every page is
still conserved (``top`` + #referenced == num_pages).
"""
import jax
import numpy as np
import pytest

from conftest import tiny_model
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving.preempt import pick_victim

PS = 4                                 # page size exercised in the suite
CH = 8                                 # prefill chunk size
# dense KV pages and MLA's latent pages through the same eviction paths
KINDS = pytest.mark.parametrize("parts", ["dense", "latent"], indirect=True)


@pytest.fixture(scope="module")
def parts(request):
    return tiny_model(getattr(request, "param", "dense"), "tiny-preempt")


class CheckedPreemptEngine(ServingEngine):
    """Asserts the pin-aware allocator invariants after every quantum."""

    def check_alloc(self):
        a = jax.device_get(self.caches["paged"])
        tbl = np.asarray(a["tbl"])
        free, top, ref = np.asarray(a["free"]), int(a["top"]), \
            np.asarray(a["ref"])
        P = ref.shape[0]
        counts = np.zeros((P,), int)
        for row in tbl:
            for p in row[row >= 0]:
                counts[p] += 1
        for _, pins in self._pins.values():
            for p in pins:
                counts[p] += 1
        assert (ref == counts).all(), \
            "device refcounts != mappings + pins"
        referenced = int((counts > 0).sum())
        assert top + referenced == P, "page conservation (pins resident)"
        stack = free[:top].tolist()
        assert len(set(stack)) == top, "free stack duplicate"
        assert not set(stack) & set(np.flatnonzero(counts).tolist()), \
            "referenced page on the free stack"

    def step(self, max_steps=10_000):
        ran = super().step(max_steps)
        self.check_alloc()
        return ran


def make_engine(m, params, checked=True, **kw):
    args = dict(max_batch=2, max_len=64, sync_every=4, paged=True,
                page_size=PS, prefill_chunk=CH, preemption=True,
                prefix_sharing=True)
    args.update(kw)
    cls = CheckedPreemptEngine if checked else ServingEngine
    return cls(m, params, EngineConfig(**args))


def oracle(m, params, reqs):
    """Every request served with ample capacity, never preempted."""
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=max(4, len(reqs)), max_len=64, sync_every=4, paged=True,
        page_size=PS, prefill_chunk=CH))
    for r in reqs:
        eng.submit(Request(**r))
    return {r.rid: r for r in eng.run()}


def preempted_run(m, params, low, high, warmup=6, **kw):
    """Submit ``low`` (default class), advance until they are armed and
    mid-decode, then submit ``high`` (priority 1) and drain."""
    eng = make_engine(m, params, **kw)
    for r in low:
        eng.submit(Request(**r))
    for _ in range(warmup):
        eng.step()
    assert eng.decoding > 0, "warmup must leave victims mid-decode"
    for r in high:
        eng.submit(Request(**{"priority": 1, **r}))
    got = {r.rid: r for r in eng.run()}
    return got, eng


RNG = np.random.default_rng(42)


def _reqs(rids, lens, max_new=16, **kw):
    return [dict(rid=rid, prompt=list(RNG.integers(0, 256, int(n))),
                 max_new_tokens=max_new, **kw)
            for rid, n in zip(rids, lens)]


# ------------------------------------------------------------------ parity


@KINDS
def test_preemption_parity_and_invariants(parts):
    """Two long low-priority decodes occupy both slots; a high-priority
    arrival evicts one. Every request's tokens match the unpreempted
    oracle token for token, the full budget is served, and the allocator
    invariants (checked every quantum, pins included) hold throughout."""
    _, m, params = parts
    low = _reqs((0, 1), (10, 13), max_new=24)
    high = _reqs((2,), (6,), max_new=6)
    got, eng = preempted_run(m, params, low, high)
    want = oracle(m, params, low + high)
    assert eng.preemption_count >= 1, "no eviction happened"
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished
    preempted = [r for r in got.values() if r.preemptions > 0]
    assert preempted, "some victim must have resumed"
    for r in preempted:
        assert len(r.tokens) == 24          # full budget despite eviction
        assert r.recompute_j > 0.0
    assert eng.free_pages == [eng.num_pages]  # drained pool, pins gone
    assert not eng._pins
    st = eng.stats()
    assert st["preemption_count"] == eng.preemption_count
    assert st["preempted_recompute_j"] == pytest.approx(
        sum(r.recompute_j for r in got.values()))


def test_preemption_without_sharing_recomputes_everything(parts):
    """With prefix sharing off there is nothing to pin: eviction releases
    every page, resume recomputes the whole folded prompt — slower, still
    token-for-token correct."""
    _, m, params = parts
    low = _reqs((0, 1), (9, 12), max_new=32)
    high = _reqs((2,), (5,), max_new=4)
    got, eng = preempted_run(m, params, low, high, prefix_sharing=False)
    want = oracle(m, params, low + high)
    assert eng.preemption_count >= 1
    assert not eng._pins                   # pins require the index
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"


def test_partially_shared_victim_parity(parts):
    """The victim ADOPTED a prefix another resident registered: eviction
    must keep the still-shared run alive for the sibling, pin only what
    the index can hand back, and resume through a prefix hit."""
    _, m, params = parts
    common = list(RNG.integers(0, 256, 8))  # two whole shared pages
    low = [dict(rid=0, prompt=common + [7, 8, 9], max_new_tokens=40),
           dict(rid=1, prompt=common + [1, 2, 3, 4], max_new_tokens=40)]
    high = _reqs((2,), (6,), max_new=6)
    got, eng = preempted_run(m, params, low, high, warmup=6)
    want = oracle(m, params, low + high)
    assert eng.preemption_count >= 1
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
    assert eng.free_pages == [eng.num_pages]


@KINDS
def test_resume_hits_pinned_prefix(parts):
    """The pin does its job: the resumed request's prefill skips the
    pinned pages (prefix hit) instead of recomputing the whole prompt."""
    _, m, params = parts
    low = _reqs((0, 1), (12, 12), max_new=24)
    high = _reqs((2,), (4,), max_new=4)
    got, eng = preempted_run(m, params, low, high)
    assert eng.preemption_count >= 1
    # the victim's prompt pages were registered at its first prefill, so
    # the resume adoption shows up as prefix hit tokens
    assert eng.prefix_hit_tokens > 0
    preempted = [r for r in got.values() if r.preemptions > 0]
    assert preempted


def test_preemption_charges_recompute_not_prefill(parts):
    """Resume prefills are metered under the ``recompute`` phase: the
    prefill phase's token count matches the unpreempted oracle's, so
    non-preempted J/token is invariant to the preemption policy."""
    _, m, params = parts
    low = _reqs((0, 1), (10, 13), max_new=24)
    high = _reqs((2,), (6,), max_new=6)
    _, eng = preempted_run(m, params, low, high)
    assert eng.preemption_count >= 1
    ref = ServingEngine(eng.model, eng.params, EngineConfig(
        max_batch=4, max_len=64, sync_every=4, paged=True, page_size=PS,
        prefill_chunk=CH))
    for r in low + [dict(priority=1, **h) for h in high]:
        ref.submit(Request(**r))
    ref.run()
    pf, ref_pf = eng.meter.phase("prefill"), ref.meter.phase("prefill")
    assert pf.tokens == pytest.approx(ref_pf.tokens)
    assert pf.energy_j == pytest.approx(ref_pf.energy_j, rel=1e-6)
    rc = eng.meter.phase("recompute")
    assert rc.energy_j == pytest.approx(eng.preempted_recompute_j)
    assert rc.energy_j > 0


def test_repeated_preemption_same_request(parts):
    """A request evicted more than once still serves its exact budget:
    the fold-into-prompt composes."""
    _, m, params = parts
    eng = make_engine(m, params)
    orig = list(RNG.integers(0, 256, 8))   # fold mutates req.prompt
    eng.submit(Request(rid=0, prompt=list(orig), max_new_tokens=40))
    for _ in range(5):
        eng.step()
    assert eng.decoding
    eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=4,
                       priority=1))
    eng.submit(Request(rid=2, prompt=[4, 5, 6], max_new_tokens=4,
                       priority=1))
    while not eng.responses[1].finished or not eng.responses[2].finished:
        eng.step()
    # rid 0 is back mid-flight; evict it again with another high-pri burst
    while not eng.decoding:
        eng.step()
    eng.submit(Request(rid=3, prompt=[7, 8, 9], max_new_tokens=4,
                       priority=1))
    eng.submit(Request(rid=4, prompt=[8, 9, 1], max_new_tokens=4,
                       priority=1))
    got = {r.rid: r for r in eng.run()}
    assert got[0].finished and len(got[0].tokens) == 40
    assert got[0].preemptions >= 1
    want = oracle(m, params, [dict(rid=0, prompt=orig, max_new_tokens=40)])
    assert got[0].tokens == want[0].tokens
    assert eng.free_pages == [eng.num_pages]


def test_no_victim_below_priority_waits(parts):
    """Nothing outranked: a same-priority arrival preempts nobody and
    waits FCFS, identical to preemption off."""
    _, m, params = parts
    low = _reqs((0, 1), (8, 8), max_new=16)
    eng = make_engine(m, params)
    for r in low:
        eng.submit(Request(**r))
    for _ in range(5):
        eng.step()
    eng.submit(Request(rid=2, prompt=[1, 2, 3], max_new_tokens=4))
    got = {r.rid: r for r in eng.run()}
    assert eng.preemption_count == 0
    want = oracle(m, params, low + [dict(rid=2, prompt=[1, 2, 3],
                                         max_new_tokens=4)])
    for rid in want:
        assert got[rid].tokens == want[rid].tokens


# ----------------------------------------------------------- victim policy


def test_pick_victim_policy():
    armed = [True, True, False, True]
    prio = [0, 0, 0, 1]
    progress = [5, 3, 0, 1]
    # lowest class first; ties -> least progress; disarmed never chosen
    assert pick_victim(armed, prio, progress, below_priority=1) == 1
    assert pick_victim(armed, prio, progress, below_priority=2) == 1
    # nothing strictly below class 0
    assert pick_victim(armed, prio, progress, below_priority=0) is None
    # slot-id tiebreak: equal class + progress -> highest slot
    assert pick_victim([True, True], [0, 0], [2, 2], 1) == 1


def test_preemption_requires_chunked(parts):
    _, m, params = parts
    with pytest.raises(ValueError, match="preemption requires chunked"):
        ServingEngine(m, params, EngineConfig(
            max_batch=2, max_len=64, paged=True, page_size=PS,
            preemption=True))
