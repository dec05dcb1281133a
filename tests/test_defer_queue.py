"""Property-based tests of the temporal deferral queue.

Deferral's contract: a request parked for a low-CI window owns NOTHING
(no slot, no page reservation, no admission-queue position), re-enters
the queue without overtaking same-class FCFS order, and can never be
made to miss its deadline by the deferral itself (forced release at
``defer_deadline_frac`` of the deadline budget reserves the rest for
service). Random interleavings of submission timing, priorities, prompt
lengths, deadlines, and preemption must preserve all of that plus page
conservation every quantum.

Hypothesis drives the interleavings where available (the
``tests/test_page_allocator.py`` style); this container ships without
it, so the same properties also run as a seeded random sweep — the
checks are identical, only the schedule generator differs, and the
suite never passes vacuously.

The deferral machinery lives in the base ``ServingEngine`` (the sharded
fleet borrows it), so these properties run single-device under tier-1;
the fleet-level deferral path is exercised by ``make hetero`` and the
``hetero`` bench section.
"""
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                    # container has no hypothesis
    HAVE_HYPOTHESIS = False

import jax

from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern
from repro.serving import EngineConfig, Request, ServingEngine

PS = 8
CH = 8


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-defer", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _engine(m, params, **kw):
    args = dict(max_batch=3, max_len=64, sync_every=4, paged=True,
                page_size=PS, prefill_chunk=CH, defer_below_priority=1)
    args.update(kw)
    return ServingEngine(m, params, EngineConfig(**args))


def check_quantum_invariants(eng):
    """Truths that hold after EVERY scheduling quantum."""
    slot_rids = {r for row in eng.slot_rid for r in row if r >= 0}
    for req in eng.deferred:
        rid = req.rid
        assert rid in eng.deferred_rids
        assert rid in eng._defer_release_h
        # a parked request owns nothing
        assert rid not in eng._resv, f"deferred {rid} holds a reservation"
        assert rid not in slot_rids, f"deferred {rid} holds a slot"
        assert not eng.responses[rid].finished
        assert all(req is not q for q in eng.queue), \
            f"deferred {rid} also queued"
    assert len(eng.deferred_rids) == len(eng.deferred)
    # page conservation: free + slot-held + pending reservations == pool
    # (sharing off, so no shared mappings or pins complicate the count)
    assert (sum(eng.free_pages) + sum(map(sum, eng._slot_pages))
            + sum(eng._resv.values()) == eng.num_pages)


def _random_specs(rng):
    """Schedule generator for the no-hypothesis sweep: same space as the
    hypothesis strategy below."""
    return [(int(rng.integers(0, 7)),            # submit at quantum
             int(rng.integers(0, 3)),            # priority
             int(rng.integers(3, 21)),           # prompt len
             int(rng.integers(1, 9)),            # max_new_tokens
             (None, 30.0)[int(rng.integers(0, 2))])   # deadline_s
            for _ in range(int(rng.integers(1, 11)))]


def _drive(eng, specs, rng, check=check_quantum_invariants):
    """Submit per the schedule while stepping; invariants every quantum.
    Returns (deferred-class rids in submission order, admission order of
    those rids)."""
    admit_order = []
    orig_stamp = type(eng)._stamp_admit

    def spy(req):
        if req.priority < 1:
            admit_order.append(req.rid)
        return orig_stamp(eng, req)

    eng._stamp_admit = spy
    pending = sorted(enumerate(specs), key=lambda t: (t[1][0], t[0]))
    deferred_class = []
    q = 0
    while pending or eng.queue or eng.active or eng.deferred:
        while pending and pending[0][1][0] <= q:
            rid, (_, prio, L, mnt, dl) = pending.pop(0)
            eng.submit(Request(
                rid=rid, prompt=list(rng.integers(0, 256, L)),
                max_new_tokens=mnt, priority=prio, deadline_s=dl))
            if prio < 1:
                deferred_class.append(rid)
        progressed = eng.step()
        check(eng)
        if not progressed and not eng.decoding and not pending:
            if eng.queue:
                eng._resolve_stall()
            elif eng.deferred:
                eng._fast_forward_deferred()
        q += 1
        assert q < 2000, "deferral wedged the engine"
    return deferred_class, admit_order


def _check_release_interleaving(parts, specs, seed):
    _, m, params = parts
    eng = _engine(m, params)
    deferred_class, admit_order = _drive(eng, specs,
                                         np.random.default_rng(seed))
    assert not eng.deferred and not eng.deferred_rids
    assert not eng._defer_release_h
    assert eng.deferred_total == len(deferred_class)
    assert eng.deferred_released == eng.deferred_total
    for rid in deferred_class:
        resp = eng.responses[rid]
        assert resp.finished, f"deferred {rid} never finished"
        assert resp.finish_reason != "deadline", \
            f"deferral made {rid} miss its deadline"
    # FCFS within the deferred class: release is prefix-closed, so the
    # admission order of class-0 requests equals their submission order
    assert admit_order == deferred_class, \
        f"release reordered: submitted {deferred_class}, " \
        f"admitted {admit_order}"
    assert eng.free_pages == [eng.num_pages]


def _check_preemption_interleaving(parts, specs, seed):
    _, m, params = parts
    eng = _engine(m, params, preemption=True, max_batch=2)
    deferred_class, _ = _drive(eng, specs, np.random.default_rng(seed))
    assert not eng.deferred
    for rid in deferred_class:
        resp = eng.responses[rid]
        assert resp.finished
        assert resp.finish_reason != "deadline"
    assert eng.free_pages == [eng.num_pages]


if HAVE_HYPOTHESIS:
    # strategy: per-request (submit_quantum, priority, prompt_len,
    # max_new, deadline) — priority 0 is the deferred class, 1/2 express
    _spec = st.tuples(st.integers(0, 6), st.integers(0, 2),
                      st.integers(3, 20), st.integers(1, 8),
                      st.sampled_from([None, 30.0]))

    @given(specs=st.lists(_spec, min_size=1, max_size=10),
           seed=st.integers(0, 9))
    @settings(max_examples=25, deadline=None)
    def test_defer_release_interleavings(parts, specs, seed):
        """Arbitrary schedules: parked requests own nothing, page
        conservation holds every quantum, every deferred request releases
        and finishes, none by deadline, release never reorders FCFS."""
        _check_release_interleaving(parts, specs, seed)

    @given(specs=st.lists(_spec, min_size=2, max_size=8),
           seed=st.integers(0, 9))
    @settings(max_examples=15, deadline=None)
    def test_defer_with_preemption_interleavings(parts, specs, seed):
        """Same properties with priority preemption evicting running
        deferred-class work mid-decode."""
        _check_preemption_interleaving(parts, specs, seed)
else:
    @pytest.mark.parametrize("seed", range(12))
    def test_defer_release_interleavings(parts, seed):
        rng = np.random.default_rng(1000 + seed)
        _check_release_interleaving(parts, _random_specs(rng), seed)

    @pytest.mark.parametrize("seed", range(8))
    def test_defer_with_preemption_interleavings(parts, seed):
        rng = np.random.default_rng(2000 + seed)
        _check_preemption_interleaving(parts, _random_specs(rng), seed)


# -------------------------------------------------------- deterministic pins


def test_release_preserves_fcfs_order(parts):
    """Deterministic FCFS pin: five same-class deferred requests released
    together must admit in submission order (prefix-closed release +
    priority-queue FCFS insert)."""
    _, m, params = parts
    eng = _engine(m, params)
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 256, 6)),
                           max_new_tokens=3, priority=0))
    assert len(eng.deferred) == 5
    order = []
    orig = type(eng)._stamp_admit

    def spy(req):
        order.append(req.rid)
        return orig(eng, req)

    eng._stamp_admit = spy
    # nothing runnable: run() fast-forwards to the window and releases
    eng.run()
    assert order == sorted(order), f"release reordered same class: {order}"
    assert eng.deferred_released == 5
    assert all(eng.responses[i].finished for i in range(5))
    # released at the region's greenest window, not before
    assert eng.meter.clock_hours >= eng.meter.region.min_hour - 1.0


def test_deferred_exempt_from_bounded_queue(parts):
    """Deferred requests bypass max_queue (they own no queue position):
    a burst of deferred-class work must not shed, and must not cause
    express work to shed."""
    _, m, params = parts
    eng = _engine(m, params, max_queue=2)
    rng = np.random.default_rng(1)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 256, 5)),
                           max_new_tokens=2, priority=0))
    eng.submit(Request(rid=100, prompt=list(rng.integers(0, 256, 5)),
                       max_new_tokens=2, priority=1))
    assert eng.shed_count == 0
    assert len(eng.deferred) == 6
    eng.run()
    assert eng.shed_count == 0
    assert all(r.finished for r in eng.responses.values())


def test_forced_release_beats_deadline(parts):
    """A deferred request whose wall-clock deadline budget half-expires is
    force-released even though its CI window is hours of virtual time
    away — and it finishes within the deadline. An express stream keeps
    the engine busy so the idle fast-forward path can't mask the forced
    path."""
    _, m, params = parts
    eng = _engine(m, params, defer_deadline_frac=0.5)
    rng = np.random.default_rng(2)
    eng.submit(Request(rid=0, prompt=list(rng.integers(0, 256, 5)),
                       max_new_tokens=2, priority=0, deadline_s=0.5))
    assert len(eng.deferred) == 1
    # burn > frac * deadline of wall clock while the window stays shut
    time.sleep(0.3)
    eng.submit(Request(rid=1, prompt=list(rng.integers(0, 256, 8)),
                       max_new_tokens=30, priority=1))
    for _ in range(200):
        eng.step()
        if not (eng.queue or eng.active or eng.deferred):
            break
    assert eng.deferred_forced == 1, "deadline pressure never forced"
    resp = eng.responses[0]
    assert resp.finished and resp.finish_reason != "deadline"


def test_defer_disabled_is_inert(parts):
    """defer_below_priority=None: nothing defers, counters stay zero, and
    stats report the deferral keys as zeros."""
    _, m, params = parts
    eng = _engine(m, params, defer_below_priority=None)
    rng = np.random.default_rng(3)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 256, 6)),
                           max_new_tokens=3, priority=0))
    eng.run()
    st_ = eng.stats()
    assert st_["deferred_requests"] == 0
    assert st_["deferred_released"] == 0
    assert st_["deferred_forced_releases"] == 0
    assert all(r.finished for r in eng.responses.values())
