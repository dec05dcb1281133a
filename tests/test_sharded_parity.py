"""Mesh-sharded serving fleet vs the single-device paged engine:
token-for-token parity plus the fleet-structure invariants.

The sharded engine runs the SAME fused step / chunked-prefill programs per
shard (shard_map bodies are the unmodified single-device functions), so
greedy decoding must be EXACTLY equal to the single-device paged engine —
any drift means a lane leaked into a neighbor, a sentinel row wrote
something real, or placement corrupted a reservation. Cases cover
mid-stream admission (more requests than fleet slots), uneven per-shard
occupancy, per-shard pool cleanliness after a drained run, preservation of
the mesh sharding through every fleet program, fleet-level host-sync
accounting, and the shard-local prefix index.

Needs 4 forced host devices: `make sharded` or the CI `sharded` step sets
XLA_FLAGS=--xla_force_host_platform_device_count=4; under plain tier-1
every test here SKIPS via the conftest guard (never passes vacuously).
"""
import jax
import numpy as np
import pytest

from conftest import tiny_model
from repro.serving import (EngineConfig, Request, ServingEngine,
                           ShardedServingEngine)

PS = 8                                 # page size exercised in the suite
CH = 8                                 # prefill chunk size
S = 4                                  # fleet shards


@pytest.fixture(autouse=True)
def _fleet_devices(host_devices):
    host_devices(S)


# dense KV pages and MLA's latent pages through the same fleet programs
KINDS = pytest.mark.parametrize("parts", ["dense", "latent"], indirect=True)


@pytest.fixture(scope="module")
def parts(request):
    return tiny_model(getattr(request, "param", "dense"), "tiny-sharded")


def run_single(m, params, reqs, **kw):
    args = dict(max_batch=4, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=CH)
    args.update(kw)
    eng = ServingEngine(m, params, EngineConfig(**args))
    for r in reqs:
        eng.submit(Request(**r))
    return {r.rid: r for r in eng.run()}, eng


def run_fleet(m, params, reqs, **kw):
    args = dict(max_batch=2, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=CH, shards=S)
    args.update(kw)
    eng = ShardedServingEngine(m, params, EngineConfig(**args))
    for r in reqs:
        eng.submit(Request(**r))
    return {r.rid: r for r in eng.run()}, eng


def assert_parity(m, params, reqs, single_kw=None, **kw):
    want, _ = run_single(m, params, reqs, **(single_kw or {}))
    got, eng = run_fleet(m, params, reqs, **kw)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished == want[rid].finished
        assert got[rid].rejected == want[rid].rejected
    return eng


def assert_fleet_pool_clean(eng):
    """Every shard's allocator back to pristine: full stack, empty tables,
    zero refcounts, host reservation mirrors exact."""
    alloc = jax.device_get(eng.caches["paged"])
    P = alloc["free"].shape[1]
    for s in range(eng.S):
        assert int(np.asarray(alloc["top"])[s]) == P
        assert (np.asarray(alloc["tbl"])[s] == -1).all()
        assert (np.asarray(alloc["ref"])[s] == 0).all()
        assert sorted(np.asarray(alloc["free"])[s].tolist()) == list(range(P))
    assert eng.free_pages == [eng.num_pages] * eng.S


def _reqs(rng, lens, max_new=9):
    return [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=max_new)
            for i, n in enumerate(lens)]


# ------------------------------------------------------------------ parity


@KINDS
def test_mid_stream_admission_token_for_token(parts):
    """More requests than fleet slots (12 > 4 shards x 2): later requests
    admit mid-stream onto whichever shard frees pages first, interleaving
    chunked prefills with the fleet decode scan — every token must equal
    the single-device paged oracle."""
    _, m, params = parts
    rng = np.random.default_rng(7)
    eng = assert_parity(m, params,
                        _reqs(rng, (3, 5, 8, 11, 16, 21, 4, 30, 6, 13,
                                    9, 18)))
    st = eng.stats()
    assert st["peak_active"] > S            # really ran slots in parallel
    assert st["requests"] == 12
    assert_fleet_pool_clean(eng)


def test_uneven_shard_occupancy(parts):
    """5 equal requests over 4 shards of 2 slots: placement by free pages
    doubles one shard up while the rest hold one — the fleet program runs
    lanes at different occupancy (and, as slots drain, different active
    counts) with exact parity throughout."""
    _, m, params = parts
    rng = np.random.default_rng(11)
    eng = assert_parity(m, params, _reqs(rng, (10, 10, 10, 10, 10),
                                         max_new=12))
    peaks = eng.peak_pages_reserved
    assert max(peaks) > min(peaks), "placement never doubled a shard up"
    assert_fleet_pool_clean(eng)


def test_budget_death_and_eos_mid_chunk(parts):
    """Slots dying mid-fused-chunk (budget exhaustion and EOS) coast on
    their own shard's trash page and release shard-locally."""
    _, m, params = parts
    probe, _ = run_single(m, params,
                          [dict(rid=0, prompt=[9, 8, 7, 6, 5],
                                max_new_tokens=12)])
    eos = probe[0].tokens[4]
    reqs = [dict(rid=0, prompt=[9, 8, 7, 6, 5], max_new_tokens=12,
                 eos_id=eos),
            dict(rid=1, prompt=[1, 2, 3], max_new_tokens=5),
            dict(rid=2, prompt=[4, 4, 4, 4], max_new_tokens=20),
            dict(rid=3, prompt=list(range(1, CH + 4)), max_new_tokens=1)]
    eng = assert_parity(m, params, reqs)
    assert_fleet_pool_clean(eng)


def test_never_fits_rejected_fitting_complete(parts):
    """Per-shard pools mean per-shard capacity: a prompt + budget that
    exceeds ONE shard's whole pool can never be represented (pages don't
    span shards) and is rejected up front, exactly like the single-device
    engine rejects against its one pool."""
    _, m, params = parts
    # 62 prompt + 4 decode = 66 > max_len=64 -> 9 of 8 table slots: reject
    reqs = [dict(rid=0, prompt=list(range(1, 63)), max_new_tokens=5),
            dict(rid=1, prompt=[1, 2, 3], max_new_tokens=5)]
    eng = assert_parity(m, params, reqs)
    assert_fleet_pool_clean(eng)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(rid=9, prompt=list(range(1, 70)),
                           max_new_tokens=5))


# --------------------------------------------------------- fleet structure


def test_mesh_sharding_preserved_through_programs(parts):
    """Every fleet program must keep the device state sharded over the
    mesh's data axis — a silent all-gather to one device would still be
    numerically correct, so parity alone can't catch it."""
    _, m, params = parts
    rng = np.random.default_rng(3)
    _, eng = run_fleet(m, params, _reqs(rng, (6, 9, 12, 5, 17)))

    def leading_axis(x):
        spec = x.sharding.spec
        return spec[0] if len(spec) else None

    for leaf in jax.tree_util.tree_leaves(eng.caches):
        assert leading_axis(leaf) == "data", \
            f"cache leaf lost its shard axis: {leaf.shape}, {leaf.sharding}"
    for leaf in jax.tree_util.tree_leaves((eng.state, eng.cur_tokens)):
        assert leading_axis(leaf) == "data"


def test_fleet_syncs_do_not_scale_with_shards(parts):
    """The scaling claim: the fleet takes ONE decode sync per chunk and
    one first-token fetch per finishing launch for ALL shards, so syncs
    per 100 decode tokens must not exceed the single-device engine serving
    a quarter of the load."""
    _, m, params = parts
    rng = np.random.default_rng(5)
    lens = list(rng.integers(4, 20, 16))
    fleet_reqs = _reqs(rng, lens, max_new=17)
    single_reqs = [dict(r) for r in fleet_reqs[:4]]

    def syncs_per_100(resps, eng):
        toks = sum(max(len(r.tokens) - 1, 0) for r in resps.values()
                   if not r.rejected)
        return 100.0 * eng.host_syncs / max(toks, 1)

    sresp, seng = run_single(m, params, single_reqs)
    fresp, feng = run_fleet(m, params, fleet_reqs)
    assert syncs_per_100(fresp, feng) <= syncs_per_100(sresp, seng) + 1e-9
    # and the fleet really served 4x the tokens
    ftoks = sum(len(r.tokens) for r in fresp.values())
    stoks = sum(len(r.tokens) for r in sresp.values())
    assert ftoks == 4 * stoks


def test_requires_paged_and_chunked(parts):
    _, m, params = parts
    with pytest.raises(ValueError, match="chunked"):
        ShardedServingEngine(m, params, EngineConfig(
            max_batch=2, max_len=64, paged=True, page_size=PS, shards=S))
    with pytest.raises(ValueError, match="chunked"):
        ShardedServingEngine(m, params, EngineConfig(
            max_batch=2, max_len=64, shards=S))


# ----------------------------------------------------- shard-local sharing


@KINDS
def test_prefix_sharing_is_shard_local(parts):
    """Followers of a resident prefix are steered to the shard HOLDING it
    and adopt its pages by refcount; parity vs the unshared single-device
    oracle is exact, the weak index empties when the last holder drains,
    and hits never cross shards (each shard's index only ever maps its own
    pool's page ids — asserted via the per-shard ref mirrors)."""
    _, m, params = parts
    rng = np.random.default_rng(13)
    common = list(rng.integers(0, 256, 2 * PS))     # two whole pages
    reqs = [dict(rid=i, prompt=common + list(rng.integers(0, 256, 3)),
                 max_new_tokens=(24 if i == 0 else 6))
            for i in range(6)]
    # 2 shards x 2 slots: the first four requests fill the fleet before
    # anything registers (no hits possible), then the short followers
    # finish while the donor (rid 0) keeps decoding with its prefix
    # registered — rids 4 and 5 admit mid-stream, match the resident run,
    # and must be STEERED onto the donor's shard to adopt it
    want, _ = run_single(m, params, [dict(r) for r in reqs])
    got, eng = run_fleet(m, params, [dict(r) for r in reqs],
                         max_batch=2, shards=2, sync_every=4,
                         prefix_sharing=True)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
    st = eng.stats()
    assert st["prefix_hit_tokens"] >= 2 * PS, "no follower ever adopted"
    assert st["prefix_shared_requests"] >= 1
    # weak-index drain: every shard's index dropped with its last holder
    for s in range(eng.S):
        assert eng._prefix_index[s] == {}
        assert eng._page_ref[s] == {}
    assert_fleet_pool_clean(eng)


def test_prefix_steering_prefers_resident_shard(parts):
    """When SEVERAL shards could take a request, placement prefers the one
    holding its prefix even though it has FEWER free pages — sharing is a
    placement input, not just an admission discount. Two-phase run: the
    donor decodes alone (prefix registered, its shard's pool partly
    reserved), then a follower arrives with every shard's slots free."""
    _, m, params = parts
    rng = np.random.default_rng(17)
    common = list(rng.integers(0, 256, 2 * PS))
    donor = dict(rid=0, prompt=common + [7, 7, 7], max_new_tokens=40)
    follower = dict(rid=1, prompt=common + [3, 3, 3], max_new_tokens=6)

    want, _ = run_single(m, params, [dict(donor), dict(follower)])
    eng = ShardedServingEngine(m, params, EngineConfig(
        max_batch=2, max_len=64, sync_every=4, paged=True, page_size=PS,
        prefill_chunk=CH, shards=2, prefix_sharing=True))
    eng.submit(Request(**dict(donor)))
    eng.run(max_steps=4)               # prefill + one chunk: donor active
    assert eng.active == 1
    eng.submit(Request(**dict(follower)))
    got = {r.rid: r for r in eng.run()}
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
    # the donor's shard had strictly fewer free pages, yet won placement
    assert eng._req_shard[1] == eng._req_shard[0]
    assert eng.stats()["prefix_hit_tokens"] >= 2 * PS
    assert_fleet_pool_clean(eng)
