"""Carbon-aware routing over heterogeneous fleets: parity oracles,
per-shard attribution, steering direction, and offline/live agreement.

The routing claim is that placement policy is PURE REGROUPING: carbon
routing changes WHICH eligible shard a request lands on, never any
request's chunk boundaries or greedy token stream (decode depends only on
context) — so a heterogeneous carbon-routed fleet must reproduce the
homogeneous free-pages fleet token for token, and on a homogeneous fleet
the carbon score ties everywhere and must degrade to the baseline's exact
placement. The attribution claim is that per-shard meters (each at its
shard's profile x region CI) sum EXACTLY to the fleet totals, and that
J/token per phase is invariant to the routing policy (energy is a
property of the work, not of where it ran — per shard profile).

Needs 4 forced host devices: `make hetero` or the CI `hetero` step sets
XLA_FLAGS=--xla_force_host_platform_device_count=4; under plain tier-1
every test here SKIPS via the conftest guard (never passes vacuously).
"""
import jax
import numpy as np
import pytest

from repro.core.energy import LLAMA_7B
from repro.core.hardware import get_profile
from repro.core.intensity import get_region
from repro.core.scheduler import (CIDirectedScheduler, FleetSlice,
                                  marginal_request_g)
from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern
from repro.serving import (EngineConfig, Request, ServingEngine,
                           ShardedServingEngine)

PS = 8                                 # page size exercised in the suite
CH = 8                                 # prefill chunk size
S = 4                                  # fleet shards

HET_PROFILES = ("rtx6000ada", "t4", "rtx6000ada", "t4")
HET_REGIONS = ("CISO", "QC", "PACE", "QC")


@pytest.fixture(autouse=True)
def _fleet_devices(host_devices):
    host_devices(S)


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-hetero", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def run_fleet(m, params, reqs, **kw):
    args = dict(max_batch=2, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=CH, shards=S)
    args.update(kw)
    eng = ShardedServingEngine(m, params, EngineConfig(**args))
    for r in reqs:
        eng.submit(Request(**r))
    return {r.rid: r for r in eng.run()}, eng


def _reqs(rng, lens, max_new=9):
    return [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=max_new)
            for i, n in enumerate(lens)]


LENS = (3, 5, 8, 11, 16, 21, 4, 30, 6, 13, 9, 18)


# ------------------------------------------------------------------ parity


def test_hetero_carbon_matches_homogeneous_free_pages(parts):
    """The tentpole oracle: a heterogeneous fleet under carbon routing
    reproduces the homogeneous free-pages fleet's exact token streams —
    different placement, identical tokens, because greedy decode depends
    only on context and every shard runs the same SPMD program."""
    _, m, params = parts
    want, _ = run_fleet(m, params, _reqs(np.random.default_rng(7), LENS))
    got, eng = run_fleet(m, params, _reqs(np.random.default_rng(7), LENS),
                         shard_profiles=HET_PROFILES,
                         shard_regions=HET_REGIONS, routing="carbon")
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished == want[rid].finished
    assert eng.stats()["carbon_routing"] == 1.0


def test_homogeneous_carbon_degrades_to_free_pages_exactly(parts):
    """On a homogeneous fleet every shard scores identically, so carbon
    routing's tie-break must reproduce free-pages placement BIT-FOR-BIT:
    same shard per request, same tokens, same per-shard meter totals."""
    _, m, params = parts
    want, ea = run_fleet(m, params, _reqs(np.random.default_rng(7), LENS))
    got, eb = run_fleet(m, params, _reqs(np.random.default_rng(7), LENS),
                        routing="carbon")
    assert ea._req_shard == eb._req_shard, "placement drifted on a tie"
    for rid in want:
        assert got[rid].tokens == want[rid].tokens
    sa, sb = ea.stats(), eb.stats()
    for s in range(S):
        for k in ("requests", "tokens", "energy_j", "carbon_g"):
            assert sa[f"shard{s}_{k}"] == sb[f"shard{s}_{k}"]


# ------------------------------------------------------------- attribution


def test_per_shard_meters_sum_to_fleet_total(parts):
    """FleetMeterView totals ARE the sum of the per-shard meters — no
    second ledger. Checked on the heterogeneous fleet where the rows
    genuinely differ (different profiles, different region CI)."""
    _, m, params = parts
    _, eng = run_fleet(m, params, _reqs(np.random.default_rng(3), LENS),
                       shard_profiles=HET_PROFILES,
                       shard_regions=HET_REGIONS, routing="carbon")
    st = eng.stats()
    for key, attr in (("tokens", "tokens"), ("energy_j", "energy_j"),
                      ("carbon_g", "total_g")):
        total = sum(st[f"shard{s}_{key}"] for s in range(S))
        want = getattr(eng.meter.totals, attr)
        assert total == pytest.approx(want, rel=1e-12, abs=1e-15)
    # phase-level: fleet view phases = sum of shard phases
    for phase in ("prefill", "decode"):
        want = sum(mm.phase(phase).energy_j for mm in eng.meters)
        assert eng.meter.phase(phase).energy_j == pytest.approx(
            want, rel=1e-12, abs=1e-15)
    # requests all landed somewhere, each counted once
    assert sum(eng.shard_requests) == len(LENS)


def test_j_per_token_invariant_to_routing_policy(parts):
    """Energy is a property of the work at a profile, not of the routing
    policy. With a uniform trace (equal prompt lengths and budgets) each
    request's prefill attribution is the same batch-1 launch, so a
    shard's prefill J/token is a pure function of its PROFILE — it must
    be exactly equal under free_pages and carbon routing even though the
    policies route different requests to it; decode J/token varies only
    with batch composition (weights-streaming amortization), so it stays
    within a coarse envelope."""
    _, m, params = parts
    het = dict(shard_profiles=HET_PROFILES, shard_regions=HET_REGIONS)
    uniform = (12,) * 10
    _, ea = run_fleet(m, params,
                      _reqs(np.random.default_rng(5), uniform, max_new=7),
                      routing="free_pages", **het)
    _, eb = run_fleet(m, params,
                      _reqs(np.random.default_rng(5), uniform, max_new=7),
                      routing="carbon", **het)
    checked = 0
    for s in range(S):
        pa, pb = ea.meters[s].phase("prefill"), eb.meters[s].phase("prefill")
        if pa.tokens == 0 or pb.tokens == 0:
            continue                   # a policy may starve a shard
        assert pb.j_per_token == pytest.approx(pa.j_per_token, rel=1e-12)
        checked += 1
        da, db = ea.meters[s].phase("decode"), eb.meters[s].phase("decode")
        if da.tokens and db.tokens:
            assert db.j_per_token == pytest.approx(da.j_per_token, rel=0.5)
    assert checked > 0, "no shard served under both policies"
    # profile heterogeneity is real: T4 and Ada shards price identical
    # work differently (which one wins is workload-dependent — Takeaway 3
    # — at this toy scale the T4's 70 W TDP wins)
    sa = ea.stats()
    by_prof = {}
    for s in range(S):
        if sa[f"shard{s}_tokens"]:
            by_prof.setdefault(HET_PROFILES[s], []).append(
                sa[f"shard{s}_energy_j"] / sa[f"shard{s}_tokens"])
    if "t4" in by_prof and "rtx6000ada" in by_prof:
        assert not np.isclose(min(by_prof["t4"]),
                              min(by_prof["rtx6000ada"]), rtol=0.05)


# ---------------------------------------------------------------- steering


def test_carbon_routing_prefers_low_ci_shards(parts):
    """Sequential singleton requests on an idle heterogeneous fleet must
    ALL land on a lowest-CI (QC) shard under carbon routing — with free
    slots everywhere the marginal score is dominated by region CI for
    same-scale work — while free-pages routing spreads by pool state."""
    _, m, params = parts
    rng = np.random.default_rng(9)
    args = dict(max_batch=2, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=CH, shards=S,
                shard_profiles=HET_PROFILES, shard_regions=HET_REGIONS,
                routing="carbon")
    eng = ShardedServingEngine(m, params, EngineConfig(**args))
    qc = {s for s in range(S) if HET_REGIONS[s] == "QC"}
    for i in range(6):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 256, 10)),
                           max_new_tokens=5))
        eng.run()
        assert eng._req_shard[i] in qc, (
            f"request {i} placed on shard {eng._req_shard[i]} "
            f"({HET_REGIONS[eng._req_shard[i]]}) with QC shards free")


def test_slo_pinned_requests_route_load_first(parts):
    """Latency-pinned work (``slo_s`` set) must NOT pile onto the green
    shards under carbon routing: among SLO-feasible shards it keeps the
    baseline's load-first ordering (greener shard only breaks free-page
    ties), so four concurrent pinned requests occupy four DISTINCT
    shards — while the same four without an SLO concentrate on the two
    QC shards. Chasing green concentrates, concentration queues
    prefills, and the pinned class is the one that cannot pay that."""
    _, m, params = parts
    args = dict(max_batch=2, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=CH, shards=S,
                shard_profiles=HET_PROFILES, shard_regions=HET_REGIONS,
                routing="carbon")

    def admit_four(slo_s):
        rng = np.random.default_rng(21)
        eng = ShardedServingEngine(m, params, EngineConfig(**args))
        for i in range(S):
            eng.submit(Request(rid=i, prompt=list(rng.integers(0, 256, 10)),
                               max_new_tokens=5, slo_s=slo_s))
        eng.run()
        return [eng._req_shard[i] for i in range(S)]

    qc = {s for s in range(S) if HET_REGIONS[s] == "QC"}
    unpinned = admit_four(None)
    assert set(unpinned) == qc, (
        f"unpinned requests should concentrate on QC shards, got {unpinned}")
    pinned = admit_four(10.0)       # generous SLO: every shard feasible
    assert sorted(pinned) == list(range(S)), (
        f"SLO-pinned requests should spread load-first over all shards, "
        f"got {pinned}")
    # greener-tie-break: the FIRST pinned request (all pools equal) still
    # prefers a QC shard — carbon informs, but never queues, pinned work
    assert pinned[0] in qc


def test_phase_steering_disaggregates_by_hardware():
    """GreenLLM's disaggregation out of one scoring rule, at a realistic
    workload: prefill-heavy requests score cheaper on the compute-rich
    RTX6000 Ada, decode-heavy on the memory-amortized T4 (same region, so
    the split is pure hardware)."""
    w = LLAMA_7B
    t4 = FleetSlice(get_profile("t4"), get_region("CISO"))
    ada = FleetSlice(get_profile("rtx6000ada"), get_region("CISO"))
    g_pf_t4, _ = marginal_request_g(t4, w, 2000, 4, 0.25)
    g_pf_ada, _ = marginal_request_g(ada, w, 2000, 4, 0.25)
    assert g_pf_ada < g_pf_t4, "prefill-heavy should steer to the Ada"
    g_dc_t4, _ = marginal_request_g(t4, w, 45, 500, 0.25)
    g_dc_ada, _ = marginal_request_g(ada, w, 45, 500, 0.25)
    assert g_dc_t4 < g_dc_ada, "decode-heavy should steer to the T4"


def test_oom_slice_scores_infeasible():
    """A slice whose profile cannot hold the workload scores (inf, inf) —
    the router can never place onto an impossible shard — while a fitting
    workload scores finite."""
    from repro.core.energy import LLMWorkload
    sl = FleetSlice(get_profile("t4"), get_region("QC"))
    g, t = marginal_request_g(sl, LLAMA_7B, 100, 10, 0.5)
    assert np.isfinite(g) and np.isfinite(t)
    huge = LLMWorkload.llama_like("huge", n_layers=80, d_model=8192,
                                  n_heads=64, n_kv_heads=8, d_ff=28672,
                                  vocab=32000)
    g, t = marginal_request_g(sl, huge, 100, 10, 0.5)
    assert g == float("inf") and t == float("inf")


# ------------------------------------------- offline/live scoring agreement


@pytest.mark.parametrize("profiles,regions", [
    (("t4", "rtx6000ada", "t4", "rtx6000ada"), ("QC", "PACE", "QC", "PACE")),
    (("t4", "rtx6000ada", "t4", "rtx6000ada"),
     ("CISO", "CISO", "CISO", "CISO")),
])
def test_simulate_day_matches_live_place(parts, profiles, regions):
    """The offline CIDirectedScheduler and the live carbon _place share
    one scoring core (FleetSlice + the phase reports): route the same
    synthetic day through both and the per-hour shard choice must agree
    at every hour — across the region dimension (QC vs PACE) and the
    hardware dimension (T4 vs Ada at equal CI)."""
    _, m, params = parts
    args = dict(max_batch=2, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=CH, shards=S, routing="carbon",
                use_diurnal_ci=True, shard_profiles=profiles,
                shard_regions=regions)
    eng = ShardedServingEngine(m, params, EngineConfig(**args))
    # one offline slice per UNIQUE (profile, region) — the scheduler
    # ranks slice types, the live engine ranks shard instances
    uniq = {}
    for sl in eng._slices:
        uniq.setdefault(sl.key, sl)
    sched = CIDirectedScheduler(list(uniq.values()), eng.workload,
                                phase="prompt", batch=1)
    day = sched.simulate_day(requests_per_hour=60.0, hours=24)
    for h in range(24):
        eng.clock.hours = float(h)
        req = Request(rid=1000 + h, prompt=list(range(45)),
                      max_new_tokens=8)
        placed = eng._place(req)
        assert placed is not None
        live_key = eng._slices[placed[0]].key
        assert live_key == day["choices"][h], (
            f"hour {h}: offline chose {day['choices'][h]}, "
            f"live placed on {live_key}")


# ------------------------------------------------------------- single-eng


def test_single_engine_rejects_bad_knobs(parts):
    """Config validation: routing/deferral knobs and per-shard list
    lengths are checked once, in the engine's constructor, at every
    shard count."""
    _, m, params = parts
    base = dict(max_batch=2, max_len=64, paged=True, page_size=PS,
                prefill_chunk=CH)
    with pytest.raises(ValueError, match="routing"):
        ServingEngine(m, params, EngineConfig(routing="greedy", **base))
    with pytest.raises(ValueError, match="defer_horizon_h"):
        ServingEngine(m, params,
                      EngineConfig(defer_horizon_h=0, **base))
    with pytest.raises(ValueError, match="defer_deadline_frac"):
        ServingEngine(m, params,
                      EngineConfig(defer_deadline_frac=1.5, **base))
    with pytest.raises(ValueError, match="shard_profiles"):
        ShardedServingEngine(m, params, EngineConfig(
            shards=S, shard_profiles=("t4",), **base))
    with pytest.raises(ValueError, match="shard_regions"):
        ShardedServingEngine(m, params, EngineConfig(
            shards=S, shard_regions=("QC", "QC"), **base))
