"""Chunked-prefill engine vs the monolithic-prefill oracle: token-for-token
parity, quantum-scheduler interleaving, energy-meter invariance to the
chunk-size knob, admission-metering accounting, and jit-entry reuse across
engines.

The chunked engine reuses the paged decode path verbatim and feeds the
same attention math chunk by chunk, so greedy decoding must be EXACTLY
equal to the monolithic paged engine — any drift means a chunk wrote the
wrong page, a stale row unmasked, a cursor moved during an interleaved
decode scan, or positions skewed at a partial chunk.

The stacked-pool cases at the end run the GQA and the latent (MLA) tiny
models against the contiguous cache: a prefix-shared page after
copy-on-write, and a slot frozen mid-chunked-prefill while decode scans
write the pool around it.
"""
import jax
import numpy as np
import pytest

from conftest import tiny_model

from repro.core.energy import prefill_counts, step_energy
from repro.models import Model, ModelConfig
from repro.models.config import SSMConfig, repeat_pattern
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving import engine as engine_mod

PS = 8                                 # page size exercised in the suite
CH = 8                                 # prefill chunk size


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-chunked", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def run_engine(m, params, reqs, prefill_chunk, **kw):
    args = dict(max_batch=4, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=prefill_chunk)
    args.update(kw)
    eng = ServingEngine(m, params, EngineConfig(**args))
    for r in reqs:
        eng.submit(Request(**r))
    resps = {r.rid: r for r in eng.run()}
    return resps, eng


def assert_parity(m, params, reqs, prefill_chunk=CH, **kw):
    want, _ = run_engine(m, params, reqs, prefill_chunk=None, **kw)
    got, eng = run_engine(m, params, reqs, prefill_chunk=prefill_chunk, **kw)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, f"request {rid} diverged"
        assert got[rid].finished == want[rid].finished
        assert got[rid].rejected == want[rid].rejected
    return eng


def assert_pool_clean(eng):
    alloc = jax.device_get(eng.caches["paged"])
    P = alloc["free"].shape[0]
    assert int(alloc["top"]) == P
    assert (np.asarray(alloc["tbl"]) == -1).all()
    assert (np.asarray(alloc["ref"]) == 0).all()
    assert eng.free_pages == [eng.num_pages]


# ------------------------------------------------------------------ parity


def test_chunk_span_1_2_many_and_partial(parts):
    """Prompts spanning one chunk, exactly two chunks, many chunks, and a
    partial last chunk — all token-for-token with the monolithic oracle."""
    _, m, params = parts
    rng = np.random.default_rng(7)
    lens = (3,           # < one chunk (partial only)
            CH,          # exactly one chunk
            2 * CH,      # exactly two chunks
            2 * CH + 5,  # many chunks, partial last
            30)          # many chunks, page boundary inside a chunk
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=9)
            for i, n in enumerate(lens)]
    eng = assert_parity(m, params, reqs)
    assert eng.prefill_chunks == sum(-(-n // CH) for n in lens)
    assert_pool_clean(eng)


def test_admitted_mid_stream_while_slots_decode(parts):
    """The acceptance case: a long prompt is admitted while other slots
    actively decode. The quantum scheduler must interleave its chunks with
    their fused decode scans — and every token must still equal the
    blocking-admit oracle."""
    _, m, params = parts
    rng = np.random.default_rng(11)
    # 2 slots: both fill with long-budget decoders; the long prompt queues
    # behind and is admitted only when slot 0 frees mid-run
    reqs = [dict(rid=0, prompt=list(rng.integers(0, 256, 5)),
                 max_new_tokens=10),
            dict(rid=1, prompt=list(rng.integers(0, 256, 6)),
                 max_new_tokens=40),
            dict(rid=2, prompt=list(rng.integers(0, 256, 3 * CH + 3)),
                 max_new_tokens=8)]
    eng = assert_parity(m, params, reqs, max_batch=2)
    st = eng.stats()
    # rid 2's 4 chunks ran while rid 1 still decoded: the scheduler packed
    # mixed quanta (prefill chunks happened after decode chunks started)
    assert st["prefill_chunks"] >= 4
    assert st["peak_active"] == 2
    assert_pool_clean(eng)


def test_eos_and_budget_one(parts):
    """EOS raised mid-chunk and a budget-1 request (prefill token is the
    whole budget, slot released straight from the prefill queue)."""
    _, m, params = parts
    probe, _ = run_engine(m, params,
                          [dict(rid=0, prompt=[9, 8, 7, 6, 5],
                                max_new_tokens=12)], prefill_chunk=None)
    eos = probe[0].tokens[4]
    reqs = [dict(rid=0, prompt=[9, 8, 7, 6, 5], max_new_tokens=12,
                 eos_id=eos),
            dict(rid=1, prompt=list(range(1, CH + 4)), max_new_tokens=1)]
    eng = assert_parity(m, params, reqs)
    assert_pool_clean(eng)


def test_pool_pressure_queues_and_completes(parts):
    """A tight pool forces requests to wait for reclaimed pages while
    earlier ones prefill chunk-by-chunk; everyone finishes with parity."""
    _, m, params = parts
    rng = np.random.default_rng(3)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, 10)),
                 max_new_tokens=8)
            for i in range(6)]
    eng = assert_parity(m, params, reqs, num_pages=7)
    assert eng.stats()["peak_pages_reserved"] <= 7
    assert_pool_clean(eng)


def test_oversized_and_never_fitting_rejected(parts):
    """Reservation rules are unchanged by chunking: a prompt + decode
    budget that can never fit the block table is rejected up front,
    fitting ones complete. A prompt that alone exceeds max_len doesn't
    even enqueue — submit() refuses it immediately."""
    _, m, params = parts
    # 62 prompt + 4 decode = 66 > max_len=64 -> needs 9 of 8 table slots
    reqs = [dict(rid=0, prompt=list(range(1, 63)), max_new_tokens=5),
            dict(rid=1, prompt=[1, 2, 3], max_new_tokens=5)]
    eng = assert_parity(m, params, reqs)
    assert eng.responses[0].finish_reason == "rejected"
    assert_pool_clean(eng)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(rid=9, prompt=list(range(1, 70)),
                           max_new_tokens=5))


def test_chunked_requires_paged_and_attention_only(parts):
    _, m, params = parts
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(m, params, EngineConfig(max_batch=2, max_len=64,
                                              prefill_chunk=8))
    cfg = ModelConfig(
        name="tiny-hybrid", family="hybrid", n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab=128, dtype="float32",
        block_pattern=repeat_pattern(("mamba2", "dense"), 2),
        ssm=SSMConfig(state_dim=16, head_dim=16, chunk=4),
        vocab_pad_multiple=8)
    hm = Model(cfg)
    assert hm.supports_paged_decode and not hm.supports_chunked_prefill
    hp = hm.init(jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="chunked prefill"):
        ServingEngine(hm, hp, EngineConfig(max_batch=2, max_len=64,
                                           paged=True, page_size=PS,
                                           prefill_chunk=8))


# ----------------------------------------------------------------- packing


def test_chunk_packing_parity_fewer_launches(parts):
    """prefill_pack > 1 packs several queued requests' chunks into ONE
    quantum when their combined token count fits prefill_chunk: every
    token stream, the FCFS completion order, and the metered prefill
    totals are EXACTLY the K=1 schedule's (packing regroups launches, it
    never re-chunks a request) — only the launch count drops."""
    _, m, params = parts
    rng = np.random.default_rng(9)
    lens = (3, 2, 4, 6, 2, 3, 9, 5)    # mostly sub-chunk prompts
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=5) for i, n in enumerate(lens)]
    runs = {}
    for pack in (1, 3):
        resp, eng = run_engine(m, params, reqs, CH, prefill_pack=pack)
        pf = eng.meter.phase("prefill")
        runs[pack] = ({rid: r.tokens for rid, r in resp.items()},
                      eng.prefill_chunks,
                      (pf.steps, pf.tokens, pf.energy_j, pf.time_s))
        assert_pool_clean(eng)
    assert runs[1][0] == runs[3][0], "packing changed a token stream"
    assert runs[3][1] < runs[1][1], "packing never merged a launch"
    assert runs[1][2] == runs[3][2], "packing drifted the prefill meter"
    # and the packed engine still matches the monolithic oracle
    eng = assert_parity(m, params, reqs, prefill_chunk=CH, prefill_pack=3)
    assert_pool_clean(eng)


def test_packing_with_sharing_one_cow_per_launch(parts, monkeypatch):
    """Regression: two whole-prompt-shared adopters of the SAME page must
    not copy-on-write it inside one packed launch. The device CoWs every
    row against a single pre-launch refcount snapshot — two rows at ref 2
    would BOTH privatize and free the original — while the host mirror
    decrefs sequentially (second row sees ref 1, keeps the page indexed):
    a use-after-free window in the prefix index for the next adopter.
    pack_chunks therefore packs at most one CoW-pending row per launch;
    a spy on the packer pins that rule against the live schedule below.

    Schedule: the donor registers its prefix and keeps decoding while a
    long prompt occupies the prefill queue; two whole-prompt twins then
    admit (adopting the resident pages), the donor releases (ref -> 2),
    and the twins' recomputed-tail chunks reach the packer together."""
    _, m, params = parts
    rng = np.random.default_rng(21)
    donor_prompt = list(rng.integers(0, 256, 2 * PS))    # two whole pages
    long_prompt = list(rng.integers(0, 256, 6 * CH))

    cow_rows: list = []
    real_pack = engine_mod.pack_chunks

    def spy(prefilling, chunk, pack):
        out = real_pack(prefilling, chunk, pack)
        cow_rows.append(sum(1 for req, _, _, _ in out if req.cow_pending))
        return out

    monkeypatch.setattr(engine_mod, "pack_chunks", spy)

    def run(pack):
        eng = ServingEngine(m, params, EngineConfig(
            max_batch=4, max_len=64, sync_every=4, paged=True,
            page_size=PS, prefill_chunk=CH, prefill_pack=pack,
            prefix_sharing=True))
        eng.submit(Request(rid=0, prompt=list(donor_prompt),
                           max_new_tokens=6))
        eng.submit(Request(rid=1, prompt=list(long_prompt),
                           max_new_tokens=4))
        eng.run(max_steps=2)           # donor registered + decoding
        eng.submit(Request(rid=2, prompt=list(donor_prompt),
                           max_new_tokens=3))
        eng.submit(Request(rid=3, prompt=list(donor_prompt),
                           max_new_tokens=3))
        resps = {r.rid: r.tokens for r in eng.run()}
        return resps, eng

    base, beng = run(1)
    assert beng.prefix_shared_requests >= 2   # the twins really adopted
    cow_rows.clear()
    packed, eng = run(3)
    assert packed == base, "packed CoW launch changed a token stream"
    assert eng.prefix_shared_requests >= 2
    # both twins' CoW chunks flowed through the packer, never together
    assert sum(cow_rows) >= 2
    assert max(cow_rows) <= 1, \
        "two CoW-pending rows packed into one launch"
    assert_pool_clean(eng)
    assert_pool_clean(beng)


def test_packing_respects_chunk_budget(parts):
    """Prompts of one full chunk or more leave no budget to pack behind
    the head: the launch count (and everything else) must equal K=1 —
    the knob can only merge launches the budget allows."""
    _, m, params = parts
    rng = np.random.default_rng(10)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=4)
            for i, n in enumerate((CH, 2 * CH, 3 * CH))]
    launches = {}
    for pack in (1, 4):
        resp, eng = run_engine(m, params, reqs, CH, prefill_pack=pack)
        launches[pack] = (eng.prefill_chunks,
                          {rid: r.tokens for rid, r in resp.items()})
    assert launches[1] == launches[4]


# ---------------------------------------------------------------- metering


def test_modeled_j_per_token_invariant_to_chunk_size(parts):
    """The paper's per-phase model attributes prefill at the request's true
    prompt length — chunking changes the schedule, not the modeled energy.
    Metered totals must be EXACTLY equal at chunk sizes {64, 256, full}."""
    _, m, params = parts
    rng = np.random.default_rng(5)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=4)
            for i, n in enumerate((300, 100, 37))]  # spans many/1/partial
    totals = {}
    for chunk in (64, 256, 512):       # 512 >= every prompt: "full" chunks
        _, eng = run_engine(m, params, reqs, prefill_chunk=chunk,
                            max_batch=1, max_len=512)  # serial: decode
        pf, dc = eng.meter.phase("prefill"), eng.meter.phase("decode")
        totals[chunk] = (pf.tokens, pf.energy_j, pf.time_s,
                         dc.tokens, dc.energy_j)
    base = totals[64]
    for chunk, t in totals.items():
        assert t == base, f"chunk={chunk}: metered totals drifted"
    assert base[0] == 300 + 100 + 37   # true prompt tokens, no padding


def test_prefill_phase_totals_invariant_under_interleaving(parts):
    """Even with decode interleaved (multi-slot), the PREFILL phase totals
    must not depend on the chunk size."""
    _, m, params = parts
    rng = np.random.default_rng(6)
    reqs = [dict(rid=i, prompt=list(rng.integers(0, 256, int(n))),
                 max_new_tokens=7)
            for i, n in enumerate((30, 9, 21, 14, 26))]
    pf_totals = set()
    for chunk in (4, 16, 64):
        _, eng = run_engine(m, params, reqs, prefill_chunk=chunk)
        pf = eng.meter.phase("prefill")
        pf_totals.add((pf.steps, pf.tokens, pf.energy_j, pf.time_s))
    assert len(pf_totals) == 1
    (steps, tokens, _, _), = pf_totals
    assert steps == len(reqs)          # one attribution per request
    assert tokens == sum(len(r["prompt"]) for r in reqs)


def test_monolithic_admission_meters_real_padded_launch(parts):
    """Regression (admission metering fix): one bucketed admission batch
    must be metered as ONE (n_pad, bucket) launch — real tokens attributed,
    launch energy shared by true prompt length — not as n batch-1 launches
    at exact length."""
    _, m, params = parts
    eng = ServingEngine(m, params, EngineConfig(max_batch=4, max_len=64))
    rng = np.random.default_rng(2)
    p0, p1, p2 = (list(rng.integers(0, 256, n)) for n in (9, 12, 16))
    for i, p in enumerate((p0, p1, p2)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=1))
    resps = {r.rid: r for r in eng.run()}
    pf = eng.meter.phase("prefill")
    assert pf.steps == 1               # ONE launch, not 3
    assert pf.tokens == 9 + 12 + 16    # real tokens only
    # the launch the device actually ran: n_pad=4 rows (pow2) x bucket 16
    rep = step_energy(eng.profile,
                      prefill_counts(eng.workload, 4, 16,
                                     useful_seq=(9 + 12 + 16) / 4))
    assert pf.energy_j == pytest.approx(rep.energy_j)
    assert pf.time_s == pytest.approx(rep.t_total)
    # per-request shares: energy split by true length, time = whole launch
    for rid, L in ((0, 9), (1, 12), (2, 16)):
        assert resps[rid].energy_j == pytest.approx(
            rep.energy_j * L / (9 + 12 + 16))
        assert resps[rid].prefill_s == pytest.approx(rep.t_total)


# ---------------------------------------------------------------- jit reuse


def test_jit_entries_reused_across_engines(parts):
    """Regression guard for the module-level jit refactor: constructing and
    running a SECOND engine with the same model config must not grow the
    compile caches of the shared entry points."""
    _, m, params = parts

    def exercise():
        for chunk in (None, CH):
            eng = ServingEngine(m, params, EngineConfig(
                max_batch=4, max_len=64, sync_every=8, paged=True,
                page_size=PS, prefill_chunk=chunk))
            eng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5],
                               max_new_tokens=6))
            eng.submit(Request(rid=1, prompt=list(range(1, CH + 5)),
                               max_new_tokens=4))
            eng.run()

    exercise()                         # populate caches (sizes may grow)
    entries = (engine_mod._PREFILL, engine_mod._FUSED_STEPS,
               engine_mod._INSERT_PAGED, engine_mod._CHUNK_PREFILL,
               engine_mod._BEGIN_CHUNKED, engine_mod._ARM,
               engine_mod._RELEASE)
    sizes = [f._cache_size() for f in entries]
    assert all(s > 0 for s in sizes[:2])
    exercise()                         # same config: zero new traces
    assert [f._cache_size() for f in entries] == sizes


# ------------------------------------------------------- the stacked pool


class WatchedEngine(ServingEngine):
    """Counts the chunk launches that copy-on-write a shared page and the
    decode scans that run while a slot is frozen mid-chunked-prefill."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.cow_launches = self.frozen_scans = 0

    def _prefill_quantum(self):
        cow = any(req.cow_pending for req, _ in self._prefilling[0])
        ran = super()._prefill_quantum()
        self.cow_launches += bool(ran and cow)
        return ran

    def _decode_chunk(self, max_steps):
        frozen = any(0 < req.prefill_pos for req, _ in self._prefilling[0])
        ran = super()._decode_chunk(max_steps)
        self.frozen_scans += bool(ran and frozen)
        return ran


@pytest.fixture(scope="module")
def kind_parts(request):
    return tiny_model(request.param, f"tiny-chunk-{request.param}")


def _contiguous(m, params, reqs, max_batch):
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=max_batch, max_len=64, sync_every=8))
    for r in reqs:
        eng.submit(Request(**r))
    return {r.rid: r.tokens for r in eng.run()}


def _watched(m, params, first, later, **kw):
    """Serve ``first``, step until one of them decodes, then submit
    ``later`` and drain."""
    eng = WatchedEngine(m, params, EngineConfig(
        max_len=64, sync_every=8, paged=True, page_size=PS,
        prefill_chunk=CH, **kw))
    for r in first:
        eng.submit(Request(**r))
    while not eng.decoding:
        eng.step()
    for r in later:
        eng.submit(Request(**r))
    return {r.rid: r.tokens for r in eng.run()}, eng


@pytest.mark.parametrize("kind_parts", ["dense", "latent"], indirect=True)
def test_shared_page_after_cow_matches_contiguous(kind_parts):
    """A follower whose whole prompt (3 pages) is resident adopts the
    donor's pages and recomputes its last token, so that chunk copies the
    shared tail page on write into the stacked pool; both then decode,
    token for token as on the contiguous cache."""
    _, m, params = kind_parts
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 256, 3 * PS).tolist()
    donor = dict(rid=0, prompt=prompt, max_new_tokens=24)
    follower = dict(rid=1, prompt=list(prompt), max_new_tokens=12)
    got, eng = _watched(m, params, [donor], [follower], max_batch=2,
                        prefix_sharing=True, num_pages=12)
    assert eng.cow_launches == 1 and eng.prefix_hit_tokens == 3 * PS - 1
    assert got == _contiguous(m, params, [donor, follower], 2)
    assert_pool_clean(eng)


@pytest.mark.parametrize("kind_parts", ["dense", "latent"], indirect=True)
def test_slot_frozen_mid_chunked_prefill_matches_contiguous(kind_parts):
    """A 4-chunk prompt admitted while another slot decodes: the fused
    scans between its chunks run with its slot inactive and its cursors
    frozen, writing garbage to the row its next chunk overwrites; every
    token matches the contiguous cache."""
    _, m, params = kind_parts
    rng = np.random.default_rng(29)
    runner = dict(rid=0, prompt=rng.integers(0, 256, 5).tolist(),
                  max_new_tokens=40)
    long = dict(rid=1, prompt=rng.integers(0, 256, 3 * CH + 5).tolist(),
                max_new_tokens=10)
    got, eng = _watched(m, params, [runner], [long], max_batch=2)
    assert eng.frozen_scans >= 2
    assert got == _contiguous(m, params, [runner, long], 2)
    assert_pool_clean(eng)
