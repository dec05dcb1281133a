"""Engine hot-path benchmark: fused on-device serving step vs the seed
per-token Python loop (requests/s, decode steps/s, host syncs per 100
generated tokens), the paged KV pool vs the contiguous slot pool (max
concurrent requests at equal pool memory; decode steps/s at equal batch),
chunked prefill vs the blocking admit path (p99 inter-token latency
under a long-prompt + active-decode mixed workload; decode steps/s at
equal batch), and prefix sharing vs the non-shared paged engine on a
shared-system-prompt workload (max concurrent requests at equal pool
bytes; follower TTFT). Every variant also reports measured TTFT and
inter-token latency p50/p99 from per-token host emission timestamps —
chunked prefill's win is a tail-latency claim, so it has to be measured,
not modeled. The ``hetero`` section serves one diurnal mixed trace
through a heterogeneous 4-shard fleet (two hardware generations, three
grid regions) twice — carbon-aware routing + low-CI deferral vs
capacity-greedy free-pages placement — and compares fleet gCO2/token at
fixed aggregate pool bytes. The ``resilience`` section kills 1 of 4
shards mid-trace and checks token parity vs a fail-free fleet, separate
recompute-phase metering, and degraded throughput vs a native 3-shard
baseline. The ``migration`` section gracefully drains a shard mid-trace
by live KV-page migration and compares its recompute bill (zero J — the
copy is metered to the separate migrate phase) against fold-based
evacuation on the same trace, both token-identical to an undisturbed
oracle. Writes ``BENCH_engine.json``; ``--smoke`` (CI) runs every
code path once at reduced size and writes ``BENCH_engine_smoke.json``
instead, so the committed numbers are never clobbered by a shared runner.

The baseline below is a faithful copy of the seed ``ServingEngine`` hot
path: one jitted decode dispatch per token, sampling + EOS/budget checks in
Python, one ``np.mean(caches["t"])`` device sync per step plus one scalar
readback per active slot, per-request prefill, and per-request whole-tree
cache inserts. The fused engine (repro.serving.engine) runs ``sync_every``
full engine micro-steps per device call and admits in bucketed batches.

    PYTHONPATH=src:. python benchmarks/engine_bench.py [--variant smoke|full]

``--variant full`` runs the actual paper 1B geometry (slow on CPU; the
default smoke variant keeps the same code path at CI-friendly size).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import llama_paper
from repro.core.energy import decode_counts, prefill_counts, step_energy
from repro.core.hardware import get_profile
from repro.core.meter import CarbonMeter
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.models.costing import workload_of
from repro.serving import (EngineConfig, Request, ServingEngine,
                           ShardedServingEngine)

BATCH = 8
N_REQUESTS = 16
MAX_NEW = 65          # 1 prefill token + 64 decode steps = 8 full chunks

# --smoke (CI) runs every code path once at reduced size: the bench can't
# rot unnoticed, without pretending a shared runner's timings are data
REPEATS = 3           # median-of-N samples for steps/s comparisons
TAIL_RUNS = 5         # min-of-N samples for the ITL p99 comparison


# ------------------------------------------------------------- latencies


def _latency_stats(emit_times: List[List[float]], t0: float) -> Dict:
    """TTFT + inter-token-latency percentiles from per-response emission
    timestamps. Tokens surfacing in the same host sync share a timestamp
    (gap 0), so the percentiles measure exactly what a caller streaming
    from this engine would see — including prefill-induced stalls."""
    ttft, itl = [], []
    for ts in emit_times:
        if not ts:
            continue
        ttft.append(ts[0] - t0)
        itl.extend(b - a for a, b in zip(ts, ts[1:]))

    def pct(xs, q):
        return float(np.percentile(xs, q)) if xs else 0.0

    return {
        "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
        "itl_p50_s": pct(itl, 50), "itl_p99_s": pct(itl, 99),
        "itl_max_s": max(itl) if itl else 0.0,
    }


# ---------------------------------------------------------------- baseline


def _insert_cache(dst, src, slot: int):
    def leaf(kp, d, s):
        bdim = 1 if getattr(kp[0], "key", None) == "unit" else 0
        idx = [slice(None)] * d.ndim
        idx[bdim] = slot
        return d.at[tuple(idx)].set(jnp.take(s, 0, axis=bdim))
    return jax.tree_util.tree_map_with_path(leaf, dst, src)


class SeedEngine:
    """The seed serving loop, preserved verbatim as the benchmark baseline."""

    def __init__(self, model: Model, params, max_batch: int, max_len: int):
        self.model, self.params = model, params
        self.max_len = max_len
        self.profile = get_profile("t4")
        self.meter = CarbonMeter(self.profile, "QC")
        self.workload = workload_of(model.cfg)
        self.queue: List[Request] = []
        self.responses: Dict[int, object] = {}
        self.t_emit: Dict[int, List[float]] = {}
        B = max_batch
        self.caches = model.init_cache(B, max_len)
        self.slot_rid = [-1] * B
        self.slot_budget = [0] * B
        self.cur_tokens = jnp.zeros((B, 1), jnp.int32)
        self._jit_decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
        self.steps = 0
        self.host_syncs = 0

    def submit(self, req: Request):
        self.queue.append(req)
        self.responses[req.rid] = []
        self.t_emit[req.rid] = []

    @property
    def active(self):
        return sum(1 for r in self.slot_rid if r >= 0)

    def _admit(self):
        for slot in [i for i, r in enumerate(self.slot_rid) if r < 0]:
            if not self.queue:
                break
            req = self.queue.pop(0)
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            last, pcache = self.model.prefill(self.params, prompt,
                                              max_len=self.max_len)
            counts = prefill_counts(self.workload, 1, len(req.prompt))
            rep = step_energy(self.profile, counts)
            self.meter.record("prefill", rep.tokens, rep.t_total, rep.energy_j)
            self.caches = _insert_cache(self.caches, pcache, slot)
            nxt = jnp.argmax(last[:, :self.model.cfg.vocab], -1).astype(jnp.int32)
            self.cur_tokens = self.cur_tokens.at[slot, 0].set(nxt[0])
            self.responses[req.rid].append(int(nxt[0]))
            self.t_emit[req.rid].append(time.perf_counter())
            self.host_syncs += 1
            self.slot_rid[slot] = req.rid
            self.slot_budget[slot] = req.max_new_tokens - 1

    def _decode_once(self):
        logits, self.caches = self._jit_decode(self.params, self.caches,
                                               self.cur_tokens)
        ctx = float(np.mean(np.asarray(self.caches["t"])))    # sync per step
        self.host_syncs += 1
        counts = decode_counts(self.workload, self.active, max(ctx, 1.0))
        rep = step_energy(self.profile, counts)
        self.meter.record("decode", rep.tokens, rep.t_total, rep.energy_j)
        nxt = jnp.argmax(logits[:, :self.model.cfg.vocab], -1).astype(jnp.int32)
        self.cur_tokens = nxt[:, None]
        for slot, rid in enumerate(self.slot_rid):
            if rid < 0:
                continue
            self.responses[rid].append(int(nxt[slot]))        # scalar sync
            self.t_emit[rid].append(time.perf_counter())
            self.host_syncs += 1
            self.slot_budget[slot] -= 1
            if self.slot_budget[slot] <= 0:
                self.slot_rid[slot] = -1
        self.steps += 1

    def run(self):
        while self.queue or self.active:
            self._admit()
            if self.active:
                self._decode_once()
        return self.responses


# ------------------------------------------------------------------ bench


def _workload(n_requests: int, max_new: int) -> List[Request]:
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=list(rng.integers(1, 400, int(rng.integers(6, 30)))),
                    max_new_tokens=max_new)
            for i in range(n_requests)]


def _time_fused(model, params, reqs, max_len: int, max_batch: int = BATCH,
                **engine_kw) -> Dict:
    eng = ServingEngine(model, params, EngineConfig(
        max_batch=max_batch, max_len=max_len, sync_every=8, **engine_kw))
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    st = eng.stats()
    served = [r for r in eng.responses.values() if not r.rejected]
    decode_tokens = sum(max(len(r.tokens) - 1, 0) for r in served)
    out = {
        "wall_s": dt,
        **_latency_stats([r.t_emit for r in served], t0),
        "requests_per_s": len(served) / dt,
        "decode_steps": st["steps"],
        "decode_steps_per_s": st["steps"] / dt,
        "host_syncs": st["host_syncs"],
        "decode_chunks": st["decode_chunks"],
        "syncs_per_100_decode_tokens":
            100.0 * st["host_syncs"] / max(decode_tokens, 1),
        "decode_steps_per_sync": st["steps"] / max(st["decode_chunks"], 1),
        "max_concurrent_requests": st["peak_active"],
    }
    if engine_kw.get("paged"):
        out.update({
            "pages_total": st["pages_total"],
            "peak_pages_reserved": st["peak_pages_reserved"],
            "peak_kv_rows_reserved": st["peak_kv_rows_reserved"],
        })
    if engine_kw.get("prefix_sharing"):
        out.update({
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "prefix_shared_requests": st["prefix_shared_requests"],
            "shared_pages": st["shared_pages"],
            "unique_pages": st["unique_pages"],
        })
    return out


def _bench_paged(model, params, max_len: int, page_size: int = 16) -> Dict:
    """Paged vs contiguous fused engine on one workload, two comparisons:

    * equal pool MEMORY — the paged pool owns exactly the KV rows the
      contiguous slots own (num_pages = BATCH * max_len / page_size) but
      spreads them over 4x the slots; short requests then pack many more
      concurrent residents into the same bytes (the embodied-carbon win);
    * equal BATCH — same slot count, ample pages; isolates the per-step
      cost of block-table indirection on the decode hot path.
    """
    # requests sized ~max_len/4 so concurrency is page-limited, not
    # slot-limited: L<=30 prompt + 33 tokens -> <= 4 pages of 16
    reqs = _workload(4 * BATCH, max_new=33)
    equal_mem_pages = BATCH * max_len // page_size
    warm = _workload(2, 8)             # compile both paged trace shapes
    _time_fused(model, params, warm, max_len, max_batch=4 * BATCH,
                paged=True, page_size=page_size, num_pages=equal_mem_pages)
    _time_fused(model, params, warm, max_len, paged=True,
                page_size=page_size, num_pages=equal_mem_pages)

    def median_of_3(**kw):
        # steps/s on a loaded CPU box swings +-30% run to run; the paged-
        # overhead criterion compares MEDIANS so it measures the layout,
        # not scheduler luck (concurrency/pages/sync counts are exact)
        runs = [_time_fused(model, params, reqs, max_len, **kw)
                for _ in range(REPEATS)]
        runs.sort(key=lambda r: r["decode_steps_per_s"])
        return runs[len(runs) // 2]

    base = median_of_3()
    paged_mem = _time_fused(model, params, reqs, max_len,
                            max_batch=4 * BATCH, paged=True,
                            page_size=page_size, num_pages=equal_mem_pages)
    paged_batch = median_of_3(paged=True, page_size=page_size,
                              num_pages=equal_mem_pages)
    concurrency_ratio = (paged_mem["max_concurrent_requests"]
                         / max(base["max_concurrent_requests"], 1))
    steps_ratio = (paged_batch["decode_steps_per_s"]
                   / max(base["decode_steps_per_s"], 1e-9))
    return {
        "page_size": page_size,
        "pool_kv_rows": equal_mem_pages * page_size,
        "contiguous": base,
        "paged_equal_memory": paged_mem,
        "paged_equal_batch": paged_batch,
        "max_concurrent_ratio": concurrency_ratio,
        "decode_steps_per_s_ratio_equal_batch": steps_ratio,
    }


def _bench_chunked(model, params, max_len: int, page_size: int = 16,
                   chunk: int = 32) -> Dict:
    """Chunked prefill vs the blocking admit path, two comparisons:

    * mixed workload — B decode-active requests plus one LONG prompt that
      is admitted mid-stream when the first slot frees. The blocking path
      stalls every decoder for the whole monolithic prefill (their inter-
      token latency spikes); the quantum scheduler bounds the stall to one
      prefill chunk per sync. Compared on measured p99 inter-token latency
      of the requests that were decoding through the admission.
    * decode-only at equal batch — the chunked engine runs the same fused
      decode scan; the quantum scheduler's bookkeeping must cost <= 10%
      decode steps/s vs the paged baseline.

    The mixed comparison runs long-context (768-token prompt, 1024-row
    slots, 4-step decode quanta) — exactly the regime chunked prefill
    exists for: a prompt comparable to one decode scan never stalls anyone
    noticeably. p99 is taken as the MINIMUM over 5 runs with GC paused:
    wall-clock tails on a loaded CPU box carry 20-40 ms scheduler/GC
    spikes that are additive and sporadic, so the min-over-runs is the
    robust estimator of each path's structural stall (same spirit as the
    median-of-3 used for steps/s above; both paths get the identical
    treatment).
    """
    import gc

    B = 4
    mixed_len = 1024
    long_len = 768

    def mixed_reqs() -> List[Request]:
        rng = np.random.default_rng(42)
        reqs = [Request(rid=i, prompt=list(rng.integers(1, 400, 8)),
                        max_new_tokens=(16 if i == 0 else 56))
                for i in range(B)]
        reqs.append(Request(rid=B,
                            prompt=list(rng.integers(1, 400, long_len)),
                            max_new_tokens=8))
        return reqs

    def decoders_itl_p99(**kw) -> float:
        eng = ServingEngine(model, params, EngineConfig(
            max_batch=B, max_len=mixed_len, sync_every=4, paged=True,
            page_size=page_size, **kw))
        for r in mixed_reqs():
            eng.submit(r)
        t0 = time.perf_counter()
        gc.collect()
        gc.disable()
        try:
            eng.run()
        finally:
            gc.enable()
        riding = [r for r in eng.responses.values()
                  if 0 < r.rid < B]    # decoding while the long prompt ran
        return _latency_stats([r.t_emit for r in riding], t0)["itl_p99_s"]

    def min5(fn):
        fn()                           # compile/warm this path's shapes
        return min(fn() for _ in range(TAIL_RUNS))

    blocked_p99 = min5(lambda: decoders_itl_p99())
    chunked_p99 = min5(lambda: decoders_itl_p99(prefill_chunk=chunk))

    # decode-only throughput at equal batch (short prompts, long decodes)
    reqs = _workload(2 * B, max_new=MAX_NEW)

    def steps_per_s(**kw) -> Dict:
        runs = [_time_fused(model, params, reqs, max_len, max_batch=B,
                            paged=True, page_size=page_size, **kw)
                for _ in range(REPEATS)]
        runs.sort(key=lambda r: r["decode_steps_per_s"])
        return runs[len(runs) // 2]

    base = steps_per_s()
    chunked = steps_per_s(prefill_chunk=chunk)
    return {
        "prefill_chunk": chunk,
        "long_prompt_len": long_len,
        "mixed_itl_p99_s_blocking": blocked_p99,
        "mixed_itl_p99_s_chunked": chunked_p99,
        "mixed_itl_p99_improvement":
            blocked_p99 / max(chunked_p99, 1e-9),
        "paged_equal_batch": base,
        "chunked_equal_batch": chunked,
        "decode_steps_per_s_ratio_equal_batch":
            chunked["decode_steps_per_s"]
            / max(base["decode_steps_per_s"], 1e-9),
    }


def _bench_prefix(model, params, smoke: bool = False) -> Dict:
    """Prefix sharing vs the non-shared chunked paged engine on a shared-
    system-prompt workload (N requests repeating one common prefix), at
    EQUAL pool bytes.

    Two claims, both structural rather than timing-luck: admission
    reserves only the UNSHARED worst case, so the same pool packs many
    more concurrent residents (the embodied-carbon lever — Eq. 2-4 charge
    per request falls with deduplicated provisioning); and chunked prefill
    starts at the first unshared token, so followers' TTFT drops by the
    skipped prefix compute. The pool holds the donor plus a little
    headroom — never two unshared requests — so the non-shared engine
    serializes the queue while the sharing engine runs the whole fleet
    off one resident prefix. Decode steps/s
    needs no separate criterion — sharing changes admission and prefill
    starts, not the decode kernels (the block table already indirects
    every read).
    """
    ps = 16
    prefix_len = 64 if smoke else 512
    n_req = 4 if smoke else 8
    chunk = 32 if smoke else 64
    max_new, suffix = 8, 8
    donor_new = 40            # request 0 keeps the prefix resident: the
    #                           followers arrive while it still decodes,
    #                           like steady system-prompt traffic would
    L = prefix_len + suffix
    max_len = 1 << (L + donor_new - 1).bit_length()      # pow2 cache width
    donor_need = -(-(L + donor_new - 1) // ps)
    # pool = the donor plus one unshared-suffix reservation per follower:
    # a second UNSHARED request can never fit, while the whole shared
    # fleet does — capacity headroom is exactly what sharing frees up
    num_pages = donor_need + n_req
    rng = np.random.default_rng(7)
    common = list(rng.integers(1, 400, prefix_len))
    suffixes = [list(rng.integers(1, 400, suffix)) for _ in range(n_req)]

    def reqs() -> List[Request]:
        return [Request(rid=i, prompt=common + suffixes[i],
                        max_new_tokens=donor_new if i == 0 else max_new)
                for i in range(n_req)]

    kw = dict(max_batch=n_req, paged=True, page_size=ps,
              num_pages=num_pages, prefill_chunk=chunk)
    for shared in (False, True):       # compile both variants' shapes
        _time_fused(model, params, reqs()[:2], max_len, prefix_sharing=shared,
                    **kw)
    base = _time_fused(model, params, reqs(), max_len,
                       prefix_sharing=False, **kw)
    shared = _time_fused(model, params, reqs(), max_len,
                         prefix_sharing=True, **kw)
    return {
        "prefix_len": prefix_len, "n_requests": n_req, "page_size": ps,
        "pool_kv_rows": num_pages * ps,
        "nonshared": base,
        "shared": shared,
        "max_concurrent_ratio": (shared["max_concurrent_requests"]
                                 / max(base["max_concurrent_requests"], 1)),
        "ttft_p50_improvement": (base["ttft_p50_s"]
                                 / max(shared["ttft_p50_s"], 1e-9)),
        # the tail TTFT is the structural claim: without sharing the last
        # follower waits out the whole serialized queue of full prefills
        "ttft_p99_improvement": (base["ttft_p99_s"]
                                 / max(shared["ttft_p99_s"], 1e-9)),
        "peak_kv_rows_per_request_nonshared":
            base["peak_kv_rows_reserved"]
            / max(base["max_concurrent_requests"], 1),
        "peak_kv_rows_per_request_shared":
            shared["peak_kv_rows_reserved"]
            / max(shared["max_concurrent_requests"], 1),
    }


def _time_sharded(model, params, reqs, max_len: int, shards: int,
                  max_batch: int, **engine_kw) -> Dict:
    """Run the mesh-sharded fleet on one workload. ``max_batch`` and
    ``num_pages`` are PER SHARD, mirroring the single-device engine's
    meaning at equal per-device batch / pool bytes."""
    eng = ShardedServingEngine(model, params, EngineConfig(
        max_batch=max_batch, max_len=max_len, sync_every=8, paged=True,
        shards=shards, **engine_kw))
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    st = eng.stats()
    served = [r for r in eng.responses.values() if not r.rejected]
    decode_tokens = sum(max(len(r.tokens) - 1, 0) for r in served)
    return {
        "wall_s": dt,
        **_latency_stats([r.t_emit for r in served], t0),
        "requests_per_s": len(served) / dt,
        "fleet_steps": st["steps"],
        # aggregate device decode steps: shard_steps counts (micro-step,
        # shard) pairs in which that shard emitted >= 1 token — the SAME
        # counting rule as the single engine's decode_steps (which skips
        # drained micro-steps), so the ratio compares like with like at
        # equal per-device batch
        "shard_decode_steps": st["shard_steps"],
        "aggregate_decode_steps_per_s": st["shard_steps"] / dt,
        "host_syncs": st["host_syncs"],
        "decode_chunks": st["decode_chunks"],
        "syncs_per_100_decode_tokens":
            100.0 * st["host_syncs"] / max(decode_tokens, 1),
        "max_concurrent_requests": st["peak_active"],
        "pages_total": st["pages_total"],
        "pages_per_shard": st["pages_per_shard"],
        "peak_pages_reserved": st["peak_pages_reserved"],
        "peak_kv_rows_reserved": st["peak_kv_rows_reserved"],
    }


def _bench_sharded(model, params, max_len: int, page_size: int = 16,
                   shards: int = 4, chunk: int = 32,
                   smoke: bool = False) -> Dict:
    """Mesh-sharded fleet vs the 1-device paged engine, three structural
    claims (measured at --xla_force_host_platform_device_count=4):

    * equal per-device BATCH (S shards of B vs one device of B, S times
      the requests): the fleet's aggregate decode steps/s — micro-steps
      summed over occupied shards — must be >= 1.5x the single device's,
      because one fused fleet program amortizes the per-call host+dispatch
      overhead over every shard and the partitions execute in parallel;
    * equal per-device POOL BYTES (same num_pages per shard as the single
      device's whole pool, page-limited workload): the fleet packs >= 3x
      the concurrent requests — per-shard free stacks mean capacity
      scales with installed devices, the embodied-carbon denominator;
    * host syncs per 100 decode tokens no worse than the single fused
      engine: the fleet syncs ONCE per chunk for all shards (the stacked
      (S, n, B) fetch), so serving S times the load costs the same sync
      cadence.
    """
    if jax.device_count() < shards:
        return {"skipped":
                f"needs {shards} host devices, have {jax.device_count()}: "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards} before the first jax import"}
    kw = dict(page_size=page_size, prefill_chunk=chunk)
    n_per_dev = (1 if smoke else 2) * BATCH
    max_new = 17 if smoke else MAX_NEW
    reps = 1 if smoke else REPEATS

    # --- equal per-device batch: aggregate decode steps/s
    single_reqs = _workload(n_per_dev, max_new)
    fleet_reqs = _workload(shards * n_per_dev, max_new)
    _time_fused(model, params, _workload(2, 8), max_len, max_batch=BATCH,
                paged=True, **kw)      # compile
    _time_sharded(model, params, _workload(2, 8), max_len, shards=shards,
                  max_batch=BATCH, **kw)

    def median(fn, key):
        runs = sorted((fn() for _ in range(reps)), key=lambda r: r[key])
        return runs[len(runs) // 2]

    base = median(lambda: _time_fused(model, params, single_reqs, max_len,
                                      max_batch=BATCH, paged=True, **kw),
                  "decode_steps_per_s")
    fleet = median(lambda: _time_sharded(model, params, fleet_reqs, max_len,
                                         shards=shards, max_batch=BATCH,
                                         **kw),
                   "aggregate_decode_steps_per_s")

    # --- equal per-device pool bytes: max concurrent requests. The pool
    # is sized so concurrency is page-limited, not slot-limited (requests
    # need <= 4 pages each, the pool holds 8 of those per device); smoke
    # keeps the same shape at a quarter of the queue depth.
    tight_pages = 2 * max_len // page_size
    conc_kw = dict(num_pages=tight_pages, max_batch=2 * BATCH, **kw)
    conc_reqs = _workload((1 if smoke else 4) * shards * BATCH, max_new=17)
    base_conc = _time_fused(model, params, conc_reqs, max_len, paged=True,
                            **conc_kw)
    fleet_conc = _time_sharded(model, params, conc_reqs, max_len,
                               shards=shards, **conc_kw)
    return {
        "shards": shards,
        "prefill_chunk": chunk,
        "per_device_batch": BATCH,
        "single_paged": base,
        "sharded": fleet,
        "aggregate_decode_steps_per_s_ratio":
            fleet["aggregate_decode_steps_per_s"]
            / max(base["decode_steps_per_s"], 1e-9),
        "pool_kv_rows_per_device": tight_pages * page_size,
        "single_paged_equal_pool": base_conc,
        "sharded_equal_pool": fleet_conc,
        "max_concurrent_ratio":
            fleet_conc["max_concurrent_requests"]
            / max(base_conc["max_concurrent_requests"], 1),
        "syncs_per_100_decode_tokens_single":
            base["syncs_per_100_decode_tokens"],
        "syncs_per_100_decode_tokens_sharded":
            fleet["syncs_per_100_decode_tokens"],
    }


def _bench_resilience(model, params, max_len: int, page_size: int = 16,
                      shards: int = 4, chunk: int = 32,
                      smoke: bool = False) -> Dict:
    """Kill 1 of ``shards`` shards mid-trace and measure the recovery
    contract end to end (at --xla_force_host_platform_device_count=4):

    * token parity — every in-flight and queued request still completes,
      with a token stream bit-identical to a fail-free fleet serving the
      same workload: greedy decode depends only on context, so
      evacuation + resume recompute must be a pure re-route;
    * the energy of the forced recompute is metered under the separate
      ``recompute`` phase (``preempted_recompute_j``), so ordinary
      prefill/decode J/token stays invariant to the failure;
    * degraded throughput — the killed fleet's request throughput stays
      within 1.3x of a NATIVE (shards-1)-shard fleet on the identical
      workload: evacuation is a re-queue onto survivors, not a collapse.
    """
    if jax.device_count() < shards:
        return {"skipped":
                f"needs {shards} host devices, have {jax.device_count()}: "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards} before the first jax import"}
    from repro.serving.faults import FaultInjector, FaultPlan
    n_req = (2 if smoke else 4) * shards
    max_new = 17 if smoke else 33
    kill_shard, kill_q = shards - 1, 3
    kw = dict(max_len=max_len, sync_every=4, paged=True,
              page_size=page_size, prefill_chunk=chunk, preemption=True)

    def timed(n_shards, kill=False):
        eng = ShardedServingEngine(model, params, EngineConfig(
            max_batch=BATCH, shards=n_shards, **kw))
        if kill:
            eng.faults = FaultInjector([FaultPlan(
                "shard_down", at_quantum=kill_q, shard=kill_shard)])
        for r in _workload(n_req, max_new):
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        st = eng.stats()
        tokens = {rid: tuple(resp.tokens)
                  for rid, resp in eng.responses.items() if not resp.rejected}
        return {
            "wall_s": dt,
            "requests_per_s": len(tokens) / dt,
            "fleet_steps": st["steps"],
            "recompute_j": st["preempted_recompute_j"],
            "shard_down_events": st["shard_down_events"],
            "shard_evacuated": st["shard_evacuated"],
            "live_shards": st["live_shards"],
        }, tokens

    timed(shards)                        # compile both fleet widths...
    timed(shards - 1)
    timed(shards, kill=True)             # ...and the disarm/quarantine
    #                                      recovery programs

    def median(*t_args, **t_kw):
        runs = sorted((timed(*t_args, **t_kw)
                       for _ in range(max(REPEATS, 3))),
                      key=lambda r: r[0]["requests_per_s"])
        return runs[len(runs) // 2]

    failfree, oracle = median(shards)
    faulted, got = median(shards, kill=True)
    survivor, _ = median(shards - 1)
    return {
        "shards": shards, "kill_shard": kill_shard, "kill_quantum": kill_q,
        "n_requests": n_req, "max_new_tokens": max_new,
        "failfree": failfree, "faulted": faulted,
        "survivor_baseline": survivor,
        "tokens_match_failfree_oracle": got == oracle,
        "recompute_j": faulted["recompute_j"],
        "recompute_j_failfree": failfree["recompute_j"],
        # native 3-shard throughput over the degraded run's: how much the
        # mid-trace kill + evacuation recompute cost beyond simply having
        # one fewer shard from the start
        "survivor_throughput_ratio":
            survivor["requests_per_s"]
            / max(faulted["requests_per_s"], 1e-9),
    }


def _resilience_criteria(d: Dict) -> Dict:
    if "skipped" in d:
        return {}
    return {
        # the kill really happened mid-trace and forced an evacuation
        "resilience_kill_fired_and_evacuated":
            d["faulted"]["shard_down_events"] == 1
            and d["faulted"]["shard_evacuated"] >= 1
            and d["faulted"]["live_shards"] == d["shards"] - 1,
        # every request completes token-identical to the fail-free fleet
        "resilience_token_identical_to_failfree":
            d["tokens_match_failfree_oracle"],
        # evacuation recompute is metered under its own phase; the
        # fail-free run charges none
        "resilience_recompute_metered_separately":
            d["recompute_j"] > 0.0 and d["recompute_j_failfree"] == 0.0,
        # surviving fleet keeps serving at a rate comparable to a fleet
        # that was (shards-1)-wide all along
        "resilience_survivor_throughput_within_1_3x":
            d["survivor_throughput_ratio"] <= 1.3,
    }


def _bench_migration(model, params, max_len: int, page_size: int = 16,
                     shards: int = 4, chunk: int = 32,
                     smoke: bool = False) -> Dict:
    """Gracefully drain 1 of ``shards`` shards mid-trace by LIVE KV-page
    migration and compare against fold-based evacuation (an unreachable
    kill at the same quantum) on the identical workload, plus an
    undisturbed oracle (at --xla_force_host_platform_device_count=4):

    * token parity — both the drained and the folded run complete every
      request with token streams bit-identical to the undisturbed fleet
      (greedy decode depends only on context);
    * the drained run's in-flight work moves by page copy, so its
      recompute phase stays at ZERO joules — the copy energy lands in
      the separate ``migrate`` phase on both endpoints — while the fold
      path re-spends real prefill energy as ``recompute``;
    * the headline acceptance ratio: fold-based evacuation spends at
      least 5x the recompute J of drain-based migration on this trace.
    """
    if jax.device_count() < shards:
        return {"skipped":
                f"needs {shards} host devices, have {jax.device_count()}: "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards} before the first jax import"}
    n_req = (2 if smoke else 4) * shards
    max_new = 17 if smoke else 33
    target, admin_q = shards - 1, 3
    kw = dict(max_batch=BATCH, max_len=max_len, sync_every=4, paged=True,
              page_size=page_size, prefill_chunk=chunk, preemption=True,
              shards=shards)

    def timed(mode):
        eng = ShardedServingEngine(model, params, EngineConfig(**kw))
        for r in _workload(n_req, max_new):
            eng.submit(r)
        t0 = time.perf_counter()
        if mode != "none":
            for _ in range(admin_q):
                eng.step()
            if mode == "drain":
                eng.drain(target)
            else:                       # unreachable kill: the fold path
                eng.fail_shard(target, reachable=False)
        eng.run()
        dt = time.perf_counter() - t0
        st = eng.stats()
        tokens = {rid: tuple(resp.tokens)
                  for rid, resp in eng.responses.items() if not resp.rejected}
        return {
            "wall_s": dt,
            "requests_per_s": len(tokens) / dt,
            "recompute_j": st["preempted_recompute_j"],
            "migrate_j": st["migrate_j"],
            "migrations": st["migrations"],
            "migrated_pages": st["migrated_pages"],
            "drain_events": st["drain_events"],
            "shard_down_events": st["shard_down_events"],
            "live_shards": st["live_shards"],
        }, tokens

    for mode in ("none", "drain", "fold"):   # compile all three programs
        timed(mode)

    def median(mode):
        runs = sorted((timed(mode) for _ in range(max(REPEATS, 3))),
                      key=lambda r: r[0]["requests_per_s"])
        return runs[len(runs) // 2]

    undisturbed, oracle = median("none")
    drained, got_drain = median("drain")
    folded, got_fold = median("fold")
    eps = 1e-9
    return {
        "shards": shards, "drain_shard": target, "drain_quantum": admin_q,
        "n_requests": n_req, "max_new_tokens": max_new,
        "undisturbed": undisturbed, "drained": drained, "folded": folded,
        "drain_tokens_match_oracle": got_drain == oracle,
        "fold_tokens_match_oracle": got_fold == oracle,
        "drain_recompute_j": drained["recompute_j"],
        "fold_recompute_j": folded["recompute_j"],
        # the headline: J of state re-derivation the page copy avoided,
        # per J of recompute the drain still spent (0 when every slot
        # migrated — the epsilon keeps the ratio finite)
        "fold_over_drain_recompute_ratio":
            (folded["recompute_j"] + eps)
            / (drained["recompute_j"] + eps),
    }


def _migration_criteria(d: Dict) -> Dict:
    if "skipped" in d:
        return {}
    return {
        # the drain really moved live pages and emptied the shard into
        # the shard-down machinery
        "migration_drain_fired_and_emptied_shard":
            d["drained"]["drain_events"] == 1
            and d["drained"]["migrations"] >= 1
            and d["drained"]["live_shards"] == d["shards"] - 1,
        # both disturbance modes are token-invisible vs the undisturbed
        # fleet on the same trace
        "migration_drain_token_identical_to_oracle":
            d["drain_tokens_match_oracle"],
        "migration_fold_token_identical_to_oracle":
            d["fold_tokens_match_oracle"],
        # page migration is recompute-FREE: the copy is metered under the
        # separate migrate phase, the recompute phase stays at zero
        "migration_drain_zero_recompute_j":
            d["drain_recompute_j"] == 0.0
            and d["drained"]["migrate_j"] > 0.0,
        # the acceptance ratio: fold-based evacuation re-spends >= 5x the
        # recompute energy that drain-based migration avoids
        "migration_drain_ge_5x_less_recompute_than_fold":
            d["fold_over_drain_recompute_ratio"] >= 5.0,
    }


def _time_seed(model, params, reqs, max_len: int) -> Dict:
    eng = SeedEngine(model, params, max_batch=BATCH, max_len=max_len)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    decode_tokens = sum(len(t) - 1 for t in eng.responses.values())
    return {
        "wall_s": dt,
        **_latency_stats(list(eng.t_emit.values()), t0),
        "requests_per_s": len(reqs) / dt,
        "decode_steps": eng.steps,
        "decode_steps_per_s": eng.steps / dt,
        "host_syncs": eng.host_syncs,
        "syncs_per_100_decode_tokens":
            100.0 * eng.host_syncs / max(decode_tokens, 1),
    }


def _bench_server(model, params, smoke: bool = False) -> Dict:
    """Open-loop trace-driven serving through the async front door at
    FIXED pool bytes: a steady low-priority background stream (Poisson
    arrivals, long decodes) plus high-priority bursts, served twice over
    the identical trace — preemption OFF then ON, everything else equal.

    The claims are the robustness PR's acceptance bar: with preemption
    on, a high-priority arrival evicts a background decode instead of
    waiting out the queue, so high-pri tail TTFT must improve >= 2x; the
    bounded queue sheds ONLY low-priority work under the reject_lowest
    policy (zero high-pri sheds, low-pri sheds reported — the overload
    is real); and because resume prefills are metered under the separate
    ``recompute`` phase, the modeled J/token of ordinary prefill/decode
    work is invariant to the preemption policy."""
    from benchmarks.load_gen import (bursty_trace, mixed_requests,
                                     poisson_trace, run_open_loop,
                                     summarize)
    ps, B, num_pages = 8, 2, 24          # fixed pool bytes for BOTH runs
    max_len = 128
    n_low = 8 if smoke else 16
    low_new = 44 if smoke else 80        # long decodes: a held slot hurts
    n_bursts = 1 if smoke else 4         # bursts of 2 (the slot count):
    burst = 2                            # preemption, not sibling queueing

    def trace():
        # rebuilt per pass: the engine folds evicted requests' tokens
        # into req.prompt in place, so specs cannot be reused as objects
        rng = np.random.default_rng(1234)
        # background arrivals outpace the 2-slot fleet by design: the
        # bounded queue MUST overflow, or the shedding claim is vacuous —
        # and the bursts land INSIDE the backlog window, where a slot is
        # only free if preemption makes one. Moderate overload (not a
        # stampede): both passes should shed a FEW low requests while
        # serving comparable decode volume, keeping the J/token
        # comparison about metering, not occupancy collapse.
        rate = 300.0 if n_low <= 8 else 120.0
        low = mixed_requests(poisson_trace(rate, n_low, rng), rng,
                             prompt_len=(8, 14), max_new_tokens=low_new,
                             priority=0, deadline_s=30.0)
        high = mixed_requests(
            bursty_trace(n_bursts, burst, 0.05, 0.01, rng, start_s=0.02),
            rng, prompt_len=(4, 8), max_new_tokens=4, priority=1,
            deadline_s=30.0, rid0=1000)
        return sorted(low + high, key=lambda s: s["arrival_s"])

    def serve(preempt: bool) -> Dict:
        eng = ServingEngine(model, params, EngineConfig(
            max_batch=B, max_len=max_len, sync_every=4, paged=True,
            page_size=ps, num_pages=num_pages, prefill_chunk=16,
            preemption=preempt, prefix_sharing=preempt, max_queue=3,
            shed_policy="reject_lowest"))
        recs = run_open_loop(eng, trace())
        s = summarize(recs)
        st = eng.stats()
        dec = eng.meter.phase("decode")
        return {
            "summary": s,
            "preemption_count": st["preemption_count"],
            "shed_count": st["shed_count"],
            "preempted_recompute_j": st["preempted_recompute_j"],
            "decode_j_per_token": dec.j_per_token,
            "decode_tokens": dec.tokens,
            "queue_wait_p99_s_class_1":
                st.get("queue_wait_p99_s_class_1", float("nan")),
        }

    serve(False)                         # compile both shapes off-clock
    serve(True)
    off = serve(False)
    on = serve(True)
    hi_on = on["summary"]["classes"].get("1", {})
    hi_off = off["summary"]["classes"].get("1", {})
    lo_on = on["summary"]["classes"].get("0", {})
    return {
        "page_size": ps, "pool_kv_rows": num_pages * ps, "max_batch": B,
        "n_low": n_low, "low_max_new": low_new,
        "n_high": n_bursts * burst,
        "preemption_off": off, "preemption_on": on,
        "high_pri_ttft_p99_improvement":
            hi_off.get("ttft_p99_s", float("nan"))
            / max(hi_on.get("ttft_p99_s", float("nan")), 1e-9),
        "high_pri_sheds_on": hi_on.get("shed", 0),
        "low_pri_sheds_on": lo_on.get("shed", 0),
        "decode_j_per_token_ratio":
            on["decode_j_per_token"] / max(off["decode_j_per_token"], 1e-12),
    }


def _bench_hetero(model, params, smoke: bool = False) -> Dict:
    """Carbon-aware routing over a heterogeneous fleet vs free-pages
    placement: the SAME diurnal mixed trace (interactive priority-1 work
    plus a deferrable priority-0 batch class) served twice through a
    4-shard fleet spanning two hardware generations (rtx6000ada, t4) and
    three grid regions (PACE 647 g/kWh, CISO 262, QC 31), at FIXED
    aggregate pool bytes — only the placement policy and the deferral
    knob change between passes.

    The claims (measured at --xla_force_host_platform_device_count=4):

    * fleet gCO2/token with ``routing="carbon"`` + batch deferral is
      >= 1.3x LOWER than capacity-greedy ``free_pages`` routing on the
      identical trace — free_pages spreads load onto the dirty-grid
      shards it has no reason to avoid, while the marginal-gCO2 score
      (phase-specific operational J at the shard's current CI plus the
      Eq. 2-4 embodied rent on reserved pages) concentrates work on the
      green slices and parks batch work for the CI valley;
    * p99 TTFT of the NON-deferred interactive class stays within 10%
      of the free_pages pass — carbon placement only reorders among
      eligible shards (free slot + pages), so latency work is never
      queued behind a greener-but-full shard;
    * ZERO deferred requests finish by deadline — the forced-release
      path (``defer_deadline_frac`` of the budget) fires before the
      deadline can, so chasing the green window never costs correctness.
    """
    shards = 4
    if jax.device_count() < shards:
        return {"skipped":
                f"needs {shards} host devices, have {jax.device_count()}: "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards} before the first jax import"}
    from benchmarks.load_gen import diurnal_trace, mixed_requests
    profiles = ["rtx6000ada", "rtx6000ada", "t4", "t4"]
    regions = ["PACE", "CISO", "QC", "QC"]
    # slots/pages per shard sized so the GREEN shards can absorb the whole
    # released batch wave (admission is work-conserving FCFS: a wave
    # larger than green capacity spills onto PACE and the comparison
    # measures capacity, not routing); pool fixed both passes
    ps = 8
    B = 4 if smoke else 8
    pages = 32 if smoke else 64
    max_len = 128
    n_batch = 8 if smoke else 16         # <= the 2 QC shards' slot count
    n_live = 6 if smoke else 12
    batch_new = 8 if smoke else 24
    live_new = 4 if smoke else 8

    def reqs() -> List[Request]:
        # rebuilt per pass (the engine mutates requests in place); the
        # arrival trace is diurnal — rate phase-locked to the CISO CI
        # curve — and interleaves the two classes by arrival time
        rng = np.random.default_rng(99)
        batch = mixed_requests(
            diurnal_trace(4.0, n_batch, rng, region="CISO", depth=0.8),
            rng, prompt_len=(6, 18), max_new_tokens=batch_new,
            priority=0, deadline_s=120.0)
        live = mixed_requests(
            diurnal_trace(2.0, n_live, rng, region="CISO", depth=0.8),
            rng, prompt_len=(4, 10), max_new_tokens=live_new,
            priority=1, rid0=1000)
        out = []
        for s in sorted(batch + live, key=lambda s: s["arrival_s"]):
            s = dict(s)
            s.pop("arrival_s")
            if s["rid"] >= 1000:
                # the interactive class is SLO-PINNED: under carbon
                # routing it keeps load-first placement (greener shard
                # only as tie-break), so chasing green slices never
                # queues its prefills — that is the p99-within-10% claim
                s["slo_s"] = 1.0
            out.append(Request(**s))
        return out

    def serve(routing: str) -> Dict:
        eng = ShardedServingEngine(model, params, EngineConfig(
            max_batch=B, max_len=max_len, sync_every=8, paged=True,
            page_size=ps, num_pages=pages, prefill_chunk=16, shards=shards,
            shard_profiles=profiles, shard_regions=regions, routing=routing,
            use_diurnal_ci=True,
            defer_below_priority=(1 if routing == "carbon" else None)))
        for r in reqs():
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        st = eng.stats()
        tot = eng.meter.totals
        live_resps = [r for r in eng.responses.values() if r.rid >= 1000]
        batch_resps = [r for r in eng.responses.values() if r.rid < 1000]
        return {
            "wall_s": dt,
            "tokens": tot.tokens,
            "energy_j": tot.energy_j,
            "operational_g": tot.operational_g,
            "embodied_g": tot.embodied_g,
            "carbon_g": tot.total_g,
            "g_per_token": tot.g_per_token,
            "j_per_token": tot.j_per_token,
            "live_ttft_p50_s": _latency_stats(
                [r.t_emit for r in live_resps], t0)["ttft_p50_s"],
            "live_ttft_p99_s": _latency_stats(
                [r.t_emit for r in live_resps], t0)["ttft_p99_s"],
            "deferred_requests": st["deferred_requests"],
            "deferred_released": st["deferred_released"],
            "deferred_forced_releases": st["deferred_forced_releases"],
            "deferred_deadline_violations": sum(
                1 for r in batch_resps if r.finish_reason == "deadline"),
            "shard_requests": [int(st[f"shard{s}_requests"])
                               for s in range(shards)],
            "shard_carbon_g": [st[f"shard{s}_carbon_g"]
                               for s in range(shards)],
            "final_clock_hours": float(eng.clock.hours),
        }

    serve("free_pages")                  # compile: each policy concentrates
    serve("carbon")                      # work differently -> own shapes
    # placement and modeled carbon are deterministic across runs; the
    # wall-clock TTFT tail is not (same 20-40ms scheduler spikes the
    # chunked section de-noises), so the latency comparison takes the
    # MINIMUM over repeats for both policies alike
    reps = 1 if smoke else TAIL_RUNS
    runs_free = [serve("free_pages") for _ in range(reps)]
    runs_carbon = [serve("carbon") for _ in range(reps)]
    free, carbon = runs_free[-1], runs_carbon[-1]
    for out_d, runs in ((free, runs_free), (carbon, runs_carbon)):
        for k in ("live_ttft_p50_s", "live_ttft_p99_s"):
            out_d[k] = min(r[k] for r in runs)
    return {
        "shards": shards,
        "shard_profiles": profiles,
        "shard_regions": regions,
        "per_shard_pool_kv_rows": pages * ps,
        "n_batch": n_batch, "n_live": n_live,
        "free_pages": free,
        "carbon": carbon,
        "g_per_token_improvement":
            free["g_per_token"] / max(carbon["g_per_token"], 1e-12),
        "live_ttft_p99_ratio":
            carbon["live_ttft_p99_s"] / max(free["live_ttft_p99_s"], 1e-9),
        "j_per_token_ratio":
            carbon["j_per_token"] / max(free["j_per_token"], 1e-12),
    }


def _hetero_criteria(hetero: Dict) -> Dict:
    if "skipped" in hetero:
        return {}
    return {
        # the tentpole claim: marginal-gCO2 placement + low-CI deferral
        # cut fleet carbon per token >= 1.3x vs capacity-greedy routing
        # on the identical trace at equal aggregate pool bytes
        "hetero_carbon_ge_1_3x_lower_g_per_token":
            hetero["g_per_token_improvement"] >= 1.3,
        # chasing green shards must not tax the latency class: p99 TTFT
        # of the non-deferred interactive work within 10%
        "hetero_live_ttft_p99_within_10pct":
            hetero["live_ttft_p99_ratio"] <= 1.10,
        # the deferral queue is SLO-safe: every parked request released
        # in time (forced by deadline pressure if the window never came)
        "hetero_zero_deferred_deadline_violations":
            hetero["carbon"]["deferred_deadline_violations"] == 0,
        # and the batch class really was parked, not trivially admitted
        "hetero_batch_class_deferred":
            hetero["carbon"]["deferred_requests"] == hetero["n_batch"],
    }


def _bench_impacts(model, params, smoke: bool = False) -> Dict:
    """Multi-criteria impact ledger + measured-power calibration
    (docs/METHODOLOGY.md#the-impact-ledger, #measured-power).

    Part 1 serves the hetero diurnal mixed trace once through the 4-shard
    two-generation fleet under carbon routing and reports the fleet's
    FOUR-criteria totals (gCO2 / water L / primary MJ / ADPe mg) per
    phase and per shard — checking that the fleet totals are the exact
    sum of the per-shard attribution (1e-12) and that the hydro shards
    really do run water/PE-lighter per joule than the coal shard.

    Part 2 synthesizes a power trace from a deliberately mis-knobbed
    rtx6000ada profile's WORKLOAD (truth profile generates the samples),
    fits the power knobs back with ``fit_power_trace``, and reports the
    recovered total-energy error plus per-phase residuals — the
    modeled-J-to-auditable-J loop, deterministic by fixed seed.
    """
    shards = 4
    if jax.device_count() < shards:
        return {"skipped":
                f"needs {shards} host devices, have {jax.device_count()}: "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards} before the first jax import"}
    from benchmarks.load_gen import diurnal_trace, mixed_requests
    profiles = ["rtx6000ada", "rtx6000ada", "t4", "t4"]
    regions = ["PACE", "CISO", "QC", "QC"]
    ps = 8
    B = 4 if smoke else 8
    pages = 32 if smoke else 64
    n_batch = 8 if smoke else 16
    n_live = 6 if smoke else 12

    def reqs() -> List[Request]:
        rng = np.random.default_rng(7)
        batch = mixed_requests(
            diurnal_trace(4.0, n_batch, rng, region="CISO", depth=0.8),
            rng, prompt_len=(6, 18), max_new_tokens=8 if smoke else 24,
            priority=0, deadline_s=120.0)
        live = mixed_requests(
            diurnal_trace(2.0, n_live, rng, region="CISO", depth=0.8),
            rng, prompt_len=(4, 10), max_new_tokens=4 if smoke else 8,
            priority=1, rid0=1000)
        out = []
        for s in sorted(batch + live, key=lambda s: s["arrival_s"]):
            s = dict(s)
            s.pop("arrival_s")
            out.append(Request(**s))
        return out

    eng = ShardedServingEngine(model, params, EngineConfig(
        max_batch=B, max_len=128, sync_every=8, paged=True, page_size=ps,
        num_pages=pages, prefill_chunk=16, shards=shards,
        shard_profiles=profiles, shard_regions=regions, routing="carbon",
        use_diurnal_ci=True))
    for r in reqs():
        eng.submit(r)
    eng.run()
    st = eng.stats()
    crits = ("water_l", "primary_mj", "adpe_mg")
    per_shard = {c: [getattr(eng.meters[s].totals, c)
                     for s in range(shards)] for c in crits}
    fleet = {c: getattr(eng.meter.totals, c) for c in crits}
    sum_err = max(
        abs(fleet[c] - sum(per_shard[c])) / max(abs(fleet[c]), 1e-30)
        for c in crits)
    # water intensity (L/kWh drawn) per shard: hydro QC vs coal PACE
    water_per_kwh = [
        per_shard["water_l"][s]
        / max(eng.meters[s].totals.energy_j / 3.6e6, 1e-30)
        for s in range(shards)]

    # part 2: measured-power calibration loop (device-free, deterministic)
    from repro.core.calibrate import fit_power_trace
    from repro.core.energy import (LLAMA_1B, decode_counts, prefill_counts)
    from repro.core.hardware import get_profile
    from repro.core.power_trace import SegmentPlan, synthesize_trace
    truth = get_profile("rtx6000ada")
    plan = [SegmentPlan("prefill", prefill_counts(LLAMA_1B, 8, 512),
                        20 if smoke else 40),
            SegmentPlan("decode", decode_counts(LLAMA_1B, 8, 600),
                        1000 if smoke else 2000)]
    rng = np.random.default_rng(0)
    trace, segs = synthesize_trace(truth, plan, interval_s=0.05, pad_s=5.0,
                                   noise_frac=0.02, rng=rng)
    import dataclasses as _dc
    start = _dc.replace(truth, idle_w=truth.idle_w * 2.0,
                        power_alpha=truth.power_alpha * 0.6,
                        eff_compute=truth.eff_compute * 0.7,
                        eff_memory=truth.eff_memory * 0.8)
    n_iter = 150 if smoke else 400
    cal = fit_power_trace(trace, segs, base=start, n_random=n_iter,
                          n_refine=n_iter, seed=1)
    return {
        "shards": shards,
        "shard_profiles": profiles,
        "shard_regions": regions,
        "fleet": {
            "tokens": eng.meter.totals.tokens,
            "energy_j": eng.meter.totals.energy_j,
            "carbon_g": eng.meter.totals.total_g,
            "water_l": fleet["water_l"],
            "primary_mj": fleet["primary_mj"],
            "adpe_mg": fleet["adpe_mg"],
            "water_per_token_l": st["water_per_token_l"],
        },
        "per_phase": {
            ph: {"water_l": st[f"{ph}_water_l"],
                 "primary_mj": st[f"{ph}_primary_mj"],
                 "adpe_mg": st[f"{ph}_adpe_mg"]}
            for ph in ("prefill", "decode")},
        "per_shard": per_shard,
        "shard_water_l_per_kwh": water_per_kwh,
        "fleet_sum_rel_err": sum_err,
        "calibration": {
            "profile": truth.name,
            "trace_samples": len(trace),
            "measured_wh": cal.measured_wh,
            "modeled_wh": cal.modeled_wh,
            "energy_error_frac": cal.energy_error_frac,
            "loss": cal.loss,
            "residuals": [
                {"phase": r.phase,
                 "measured_wh": r.measured_wh,
                 "modeled_wh": r.modeled_wh,
                 "energy_error_frac": r.energy_error_frac,
                 "time_error_frac": r.time_error_frac}
                for r in cal.residuals],
        },
    }


def _impacts_criteria(impacts: Dict) -> Dict:
    if "skipped" in impacts:
        return {}
    return {
        # fleet four-criteria totals are the EXACT sum of the per-shard
        # attribution — no second ledger that could drift
        "impacts_fleet_sums_exact_1e12":
            impacts["fleet_sum_rel_err"] <= 1e-12,
        # every criterion is populated for both serving phases
        "impacts_all_criteria_per_phase":
            all(v > 0 for ph in impacts["per_phase"].values()
                for v in ph.values()),
        # the hydro-grid shards (QC, shards 2-3) withdraw less water per
        # kWh than the coal-grid shard (PACE, shard 0)
        "impacts_clean_grid_less_water_per_kwh":
            max(impacts["shard_water_l_per_kwh"][2:])
            < impacts["shard_water_l_per_kwh"][0],
        # the calibration loop closes: fitted model's total energy within
        # 5% of the trace integral (ISSUE 9 acceptance criterion)
        "impacts_calibration_energy_within_5pct":
            abs(impacts["calibration"]["energy_error_frac"]) <= 0.05,
    }


def _server_criteria(server: Dict) -> Dict:
    return {
        # preemption turns queueing delay into eviction: high-priority
        # tail TTFT >= 2x better at the same pool bytes and trace
        "server_high_pri_ttft_p99_ge_2x_better":
            server["high_pri_ttft_p99_improvement"] >= 2.0,
        # the bounded queue protects the high class: overload sheds ONLY
        # low-priority work (and really does shed — the pressure is real)
        "server_zero_high_pri_sheds":
            server["high_pri_sheds_on"] == 0,
        "server_low_pri_sheds_under_overload":
            server["low_pri_sheds_on"] > 0,
        # recompute is metered in its own phase, so ordinary decode
        # J/token is invariant to the preemption policy
        "server_decode_j_per_token_within_10pct":
            abs(server["decode_j_per_token_ratio"] - 1.0) <= 0.10,
    }


def bench(variant: str = "smoke", n_requests: int = N_REQUESTS,
          max_new: int = MAX_NEW, smoke: bool = False) -> Dict:
    cfg = llama_paper.make(variant, "llama-paper-1b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = 128 if variant == "smoke" else 512
    # warmup both paths (compile), then timed runs on fresh engines
    warm = _workload(2, 8)
    _time_fused(model, params, warm, max_len)
    _time_seed(model, params, warm, max_len)
    reqs = _workload(n_requests, max_new)
    fused = _time_fused(model, params, reqs, max_len)
    seed = _time_seed(model, params, reqs, max_len)
    paged = _bench_paged(model, params, max_len)
    chunked = _bench_chunked(model, params, max_len)
    prefix = _bench_prefix(model, params, smoke=smoke)
    sharded = _bench_sharded(model, params, max_len, smoke=smoke)
    server = _bench_server(model, params, smoke=smoke)
    hetero = _bench_hetero(model, params, smoke=smoke)
    resilience = _bench_resilience(model, params, max_len, smoke=smoke)
    migration = _bench_migration(model, params, max_len, smoke=smoke)
    impacts = _bench_impacts(model, params, smoke=smoke)
    speedup = fused["decode_steps_per_s"] / seed["decode_steps_per_s"]
    out = {
        "config": cfg.name, "variant": variant, "batch": BATCH,
        "requests": n_requests, "max_new_tokens": max_new,
        "seed": seed, "fused": fused, "paged": paged, "chunked": chunked,
        "prefix": prefix, "sharded": sharded, "server": server,
        "hetero": hetero, "resilience": resilience,
        "migration": migration, "impacts": impacts,
        "decode_steps_per_s_speedup": speedup,
        "criteria": {
            "fused_ge_2x_decode_steps_per_s": speedup >= 2.0,
            # no chunk synced early: the engine never takes more than the
            # optimal ceil(steps / sync_every) host syncs
            "at_most_1_sync_per_8_decode_steps":
                fused["decode_chunks"] <= -(-fused["decode_steps"] // 8),
            # paged pool at EQUAL memory packs >= 2x concurrent requests
            "paged_ge_2x_concurrent_at_equal_memory":
                paged["max_concurrent_ratio"] >= 2.0,
            # block-table indirection costs <= 10% decode steps/s at equal
            # batch
            "paged_decode_steps_within_10pct":
                paged["decode_steps_per_s_ratio_equal_batch"] >= 0.9,
            # chunked prefill bounds decode tail latency: p99 inter-token
            # latency under a long-prompt admission improves >= 2x vs the
            # blocking admit path
            "chunked_itl_p99_ge_2x_better":
                chunked["mixed_itl_p99_improvement"] >= 2.0,
            # and the quantum scheduler costs <= 10% decode steps/s on a
            # decode-only workload at equal batch
            "chunked_decode_steps_within_10pct":
                chunked["decode_steps_per_s_ratio_equal_batch"] >= 0.9,
            # prefix sharing at EQUAL pool bytes packs >= 2x concurrent
            # requests on the shared-system-prompt workload (shared pages
            # are reserved once -> peak_kv_rows_reserved, the embodied-
            # carbon input, counts them once)
            "prefix_ge_2x_concurrent_at_equal_memory":
                prefix["max_concurrent_ratio"] >= 2.0,
            # followers skip the shared prefix compute, so the tail TTFT
            # (the last follower, who otherwise waits out the serialized
            # queue of full prefills) must improve vs non-shared paged
            "prefix_ttft_improves":
                prefix["ttft_p99_improvement"] > 1.0,
        },
    }
    out["criteria"].update(_sharded_criteria(sharded))
    out["criteria"].update(_server_criteria(server))
    out["criteria"].update(_hetero_criteria(hetero))
    out["criteria"].update(_resilience_criteria(resilience))
    out["criteria"].update(_migration_criteria(migration))
    out["criteria"].update(_impacts_criteria(impacts))
    return out


def _sharded_criteria(sharded: Dict) -> Dict:
    if "skipped" in sharded:
        return {}
    return {
        # the fleet's one-program-per-quantum design must WIN aggregate
        # throughput at equal per-device batch, not just break even:
        # >= 1.5x over the single fused device
        "sharded_ge_1_5x_aggregate_decode_steps":
            sharded["aggregate_decode_steps_per_s_ratio"] >= 1.5,
        # per-shard pools scale capacity with installed devices:
        # >= 3x concurrent requests at equal per-device pool bytes
        "sharded_ge_3x_concurrent_at_equal_per_device_pool":
            sharded["max_concurrent_ratio"] >= 3.0,
        # and the whole fleet still syncs like ONE fused engine
        "sharded_syncs_per_100_tokens_no_worse":
            sharded["syncs_per_100_decode_tokens_sharded"]
            <= sharded["syncs_per_100_decode_tokens_single"] + 1e-9,
    }


_LAST: Dict = {}


def run():
    """Small workload for the aggregator's timing loop."""
    global _LAST
    _LAST = bench(n_requests=6, max_new=16, smoke=True)
    return _LAST


def derived() -> float:
    """Fused/seed decode-steps/s speedup."""
    if not _LAST:
        run()
    return _LAST["decode_steps_per_s_speedup"]


def main():
    global REPEATS, TAIL_RUNS
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=N_REQUESTS)
    ap.add_argument("--max-new-tokens", type=int, default=MAX_NEW)
    ap.add_argument("--out", default=None,
                    help="output JSON (default BENCH_engine.json, or "
                         "BENCH_engine_smoke.json under --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: every code path once at reduced size; "
                         "never overwrites the committed BENCH_engine.json")
    ap.add_argument("--sharded-only", action="store_true",
                    help="re-measure ONLY the mesh-sharded section (run "
                         "under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=4) and merge it into the existing "
                         "output JSON — forcing host devices degrades "
                         "XLA:CPU's single-device throughput, so the other "
                         "sections' committed numbers must stay measured "
                         "on the default environment")
    ap.add_argument("--server-only", action="store_true",
                    help="re-measure ONLY the open-loop async-server "
                         "section and merge it into the existing output "
                         "JSON — the server bench is wall-clock "
                         "sensitive, so it can be refreshed on a quiet "
                         "machine without re-running everything else")
    ap.add_argument("--hetero-only", action="store_true",
                    help="re-measure ONLY the heterogeneous-fleet carbon "
                         "routing section (run under XLA_FLAGS=--xla_"
                         "force_host_platform_device_count=4) and merge "
                         "it into the existing output JSON — same "
                         "two-pass flow as --sharded-only, and for the "
                         "same reason: forcing host devices degrades the "
                         "single-device sections' timings")
    ap.add_argument("--resilience-only", action="store_true",
                    help="re-measure ONLY the shard-loss resilience "
                         "section (run under XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=4) and merge it into the "
                         "existing output JSON — same two-pass flow as "
                         "--sharded-only / --hetero-only")
    ap.add_argument("--migration-only", action="store_true",
                    help="re-measure ONLY the live KV-page migration "
                         "section (run under XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=4) and merge it into the "
                         "existing output JSON — same two-pass flow as "
                         "--sharded-only / --resilience-only")
    ap.add_argument("--impacts-only", action="store_true",
                    help="re-measure ONLY the multi-criteria impact "
                         "ledger + power-calibration section (run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=4) and merge it into the existing output "
                         "JSON — same two-pass flow as --sharded-only")
    args = ap.parse_args()
    if args.smoke:
        REPEATS, TAIL_RUNS = 1, 1
        args.requests = min(args.requests, 6)
        args.max_new_tokens = min(args.max_new_tokens, 17)
    if args.out is None:
        args.out = ("BENCH_engine_smoke.json" if args.smoke
                    else "BENCH_engine.json")
    if args.sharded_only:
        with open(args.out) as f:
            res = json.load(f)
        if res.get("variant") != args.variant:
            raise SystemExit(
                f"--sharded-only: {args.out} holds variant "
                f"{res.get('variant')!r}, refusing to merge a "
                f"{args.variant!r} sharded section into it")
        cfg = llama_paper.make(args.variant, "llama-paper-1b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 128 if args.variant == "smoke" else 512
        sharded = _bench_sharded(model, params, max_len, smoke=args.smoke)
        if "skipped" in sharded:
            # never clobber committed measurements with a skip stub
            raise SystemExit(f"--sharded-only: {sharded['skipped']}")
        res["sharded"] = sharded
        res["criteria"] = {k: v for k, v in res["criteria"].items()
                           if not k.startswith("sharded_")}
        res["criteria"].update(_sharded_criteria(res["sharded"]))
    elif args.hetero_only:
        with open(args.out) as f:
            res = json.load(f)
        if res.get("variant") != args.variant:
            raise SystemExit(
                f"--hetero-only: {args.out} holds variant "
                f"{res.get('variant')!r}, refusing to merge a "
                f"{args.variant!r} hetero section into it")
        cfg = llama_paper.make(args.variant, "llama-paper-1b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        hetero = _bench_hetero(model, params, smoke=args.smoke)
        if "skipped" in hetero:
            # never clobber committed measurements with a skip stub
            raise SystemExit(f"--hetero-only: {hetero['skipped']}")
        res["hetero"] = hetero
        res["criteria"] = {k: v for k, v in res["criteria"].items()
                           if not k.startswith("hetero_")}
        res["criteria"].update(_hetero_criteria(res["hetero"]))
    elif args.resilience_only:
        with open(args.out) as f:
            res = json.load(f)
        if res.get("variant") != args.variant:
            raise SystemExit(
                f"--resilience-only: {args.out} holds variant "
                f"{res.get('variant')!r}, refusing to merge a "
                f"{args.variant!r} resilience section into it")
        cfg = llama_paper.make(args.variant, "llama-paper-1b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 128 if args.variant == "smoke" else 512
        resilience = _bench_resilience(model, params, max_len,
                                       smoke=args.smoke)
        if "skipped" in resilience:
            # never clobber committed measurements with a skip stub
            raise SystemExit(f"--resilience-only: {resilience['skipped']}")
        res["resilience"] = resilience
        res["criteria"] = {k: v for k, v in res["criteria"].items()
                           if not k.startswith("resilience_")}
        res["criteria"].update(_resilience_criteria(res["resilience"]))
    elif args.migration_only:
        with open(args.out) as f:
            res = json.load(f)
        if res.get("variant") != args.variant:
            raise SystemExit(
                f"--migration-only: {args.out} holds variant "
                f"{res.get('variant')!r}, refusing to merge a "
                f"{args.variant!r} migration section into it")
        cfg = llama_paper.make(args.variant, "llama-paper-1b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 128 if args.variant == "smoke" else 512
        migration = _bench_migration(model, params, max_len,
                                     smoke=args.smoke)
        if "skipped" in migration:
            # never clobber committed measurements with a skip stub
            raise SystemExit(f"--migration-only: {migration['skipped']}")
        res["migration"] = migration
        res["criteria"] = {k: v for k, v in res["criteria"].items()
                           if not k.startswith("migration_")}
        res["criteria"].update(_migration_criteria(res["migration"]))
    elif args.impacts_only:
        with open(args.out) as f:
            res = json.load(f)
        if res.get("variant") != args.variant:
            raise SystemExit(
                f"--impacts-only: {args.out} holds variant "
                f"{res.get('variant')!r}, refusing to merge a "
                f"{args.variant!r} impacts section into it")
        cfg = llama_paper.make(args.variant, "llama-paper-1b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        impacts = _bench_impacts(model, params, smoke=args.smoke)
        if "skipped" in impacts:
            # never clobber committed measurements with a skip stub
            raise SystemExit(f"--impacts-only: {impacts['skipped']}")
        res["impacts"] = impacts
        res["criteria"] = {k: v for k, v in res["criteria"].items()
                           if not k.startswith("impacts_")}
        res["criteria"].update(_impacts_criteria(res["impacts"]))
    elif args.server_only:
        with open(args.out) as f:
            res = json.load(f)
        if res.get("variant") != args.variant:
            raise SystemExit(
                f"--server-only: {args.out} holds variant "
                f"{res.get('variant')!r}, refusing to merge a "
                f"{args.variant!r} server section into it")
        cfg = llama_paper.make(args.variant, "llama-paper-1b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        res["server"] = _bench_server(model, params, smoke=args.smoke)
        res["criteria"] = {k: v for k, v in res["criteria"].items()
                           if not k.startswith("server_")}
        res["criteria"].update(_server_criteria(res["server"]))
    else:
        res = bench(args.variant, args.requests, args.max_new_tokens,
                    smoke=args.smoke)
        if "skipped" in res["sharded"] or "skipped" in res["hetero"] \
                or "skipped" in res["resilience"] \
                or "skipped" in res["migration"] \
                or "skipped" in res["impacts"]:
            # pass 1 of the two-pass flow runs without forced host devices:
            # keep existing MEASURED 4-device sections (and their criteria)
            # rather than clobbering them with skip stubs — pass 2
            # (`make bench-engine-sharded` / `make bench-engine-hetero`)
            # is what refreshes them
            try:
                with open(args.out) as f:
                    prev = json.load(f)
            except (OSError, ValueError):
                prev = {}
            for section, crit in (("sharded", _sharded_criteria),
                                  ("hetero", _hetero_criteria),
                                  ("resilience", _resilience_criteria),
                                  ("migration", _migration_criteria),
                                  ("impacts", _impacts_criteria)):
                if "skipped" not in res[section]:
                    continue
                old = prev.get(section, {})
                if "skipped" not in old and old and \
                        prev.get("variant") == args.variant:
                    res[section] = old
                    res["criteria"].update(crit(old))
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    s, fu = res["seed"], res["fused"]
    print(f"\n== engine bench ({res['config']}, batch {BATCH}, "
          f"{res['requests']} reqs x {res['max_new_tokens']} tokens) ==")
    print(f"{'':>24}  {'seed loop':>12}  {'fused step':>12}")
    for key in ("requests_per_s", "decode_steps_per_s",
                "syncs_per_100_decode_tokens"):
        print(f"{key:>24}  {s[key]:12.2f}  {fu[key]:12.2f}")
    print(f"decode steps/s speedup: {res['decode_steps_per_s_speedup']:.2f}x"
          f"   decode steps per host sync: {fu['decode_steps_per_sync']:.1f}")
    pg = res["paged"]
    print(f"\n== paged KV pool (page_size {pg['page_size']}, "
          f"{pg['pool_kv_rows']} pooled KV rows) ==")
    print(f"max concurrent requests: contiguous "
          f"{pg['contiguous']['max_concurrent_requests']} -> paged "
          f"{pg['paged_equal_memory']['max_concurrent_requests']} "
          f"({pg['max_concurrent_ratio']:.2f}x at equal memory)")
    print(f"decode steps/s at equal batch: "
          f"{pg['contiguous']['decode_steps_per_s']:.2f} -> "
          f"{pg['paged_equal_batch']['decode_steps_per_s']:.2f} "
          f"({pg['decode_steps_per_s_ratio_equal_batch']:.2f}x)")
    print(f"peak pages reserved: "
          f"{pg['paged_equal_memory']['peak_pages_reserved']}"
          f"/{pg['paged_equal_memory']['pages_total']}")
    ck = res["chunked"]
    print(f"\n== chunked prefill (chunk {ck['prefill_chunk']}, "
          f"long prompt {ck['long_prompt_len']}) ==")
    print(f"mixed-workload decode ITL p99: blocking "
          f"{1e3 * ck['mixed_itl_p99_s_blocking']:.1f}ms -> chunked "
          f"{1e3 * ck['mixed_itl_p99_s_chunked']:.1f}ms "
          f"({ck['mixed_itl_p99_improvement']:.2f}x better)")
    print(f"decode steps/s at equal batch: "
          f"{ck['paged_equal_batch']['decode_steps_per_s']:.2f} -> "
          f"{ck['chunked_equal_batch']['decode_steps_per_s']:.2f} "
          f"({ck['decode_steps_per_s_ratio_equal_batch']:.2f}x)")
    px = res["prefix"]
    print(f"\n== prefix sharing ({px['n_requests']} reqs x "
          f"{px['prefix_len']}-token shared prefix, "
          f"{px['pool_kv_rows']} pooled KV rows) ==")
    print(f"max concurrent requests: non-shared "
          f"{px['nonshared']['max_concurrent_requests']} -> shared "
          f"{px['shared']['max_concurrent_requests']} "
          f"({px['max_concurrent_ratio']:.2f}x at equal pool bytes)")
    print(f"TTFT p50: {1e3 * px['nonshared']['ttft_p50_s']:.1f}ms -> "
          f"{1e3 * px['shared']['ttft_p50_s']:.1f}ms "
          f"({px['ttft_p50_improvement']:.2f}x)   p99: "
          f"{1e3 * px['nonshared']['ttft_p99_s']:.1f}ms -> "
          f"{1e3 * px['shared']['ttft_p99_s']:.1f}ms "
          f"({px['ttft_p99_improvement']:.2f}x)   "
          f"prefix-hit tokens: {px['shared']['prefix_hit_tokens']}")
    print(f"peak KV rows reserved per concurrent request: "
          f"{px['peak_kv_rows_per_request_nonshared']:.0f} -> "
          f"{px['peak_kv_rows_per_request_shared']:.0f}")
    sh = res["sharded"]
    if "skipped" in sh:
        print(f"\n== mesh-sharded serving: SKIPPED ({sh['skipped']}) ==")
    else:
        print(f"\n== mesh-sharded serving ({sh['shards']} shards x batch "
              f"{sh['per_device_batch']}) ==")
        print(f"aggregate decode steps/s at equal per-device batch: "
              f"{sh['single_paged']['decode_steps_per_s']:.2f} -> "
              f"{sh['sharded']['aggregate_decode_steps_per_s']:.2f} "
              f"({sh['aggregate_decode_steps_per_s_ratio']:.2f}x)")
        print(f"max concurrent requests at equal per-device pool bytes: "
              f"{sh['single_paged_equal_pool']['max_concurrent_requests']}"
              f" -> {sh['sharded_equal_pool']['max_concurrent_requests']} "
              f"({sh['max_concurrent_ratio']:.2f}x)")
        print(f"host syncs per 100 decode tokens: single "
              f"{sh['syncs_per_100_decode_tokens_single']:.2f}, fleet "
              f"{sh['syncs_per_100_decode_tokens_sharded']:.2f}")
    sv = res.get("server")
    if sv:
        on, off = sv["preemption_on"], sv["preemption_off"]
        hi_on = on["summary"]["classes"].get("1", {})
        hi_off = off["summary"]["classes"].get("1", {})
        print(f"\n== async front door ({sv['n_low']} low-pri + "
              f"{sv['n_high']} bursty high-pri open-loop, "
              f"{sv['pool_kv_rows']} pooled KV rows) ==")
        print(f"high-pri TTFT p99: preemption off "
              f"{1e3 * hi_off.get('ttft_p99_s', float('nan')):.1f}ms -> on "
              f"{1e3 * hi_on.get('ttft_p99_s', float('nan')):.1f}ms "
              f"({sv['high_pri_ttft_p99_improvement']:.2f}x better)")
        print(f"preemptions: {on['preemption_count']}   sheds (on): "
              f"high {sv['high_pri_sheds_on']}, low "
              f"{sv['low_pri_sheds_on']}   recompute J: "
              f"{on['preempted_recompute_j']:.1f}")
        print(f"decode J/token on/off ratio: "
              f"{sv['decode_j_per_token_ratio']:.4f}")
    ht = res.get("hetero")
    if ht and "skipped" in ht:
        print(f"\n== hetero carbon routing: SKIPPED ({ht['skipped']}) ==")
    elif ht:
        fp, cb = ht["free_pages"], ht["carbon"]
        fleet = ", ".join(f"{p}@{r}" for p, r in
                          zip(ht["shard_profiles"], ht["shard_regions"]))
        print(f"\n== hetero carbon routing ({fleet}; {ht['n_live']} "
              f"interactive + {ht['n_batch']} deferrable batch, "
              f"{ht['per_shard_pool_kv_rows']} KV rows/shard) ==")
        print(f"fleet gCO2/token: free_pages {fp['g_per_token']:.3e} -> "
              f"carbon {cb['g_per_token']:.3e} "
              f"({ht['g_per_token_improvement']:.2f}x lower)")
        print(f"requests per shard: free_pages {fp['shard_requests']} -> "
              f"carbon {cb['shard_requests']}")
        print(f"interactive TTFT p99: free_pages "
              f"{1e3 * fp['live_ttft_p99_s']:.1f}ms -> carbon "
              f"{1e3 * cb['live_ttft_p99_s']:.1f}ms "
              f"(ratio {ht['live_ttft_p99_ratio']:.2f})")
        print(f"deferral: {cb['deferred_requests']} parked, "
              f"{cb['deferred_released']} released "
              f"({cb['deferred_forced_releases']} deadline-forced), "
              f"{cb['deferred_deadline_violations']} deadline violations")
    im = res.get("impacts")
    if im and "skipped" in im:
        print(f"\n== impact ledger: SKIPPED ({im['skipped']}) ==")
    elif im:
        fl, cal = im["fleet"], im["calibration"]
        print(f"\n== impact ledger ({im['shards']}-shard fleet, "
              f"carbon routing) ==")
        print(f"fleet totals: {fl['carbon_g']:.3f} gCO2  "
              f"{fl['water_l']:.3e} L  {fl['primary_mj']:.3e} MJ  "
              f"{fl['adpe_mg']:.3e} mgSbeq  "
              f"(shard-sum rel err {im['fleet_sum_rel_err']:.1e})")
        print(f"calibration: measured {cal['measured_wh']:.4f} Wh -> "
              f"modeled {cal['modeled_wh']:.4f} Wh "
              f"({cal['energy_error_frac']:+.2%} error, "
              f"{len(cal['residuals'])} phase residuals)")
    print(f"criteria: {res['criteria']}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
