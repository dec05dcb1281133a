"""Serving engine: request queue, continuous batching, prefill/decode phase
split, per-phase carbon metering — on one device or on a fleet of S
data-parallel shards over a device mesh (one class: a single device is
the one-shard case; the fleet operations live in serving/sharded.py).

The engine runs the *model* for real (CPU here, TPU in production) while the
*energy/carbon* of each step is attributed via the calibrated analytical
model against a target hardware profile (paper §2: the measured quantity is
GPU power x time; in this container the model stands in for the meter — see
DESIGN.md hardware-adaptation table). Both phases are metered separately,
reproducing the paper's §2.3 decomposition, and the CarbonMeter carries the
region CI + embodied amortization (Eq. 2-4).

Hot path (this module's whole point — decode is the memory-bound phase
that dominates serving energy, so its per-token host overhead must be ~0):

  * one jitted, fixed-shape **fused step** does decode -> sampling -> EOS/
    budget masking -> per-slot done flags entirely on device;
    ``sync_every`` such micro-steps run inside a single ``lax.scan`` chunk,
    so the host syncs once per chunk (on the stacked token matrix) instead
    of once per token;
  * admissions are **batched**: all waiting requests that fit free slots
    prefill together through a jitted, power-of-two length-bucketed prefill
    (right padding + attention masking — prompt-length variation retraces
    at most log2(max_len) shapes), and the new caches enter the pool in a
    single scatter pass per leaf (``sampling.insert_prefill``) rather than
    per-request whole-tree copies.

Continuous batching: a fixed pool of decode slots; arriving requests are
prefilled (phase 1) and their caches inserted into free slots; each fused
chunk advances every active slot (phase 2); finished slots are freed at
chunk boundaries. This is the standard in-flight batching loop (Orca/vLLM
style) in pure JAX with a static batch shape.

Chunked prefill (``prefill_chunk`` set, requires ``paged``): the two-phase
admit-then-decode loop above serializes phases — every admission runs a
monolithic prefill while all active decode slots stall, so a long prompt
spikes time-between-tokens for everyone else. The quantum scheduler
instead splits each prompt into fixed-size chunks and packs AT MOST ONE
prefill chunk plus the fused decode scan into every scheduling quantum
(Sarathi-style): chunk i of a prompt attends over its own queries plus the
KV of chunks 0..i-1 already resident in the paged pool (the chunked-
prefill kernel chases the same scalar-prefetched block table as paged
decode), so decode TBT is bounded by one chunk's compute regardless of
prompt length. Pages materialize chunk by chunk (incremental bulk-alloc +
scatter) against the worst-case reservation made at admission.

Metering under chunking: chunking changes the SCHEDULE, not the modeled
energy — the paper's per-phase model attributes each request's prefill at
its true prompt length (batch 1, exact) when its last chunk completes, so
modeled J/token is invariant to the ``prefill_chunk`` choice (asserted in
tests/test_chunked_parity.py); decode quanta keep their per-micro-step
active-slot attribution. The wall-clock wins (TTFT, inter-token p99) are
measured, not modeled — benchmarks/engine_bench.py tracks them via the
per-token emission timestamps on ``Response.t_emit``.

Prefix sharing (``prefix_sharing``, requires ``prefill_chunk``): production
traffic is dominated by requests repeating a common prompt prefix (system
prompts, few-shot templates), and the paper's embodied-carbon model
(Eq. 2-4) charges each request for the memory the fleet must provision for
it — so materializing one private copy of the same prefix per slot is pure
embodied waste. A host-side prefix index (SHA-256 chain over page-size
token chunks -> resident physical page run) lets admission map the shared
pages of a new prompt straight into its block table with per-page refcounts
(``paged.map_shared_prefix``); chunked prefill then starts at the first
UNSHARED token, so only novel pages are computed and allocated — admission
reserves only the unshared worst case, which is what multiplies concurrent
capacity at equal pool bytes. Writes into a page with refcount > 1 (the
recomputed tail token when the whole prompt is shared) go through
copy-on-write (``paged.cow_chunk_pages``); release is decref-to-zero, and
index entries drop when their page's last holder releases (weak index: no
eviction policy needed — concurrent requests share, the pool never pins
dead prefixes). The decode and chunked-prefill kernels need NO change: the
block table already indirects every read, which is the design's proof of
leverage.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy import decode_counts, prefill_counts, step_energy
from repro.core.hardware import HardwareProfile, get_profile
from repro.models import Model
from repro.models.costing import workload_of
from repro.serving import paged, preempt, sampling, trace
from repro.serving.faults import InjectedFault
from repro.serving.request import Request, Response
from repro.serving.sharded import FleetOps, over_mesh, stack_lanes


# Module-level jitted entry points with the model as a STATIC argument:
# every ServingEngine instance sharing a Model instance reuses the same
# compiled executables (fresh engines used to rebuild jax.jit wrappers
# around per-engine partials, so each one re-paid every trace+compile —
# which dominated short-lived engines' wall time). Each program the
# engine launches has a single-device entry and its shard_map form
# (``over_mesh``, with the mesh static too); ``ServingEngine._launch``
# picks one by the shard count.


def _prefill_fn(model, params, tokens, mask, key, *, max_len, vocab,
                temperature):
    last, pcache = model.prefill(params, tokens, extras={"mask": mask},
                                 max_len=max_len)
    first = sampling.sample(last[:, :vocab], key, temperature)
    return first, pcache


def _chunk_prefill_fn(model, params, caches, tokens, mask, slots, key, *,
                      vocab, temperature, page_size, sharing=False):
    """One chunked-prefill step: allocate the chunk's pages, run the chunk
    through the model against a gathered slot view (its KV scatters into
    the pool, its queries see the slots' whole logical history), and sample
    a candidate next token (only meaningful after the LAST chunk).

    ``sharing`` additionally privatizes (copy-on-write) any page the chunk
    writes that is mapped with refcount > 1 — only possible when the slot
    adopted a shared prefix covering its whole prompt and now recomputes
    the last prompt token for first-token logits. Returns the chunk's
    last-valid-token logits (n, vocab) and the slots' block-table rows
    too, so the host can register the prompt's pages in the prefix index
    at the last chunk without an extra sync, and the model's counters
    for the chunk (``Model.prefill_chunk``; {} for a model without
    them)."""
    nv = mask.sum(axis=1).astype(jnp.int32)              # (n,) valid tokens
    t0 = caches["t"][slots]
    start_pg = (t0 + page_size - 1) // page_size
    end_pg = (t0 + nv + page_size - 1) // page_size
    caches = dict(caches)
    caches["paged"] = paged.alloc_chunk_pages(caches["paged"], slots,
                                              start_pg, end_pg)
    if sharing:
        caches = paged.cow_chunk_pages(
            caches, slots, t0, nv, page_size,
            span=tokens.shape[1] // page_size + 1)
    view = paged.gather_slot_view(caches, slots)
    last, view, counts = model.prefill_chunk(params, view, tokens, mask,
                                             counters=True)
    caches = paged.scatter_slot_view(caches, view, slots)
    logits = last[:, :vocab]
    with jax.named_scope("sample"):
        first = sampling.sample(logits, key, temperature)
    return first, logits, caches["paged"]["tbl"][slots], caches, counts


_PREFILL = jax.jit(_prefill_fn, static_argnums=(0,),
                   static_argnames=("max_len", "vocab", "temperature"))
_INSERT = jax.jit(sampling.insert_prefill)
_INSERT_PAGED = jax.jit(paged.insert_prefill_paged,
                        static_argnames=("page_size",))


def _pair(fn, fleet_name=None, runs_model=False, static=(), donate=False):
    """The single-device jit entry of program ``fn`` and of its mesh form
    (``over_mesh``), with the model (and the mesh) static."""
    lead = int(runs_model)
    return (jax.jit(fn, static_argnums=tuple(range(lead)),
                    static_argnames=static,
                    donate_argnums=(lead + 1,) if donate else ()),
            jax.jit(over_mesh(fn, fleet_name or f"_{fn.__name__}_fleet",
                              runs_model),
                    static_argnums=tuple(range(lead + 1)),
                    static_argnames=static,
                    donate_argnums=(lead + 2,) if donate else ()))


# the decode scan and the chunk program donate the cache tree (argument 2;
# argument 3 of the mesh forms, after the mesh): the pool they return is
# written in place of the one they were given, so no launch copies the
# pool and no copy queues while the host runs ahead. The engine replaces
# ``self.caches`` with each output at once, and no host path keeps a
# reference to an earlier tree.
_FUSED_STEPS, _FUSED_FLEET = _pair(
    sampling.fused_decode_steps, "_fused_steps_fleet", runs_model=True,
    static=("n_steps", "temperature", "page_size", "freeze_inactive"),
    donate=True)
_CHUNK_PREFILL, _CHUNK_FLEET = _pair(
    _chunk_prefill_fn, "_chunk_prefill_fleet", runs_model=True,
    static=("vocab", "temperature", "page_size", "sharing"), donate=True)
_BEGIN_CHUNKED, _BEGIN_FLEET = _pair(paged.begin_chunked_prefill)
_MAP_PREFIX, _MAP_PREFIX_FLEET = _pair(paged.map_shared_prefix)
_ARM, _ARM_FLEET = _pair(sampling.arm_slots)
_RELEASE, _RELEASE_FLEET = _pair(paged.release_slots)
_RELEASE_KEEP, _RELEASE_KEEP_FLEET = _pair(paged.release_slots_keep)
_DECREF, _DECREF_FLEET = _pair(paged.decref_pages)
_DISARM, _DISARM_FLEET = _pair(sampling.disarm_slots)


def pack_chunks(prefilling, chunk: int, pack: int):
    """Select the prefill work of ONE quantum from the FCFS ``prefilling``
    deque of (request, slot) pairs: up to ``pack`` requests' next chunks
    whose combined token count fits the ``chunk`` budget.

    The head always contributes its next chunk (min(remaining, chunk)
    tokens — the K=1 schedule). Requests behind it join only with their
    WHOLE remainder, and only while the budget holds, so every request's
    chunk-boundary sequence is bit-identical to the head-only schedule —
    packing regroups launches, it never re-chunks anyone (that is what
    makes greedy parity and per-request prefill metering exactly invariant
    to ``pack``). FCFS is preserved: the scan stops at the first request
    that doesn't fit, so nobody overtakes. Returns [(req, slot, pos0,
    piece), ...]; launch shapes are (k, chunk) with k <= pack, so the knob
    bounds the extra trace count.

    At most ONE ``cow_pending`` row (a whole-prompt-shared adopter about
    to recompute its tail token into a still-shared page) rides a launch:
    ``paged.cow_chunk_pages`` evaluates every row against a single
    pre-launch refcount snapshot, so two such rows adopting the SAME page
    at refcount 2 would both privatize it and free the original, while
    the engine's sequential host mirror would keep it indexed — a
    use-after-free window for the next adopter. One CoW row per launch
    keeps the mirror in exact lockstep with the device (single decref,
    snapshot refcount > 1 means the page always survives the launch).
    """
    take = []
    budget = chunk
    cow_seen = False
    for req, slot in prefilling:
        if len(take) >= pack:
            break
        rem = len(req.prompt) - req.prefill_pos
        piece = min(rem, chunk) if not take else rem
        if piece > budget:
            break
        if req.cow_pending and cow_seen:
            break                      # second CoW row waits its turn
        take.append((req, slot, req.prefill_pos,
                     req.prompt[req.prefill_pos:req.prefill_pos + piece]))
        budget -= piece
        cow_seen = cow_seen or req.cow_pending
    return take


@dataclasses.dataclass
class EngineConfig:
    """Every serving knob, single-device and fleet. Per-knob semantics are
    commented inline below; this is the interaction map.

    Capacity & batching: ``max_batch`` (decode slots, default 8) and
    ``max_len`` (512) bound the contiguous cache; ``sync_every`` (8) sets
    decode steps per host sync; ``prefill_min_bucket`` (8) the smallest
    padded-prefill launch — prefill is metered at the padded launch but
    attributed at true length (docs/METHODOLOGY.md#phase-attribution).

    Accounting: ``profile`` ("t4"), ``region`` ("QC"),
    ``lifetime_years`` (5.0), ``n_devices`` (1) feed the per-phase meter
    — Eq. 2-4 carbon plus the PR 9 water/primary-energy/ADPe ledger
    (docs/METHODOLOGY.md#the-impact-ledger); ``use_diurnal_ci`` (False)
    swaps the flat Table 2 CI for the diurnal trace at the virtual
    clock; ``carbon_budget_g_per_ktok`` (None) defers prefills above a
    carbon rate (paper SS4, ROADMAP "carbon-budget admission").

    KV memory ladder (each rung requires the previous): ``paged``
    (False) + ``page_size`` (16) + ``num_pages`` (None = equal-memory)
    enable the refcounted pool; ``prefill_chunk`` (None) requires paged
    and enables the quantum scheduler (``prefill_pack`` (1) packs chunk
    launches, metering-invariant); ``preemption`` (False) and
    ``prefix_sharing`` (False) both require ``prefill_chunk``.

    Front door (PR 6, enforced by AsyncServingServer but living here):
    ``max_queue`` (None) + ``shed_policy`` ("reject_newest") bound
    admission; ``pressure_clamp`` (None) degrades low-class budgets under
    pressure; ``max_retries`` (3) bounds per-site fault retries before
    FaultError — or a shard-down conversion on a fleet (PR 8);
    ``tenant_quota`` (None) rate-limits per tenant at submit().

    Fleet: ``shards`` (1 = one device; S > 1 shards over a mesh need
    paged + chunked), per-shard ``shard_profiles`` / ``shard_regions``
    (None = homogeneous),
    ``routing`` ("free_pages"; "carbon" = marginal-gCO2 placement, exact
    free-pages parity on a homogeneous fleet), and the deferral queue
    ``defer_below_priority`` (None) / ``defer_horizon_h`` (24) /
    ``defer_deadline_frac`` (0.5) — PR 7, ROADMAP "carbon-aware
    routing". Token streams are invariant to every accounting and
    placement knob; only grouping, attribution, and admission order may
    move.
    """
    max_batch: int = 8                 # decode slot count
    max_len: int = 512                 # cache allocation per slot
    profile: str = "t4"                # hardware the meter attributes to
    region: str = "QC"
    lifetime_years: float = 5.0
    n_devices: int = 1
    temperature: float = 0.0           # 0 = greedy
    sync_every: int = 8                # decode steps per host sync (chunk)
    prefill_min_bucket: int = 8        # smallest padded-prefill bucket
    # carbon-budget admission (paper SS4): defer new prefills while the
    # run's cumulative carbon rate exceeds the budget (g CO2eq per 1000
    # generated tokens). None = unlimited.
    carbon_budget_g_per_ktok: Optional[float] = None
    # paged KV pool: slots share num_pages pages of page_size tokens per
    # cache leaf instead of owning max_len contiguous rows each — the same
    # pool memory serves more concurrent requests (embodied carbon per
    # request drops with provisioned-but-idle HBM). num_pages None =
    # equal-memory default, max_batch * max_len / page_size.
    paged: bool = False
    page_size: int = 16
    num_pages: Optional[int] = None
    # chunked prefill (requires paged): split prompts into fixed-size
    # chunks scheduled into the same quantum as decode — at most one chunk
    # plus one fused decode scan per host sync, so decode time-between-
    # tokens is bounded by one chunk's compute instead of a whole prompt's.
    # None = monolithic admission prefill (the parity oracle). 256 is the
    # production default; tests/benches use smaller chunks.
    prefill_chunk: Optional[int] = None
    # chunk packing: up to this many prefilling requests' next chunks ride
    # ONE quantum when their combined token count fits prefill_chunk (FCFS
    # order preserved — a request is packed only behind everything ahead of
    # it). 1 = the head-only schedule; packing changes launch grouping,
    # never any request's chunk boundaries, so greedy parity and the
    # per-request metering are exactly invariant to this knob.
    prefill_pack: int = 1
    # data-parallel shard count: one shard per device of the engine's
    # mesh, each with its own slots and page pool; 1 = a single device
    shards: int = 1
    # ---- front-door robustness (async server, PR 6) ----
    # bounded admission queue: a submission arriving with the queue at
    # max_queue is SHED per shed_policy instead of queued (the request's
    # Response finishes immediately with finish_reason="shed"). None =
    # unbounded (the pre-front-door behavior).
    max_queue: Optional[int] = None
    # "reject_newest": the incoming request is the one shed.
    # "reject_lowest": the newest request of the LOWEST waiting priority
    # class is shed to make room — unless the incoming request itself is
    # at or below that class, in which case it is shed instead (a burst
    # of high-priority traffic displaces queued low-priority work, never
    # the reverse).
    shed_policy: str = "reject_newest"
    # graceful degradation: when the bounded queue is at least half full,
    # requests admitted from a priority class strictly below the highest
    # waiting class get max_new_tokens clamped to this value — shorter
    # low-class answers free slots and pages for the classes the fleet is
    # actually backed up on. None = never clamp.
    pressure_clamp: Optional[int] = None
    # priority preemption (requires prefill_chunk): a request that cannot
    # be admitted for lack of a slot or pages may evict the lowest armed
    # slot of a STRICTLY lower priority class; the victim's computed
    # prefix stays resident via the prefix-index pin and the request
    # resumes by re-admission (see serving/preempt.py for the contract).
    preemption: bool = False
    # fault recovery: a launch site (page_alloc / prefill_chunk /
    # decode_scan) that keeps failing is retried with exponential backoff
    # up to this many CONSECUTIVE failures, after which run() raises
    # FaultError with engine state consistent (serving/faults.py).
    max_retries: int = 3
    # page-level prefix sharing (requires prefill_chunk): requests whose
    # prompts repeat a page-aligned prefix already resident in the pool map
    # those pages into their block table by refcount instead of recomputing
    # and re-storing them — admission reserves only the UNSHARED worst
    # case, prefill starts at the first unshared token, and writes into
    # shared pages go through copy-on-write. Off by default: the unshared
    # paged engine is the token-for-token parity oracle.
    prefix_sharing: bool = False
    # ---- heterogeneous fleet + carbon routing (PR 7) ----
    # per-shard hardware profile / grid region names (length must equal
    # `shards`; None = every shard uses `profile` / `region`). The model runs identically everywhere —
    # heterogeneity lives in the energy/carbon attribution and in
    # placement, never in the token streams.
    shard_profiles: Optional[Sequence[str]] = None
    shard_regions: Optional[Sequence[str]] = None
    # fleet placement policy: "free_pages" (PR 5 baseline — longest
    # resident prefix, then most free pages) or "carbon" (marginal gCO2:
    # phase-specific operational J at each shard's profile and CURRENT CI
    # plus embodied rent over the pages the request would reserve,
    # core/scheduler.marginal_request_g). Eligibility (free slot, fitting
    # reservation, FCFS head-only) is IDENTICAL under both policies, and
    # on a homogeneous fleet every shard scores equal so "carbon" degrades
    # to the exact "free_pages" order — routing regroups placement, never
    # chunk boundaries or greedy token streams.
    routing: str = "free_pages"
    # meter operational carbon (and score carbon routing) at the region's
    # synthetic diurnal CI trace as the engine's virtual clock advances,
    # instead of the flat Table 2 mean.
    use_diurnal_ci: bool = False
    # temporal deferral: requests with priority STRICTLY below this are
    # held OUT of the admission queue (no slot, no reservation, exempt
    # from max_queue — they own nothing) until the CI forecaster's
    # greenest window opens at the engine's virtual clock, or until
    # defer_deadline_frac of their wall-clock deadline budget has elapsed
    # (forced release: the remaining budget is reserved for service, so
    # deferral never violates deadline_s). None = never defer.
    defer_below_priority: Optional[int] = None
    # look-ahead horizon (virtual hours) for the greenest-window search
    defer_horizon_h: int = 24
    defer_deadline_frac: float = 0.5
    # ---- per-tenant rate limits (PR 8) ----
    # token bucket per tenant, checked at submit(): maps a tenant name (or
    # "*" as the default for any named tenant) to (capacity, refill_per_s).
    # Each submission costs one bucket token; an empty bucket sheds the
    # request as a terminal finish_reason="rate_limited" Response before
    # it owns anything (no queue position, no slot, no pages). None = no
    # limits; requests with tenant=None are never limited.
    tenant_quota: Optional[Dict[str, Tuple[float, float]]] = None


class ServingEngine(FleetOps):
    """Continuous batching over ``cfg.shards`` = S data-parallel shards
    behind one queue, one per device of ``mesh`` (None = the first S
    devices); a single device is S = 1. Each shard has its own slots
    (``cfg.max_batch``), page pool (``cfg.num_pages``) and prefix index;
    every host mirror is indexed by shard (``slot_rid[s][b]``), and the
    fleet operations are ``FleetOps`` (serving/sharded.py). S > 1 needs
    the paged pool and chunked prefill: the quantum scheduler is what
    packs every shard's chunk and decode scan into single programs."""

    def __init__(self, model: Model, params, cfg: EngineConfig, mesh=None):
        S, B = cfg.shards, cfg.max_batch
        if S < 1:
            raise ValueError("shards must be >= 1")
        if S > 1 and (not cfg.paged or cfg.prefill_chunk is None):
            raise ValueError(
                "mesh-sharded serving requires the paged pool + chunked "
                "prefill (paged=True, prefill_chunk set): the quantum "
                "scheduler is what packs every shard's prefill chunk and "
                "decode scan into single fleet-wide programs")
        if cfg.shed_policy not in ("reject_newest", "reject_lowest"):
            raise ValueError(f"unknown shed_policy {cfg.shed_policy!r}")
        if cfg.max_queue is not None and cfg.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if cfg.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if cfg.routing not in ("free_pages", "carbon"):
            raise ValueError(f"unknown routing {cfg.routing!r}")
        if cfg.defer_horizon_h < 1:
            raise ValueError("defer_horizon_h must be >= 1")
        if not (0.0 < cfg.defer_deadline_frac < 1.0):
            raise ValueError("defer_deadline_frac must be in (0, 1)")
        if cfg.tenant_quota is not None:
            for name, spec in cfg.tenant_quota.items():
                try:
                    cap, refill = spec
                except (TypeError, ValueError):
                    raise ValueError(
                        f"tenant_quota[{name!r}] must be (capacity, "
                        f"refill_per_s), got {spec!r}") from None
                if cap < 1 or refill < 0:
                    raise ValueError(
                        f"tenant_quota[{name!r}]: capacity must be >= 1 "
                        "and refill_per_s >= 0")
        self.paged = cfg.paged
        if cfg.paged:
            if not model.supports_paged_decode:
                raise ValueError(
                    f"{model.cfg.name}: paged KV pool requires full-window "
                    "attention-family blocks (no ring eviction)")
            if cfg.max_len % cfg.page_size:
                raise ValueError("max_len must be a multiple of page_size")
            self.max_pages_slot = cfg.max_len // cfg.page_size
            # equal-memory default: the rows the contiguous pool would own
            self.num_pages = (B * self.max_pages_slot
                              if cfg.num_pages is None else cfg.num_pages)
            if self.num_pages < 1:
                raise ValueError("num_pages must be >= 1")
        self.chunked = cfg.prefill_chunk is not None
        if self.chunked:
            if cfg.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            if cfg.prefill_pack < 1:
                raise ValueError("prefill_pack must be >= 1")
            if not cfg.paged:
                raise ValueError("chunked prefill requires the paged KV "
                                 "pool (chunk i reads chunks 0..i-1 "
                                 "through the block table)")
            if not model.supports_chunked_prefill:
                raise ValueError(
                    f"{model.cfg.name}: chunked prefill requires all "
                    "stateful blocks to keep their KV in the paged pool "
                    "(recurrent blocks need carried-state chunk resume)")
        if cfg.preemption and not self.chunked:
            raise ValueError(
                "preemption requires chunked prefill (prefill_chunk set): "
                "a preempted request resumes through the chunked admission "
                "path, adopting its pinned prefix and recomputing only the "
                "unshared tail")
        self.sharing = cfg.prefix_sharing
        if self.sharing and not self.chunked:
            raise ValueError(
                "prefix_sharing requires chunked prefill (prefill_chunk "
                "set): sharing works by starting the chunk schedule at "
                "the first unshared token")

        self.model, self.cfg = model, cfg
        self.S, self.B = S, B
        self.workload = workload_of(model.cfg)
        self.profile: HardwareProfile = get_profile(cfg.profile)
        self._init_fleet()

        # ---- device state: one shard's tree; a fleet stacks it S-wide
        caches = model.init_cache(B, cfg.max_len)
        if cfg.paged:
            caches = paged.paginate_cache(caches, B, cfg.page_size,
                                          self.num_pages)
        state = (caches, jnp.zeros((B, 1), jnp.int32),
                 sampling.init_slot_state(B))
        (self.caches, self.cur_tokens, self.state), self.params = (
            self._place_over_mesh(state, params, mesh))

        # ---- host mirrors (bookkeeping only; the device state drives the
        # chunk), one row of B slots per shard
        def rows(v):
            return [[v] * B for _ in range(S)]

        self.queue: deque = deque()
        self.responses: Dict[int, Response] = {}
        self.slot_rid = rows(-1)                     # -1 = free
        self.slot_budget = rows(0)
        self.slot_eos: List[List[Optional[int]]] = rows(None)
        self._slot_ctx = rows(0.0)                   # context length mirror
        # which slots are ARMED for decode (device state["active"] at
        # chunk boundaries): in chunked mode a slot is occupied
        # (slot_rid >= 0) during its whole prefill but must not trigger
        # decode scans until its last chunk arms it
        self._slot_armed = rows(False)
        self._slo: List[List[Optional[float]]] = rows(None)
        # front-door mirrors: the Request occupying each slot (eviction
        # and deadline cancellation mutate it in place), its priority
        # class, and its absolute deadline
        self._slot_req: List[List[Optional[Request]]] = rows(None)
        self._slot_prio = rows(0)
        self._slot_deadline: List[List[Optional[float]]] = rows(None)
        self._req_slo: Dict[int, Optional[float]] = {}
        self._key = jax.random.PRNGKey(0)
        self._steps = 0                # micro-steps in which a slot emitted
        self.decode_chunks = 0                       # device->host syncs
        self.prefill_batches = 0
        self.prefill_chunks = 0                      # chunked-prefill launches
        # the dropless MoE layer's counts (models/moe.py), summed over
        # layers and shards: token-expert pairs computed by the held
        # experts and held experts that received a pair, per decode
        # micro-step, and pairs of the chunked prefill. Fetched with the
        # tokens the host already fetches; a chunk's counts wait on
        # device until the next fetch.
        self.counted = bool(model.cfg.moe is not None
                            and model.cfg.moe.dropless)
        self.moe_decode_pairs = 0
        self.moe_decode_experts_hit = 0
        self.moe_prefill_pairs = 0
        self._pending_counts: List[Dict[str, jax.Array]] = []
        self._quantum_counts: Dict[str, int] = {}
        # rid -> logits at the last prompt position (chunked path), kept on
        # device; None = not recorded. Set to {} to compare attention paths
        # (chip_smoke.py) without an extra host sync per request.
        self.prompt_logits: Optional[Dict[int, jax.Array]] = None
        self.peak_active = 0                         # max concurrent requests
        self._has_deadlines = False    # skip the sweep when nobody set one
        # scheduling-quantum counter (one per step()) — the fault
        # injector's clock and the backoff schedule's time base
        self._quantum = 0
        self._run_q0 = 0               # quantum at the current run()'s start
        self.faults = None             # Optional[faults.FaultInjector]
        self._backoff: Dict[str, Tuple[int, int]] = {}   # site -> (fails, retry_at)
        self.fault_retries = 0
        self.fault_retry_site: Dict[str, int] = {}       # site -> retries
        # per-tenant token buckets: tenant -> [tokens, last_refill_t]
        self._tenant_buckets: Dict[str, List[float]] = {}
        self.rate_limited = 0
        # front-door counters (stats())
        self.shed_count = 0
        self._shed_by_class: Dict[int, int] = {}
        self.preemption_count = 0
        self.deadline_cancelled = 0
        self.clamped_requests = 0
        self.preempted_recompute_j = 0.0
        self._wait_samples: Dict[int, List[float]] = {}  # class -> waits (s)
        # preemption pins: rid -> (shard, physical pages whose refcounts
        # were transferred out of the evicted slot, kept resident +
        # indexed for the resume's prefix hit); dropped after re-adoption
        # or cancel
        self._pins: Dict[int, Tuple[int, List[int]]] = {}
        if self.paged:
            # host mirror of worst-case page RESERVATIONS (>= device usage,
            # so admission by reservation means the on-device free stack
            # can never underflow mid-flight)
            self.free_pages = [self.num_pages] * S
            self.peak_pages_reserved = [0] * S
            # per-quantum sums of pages reserved and pages mapped, added at
            # the end of every step(): their ratio over an interval is the
            # share of the reservation that holds KV
            self.reserved_page_quanta = 0
            self.mapped_page_quanta = 0
            self._slot_pages = rows(0)
            self._resv: Dict[int, int] = {}
        if self.chunked:
            # per shard, FCFS queue of (request, slot) mid-prefill;
            # req.prefill_pos tracks how many prompt tokens are in the pool
            self._prefilling: List[deque] = [deque() for _ in range(S)]
        if self.sharing:
            # SHARD-LOCAL prefix index (adoption never crosses shards):
            # SHA-256 chain digest of the first (i+1) page-size token
            # chunks -> physical page holding chunk i. WEAK entries: an
            # index page is always mapped by >= 1 live slot; _page_ref
            # mirrors the device refcount for indexed pages (all sharing
            # traffic originates host-side, so the mirror is exact) and
            # the entry drops at decref-to-zero.
            self._prefix_index: List[Dict[bytes, int]] = [
                {} for _ in range(S)]
            self._page_key: List[Dict[int, bytes]] = [{} for _ in range(S)]
            self._page_ref: List[Dict[int, int]] = [{} for _ in range(S)]
            # per-slot indexed pages: adopted from the index at admission
            # (not in this slot's reservation) vs registered by this slot
            # (popped under its reservation) — release accounting differs
            self._slot_shared_in: List[Dict[int, List[int]]] = [
                {} for _ in range(S)]
            self._slot_own_idx: List[Dict[int, List[int]]] = [
                {} for _ in range(S)]
            self.prefix_hit_tokens = 0     # prompt tokens never recomputed
            self.prefix_shared_requests = 0
            self.peak_shared_mappings = 0  # extra mappings beyond 1st copy

    # ------------------------------------------------------- device programs
    def _disarm(self, pairs: List[Tuple[int, int]]) -> None:
        """Stop the device sampling from these (shard, slot) pairs."""
        rows = [[b for t, b in pairs if t == s] for s in range(self.S)]
        self.state = self._launch(_DISARM, _DISARM_FLEET, (self.state,),
                                  (stack_lanes(rows, self.B),))

    # ------------------------------------------------------------- metering
    def _meter_prefill(self, batch: int, seq: int,
                       useful_seq: Optional[float] = None, skip: int = 0,
                       phase: str = "prefill", shard: int = 0):
        """Meter one prefill launch of ``batch`` sequences padded to
        ``seq`` on ``shard``, at its profile; ``useful_seq`` (mean real
        tokens per row) attributes only the real tokens while the energy
        covers the whole padded launch. ``skip`` > 0 (prefix sharing,
        batch 1) removes the cost of the first ``skip`` tokens — their
        compute and KV writes never ran; the difference prefill(seq) -
        prefill(skip) is exactly the cost of computing the suffix with
        attention over the full prefix. ``phase`` names the meter bucket:
        a preempted request's resume prefill is charged to
        ``"recompute"`` so the prefill phase's J/token — and every
        non-preempted request's modeled energy — is invariant to the
        preemption policy."""
        counts = prefill_counts(self.workload, batch, seq,
                                useful_seq=useful_seq)
        if skip > 0:
            base = prefill_counts(self.workload, batch, skip)
            counts = dataclasses.replace(
                counts, flops=counts.flops - base.flops,
                # the suffix launch still streams the weights once
                hbm_bytes=(counts.hbm_bytes - base.hbm_bytes
                           + self.workload.params_bytes),
                kv_bytes=counts.kv_bytes - base.kv_bytes,
                compute_tokens=counts.compute_tokens - base.compute_tokens)
        return self._record(shard, phase, counts)

    def _meter_decode(self, batch: int, context: float, shard: int = 0):
        return self._record(shard, "decode",
                            decode_counts(self.workload, batch, context))

    def _record(self, s: int, phase: str, counts):
        """Price ``counts`` at shard ``s``'s profile, record them in its
        meter's ``phase`` and add their modeled time to its quantum."""
        rep = step_energy(self.shard_profile[s], counts)
        self.meters[s].record(phase, rep.tokens, rep.t_total, rep.energy_j)
        self._q_time[s] += rep.t_total
        return rep

    # ------------------------------------------------------------ lifecycle
    def submit(self, req: Request) -> None:
        """Validate, register, and enqueue (or shed) a request. Raises
        ValueError immediately for requests that are malformed rather than
        merely unschedulable — failing here beats failing deep inside
        bucketing or prefill with a shape error."""
        if not req.prompt:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens <= 0:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}")
        if self.cfg.paged and len(req.prompt) > self.cfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f"exceeds max_len={self.cfg.max_len} — the paged block "
                "table has no ring eviction, so the prompt can never be "
                "represented (shorten it or raise max_len)")
        if req.rid in self.responses:
            raise ValueError(f"request {req.rid}: duplicate rid")
        req.t_submit = time.perf_counter()
        if req.deadline_s is not None:
            self._has_deadlines = True
        self._req_slo[req.rid] = req.slo_s
        self.responses[req.rid] = Response(rid=req.rid, tokens=[],
                                           priority=req.priority)
        if self._rate_limit(req):
            # over-quota: terminal before the request owns anything — no
            # queue position, no max_queue charge, no slot, no pages
            resp = self.responses[req.rid]
            resp.finished = True
            resp.finish_reason = "rate_limited"
            self.rate_limited += 1
            return
        dbp = self.cfg.defer_below_priority
        if dbp is not None and req.priority < dbp:
            # batch-class work waits for the low-CI window; held requests
            # own nothing, so they bypass the bounded admission queue
            self._defer(req)
            return
        mq = self.cfg.max_queue
        if mq is not None and len(self.queue) >= mq:
            victim = self._pick_shed_victim(req)
            if victim is req:
                self._shed(req)
                return
            self.queue.remove(victim)
            self._shed(victim)
        self._enqueue(req)

    def _rate_limit(self, req: Request) -> bool:
        """Charge ``req``'s tenant one bucket token; True when the bucket
        is empty (the submission must be shed as rate_limited). A tenant
        without an explicit quota falls back to the ``"*"`` default;
        untracked requests (``tenant=None``) are never limited. Refill is
        continuous at ``refill_per_s`` against the host wall clock, capped
        at ``capacity`` — with refill 0 the bucket is a hard budget of
        ``capacity`` submissions, which is what the tests pin."""
        quota = self.cfg.tenant_quota
        if quota is None or req.tenant is None:
            return False
        spec = quota.get(req.tenant, quota.get("*"))
        if spec is None:
            return False
        cap, refill = float(spec[0]), float(spec[1])
        now = time.perf_counter()
        bucket = self._tenant_buckets.get(req.tenant)
        if bucket is None:
            bucket = [cap, now]
            self._tenant_buckets[req.tenant] = bucket
        bucket[0] = min(cap, bucket[0] + (now - bucket[1]) * refill)
        bucket[1] = now
        if bucket[0] < 1.0:
            return True
        bucket[0] -= 1.0
        return False

    def _enqueue(self, req: Request, resume: bool = False) -> None:
        """Priority-ordered insert, FCFS within a class (all-default
        priorities degrade to the plain FCFS append the parity oracles
        rely on). ``resume`` inserts at the FRONT of the request's class
        band: a preempted request already waited its turn once."""
        q = self.queue
        if resume:
            i = 0
            while i < len(q) and q[i].priority > req.priority:
                i += 1
        else:
            i = len(q)
            while i > 0 and q[i - 1].priority < req.priority:
                i -= 1
        q.insert(i, req)

    def _pick_shed_victim(self, incoming: Request) -> Request:
        if self.cfg.shed_policy == "reject_newest":
            return incoming
        # reject_lowest: shed the NEWEST request of the LOWEST waiting
        # class — unless the incoming request is at or below that class
        lowest = min(r.priority for r in self.queue)
        if incoming.priority <= lowest:
            return incoming
        for r in reversed(self.queue):
            if r.priority == lowest:
                return r
        return incoming                # unreachable: lowest came from queue

    def _shed(self, req: Request) -> None:
        resp = self.responses[req.rid]
        resp.finished = True
        resp.finish_reason = "shed"
        self.shed_count += 1
        self._shed_by_class[req.priority] = (
            self._shed_by_class.get(req.priority, 0) + 1)
        self._drop_pin(req.rid)        # a shed resumee abandons its pin

    def _drop_pin(self, rid: int) -> None:
        """Release a preemption pin: decref the pinned pages on device and
        mirror the last-holder-credits-once flow on the host (pins only
        exist with prefix sharing — the pin IS an index residency)."""
        pin = self._pins.pop(rid, None)
        if pin is None:
            return
        s, pins = pin
        if self.health.is_dead(s):
            # defensive: declaration already invalidated dead-shard pins;
            # never send a decref to a dead pool
            return
        pages = np.full((self.S, self.max_pages_slot), -1, np.int32)
        pages[s, :len(pins)] = pins
        self._launch_alloc(_DECREF, _DECREF_FLEET, pages)
        for p in pins:
            self._page_ref[s][p] -= 1
            if self._page_ref[s][p] <= 0:
                self._drop_index_page(s, p)
                self.free_pages[s] += 1   # the pin was the last holder

    def free_slots(self, s: int) -> List[int]:
        return [i for i, r in enumerate(self.slot_rid[s]) if r < 0]

    @property
    def active(self) -> int:
        return sum(1 for row in self.slot_rid for r in row if r >= 0)

    @property
    def decoding(self) -> int:
        """Slots armed for decode (excludes slots still mid-prefill)."""
        return sum(sum(row) for row in self._slot_armed)

    def _over_budget(self) -> bool:
        b = self.cfg.carbon_budget_g_per_ktok
        if b is None:
            return False
        t = self.meter.totals
        if t.tokens < 1:
            return False
        return (t.total_g / t.tokens * 1000.0) > b

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # ------------------------------------------------------- prefix sharing
    def _prompt_page_keys(self, req: Request) -> List[bytes]:
        """Chain digest per full page-size chunk of the prompt: key[i]
        commits to tokens [0, (i+1)*page_size), so an index hit at i means
        the WHOLE prefix through page i matches — not just that one chunk.
        Cached on the request (waiting requests re-match every admission
        pass as the index fills)."""
        if req.prefix_keys is None:
            ps = self.cfg.page_size
            keys: List[bytes] = []
            h = hashlib.sha256()
            for i in range(len(req.prompt) // ps):
                h.update(np.asarray(req.prompt[i * ps:(i + 1) * ps],
                                    np.int64).tobytes())
                keys.append(h.digest())
            req.prefix_keys = keys
        return req.prefix_keys

    def _match_prefix(self, req: Request, s: int) -> Tuple[int, List[int]]:
        """Longest prefix of the prompt resident in shard ``s``: (#shared
        whole pages, their physical ids, in logical order)."""
        phys: List[int] = []
        for k in self._prompt_page_keys(req):
            p = self._prefix_index[s].get(k)
            if p is None:
                break
            phys.append(p)
        return len(phys), phys

    def _drop_index_page(self, s: int, p: int) -> None:
        key = self._page_key[s].pop(p, None)
        if key is not None:
            self._prefix_index[s].pop(key, None)
        self._page_ref[s].pop(p, None)

    def _reject(self, req: Request) -> None:
        """Fail a request that can never fit the pool (prompt alone exceeds
        total capacity) without admitting it."""
        resp = self.responses[req.rid]
        resp.finished = True
        resp.rejected = True
        resp.finish_reason = "rejected"

    def _release_slots(self, pairs: List[Tuple[int, int]]) -> None:
        """Return finished (shard, slot) pairs' pages to their pools: one
        release program for every shard (actual mapped pages, decref-to-
        zero) + the host reservation mirror (``_return_pages``)."""
        if not self.paged or not pairs:
            return
        with trace.span("release"):
            mask = np.zeros((self.S, self.B), bool)
            for s, b in pairs:
                mask[s, b] = True
            self._launch_alloc(_RELEASE, _RELEASE_FLEET, mask)
            for s, b in pairs:
                self._return_pages(s, b)

    def _return_pages(self, s: int, b: int, keep=()) -> None:
        """Host mirror of slot (s, b)'s page release. With prefix sharing
        the per-slot flows are asymmetric but the shard's mirror stays
        exact: a page this slot POPPED (reserved) that others still
        reference is NOT freed (give back one page fewer), and a page
        this slot merely adopted whose refcount just hit zero IS freed
        (give back one page more) — every physical page is charged once
        by its popper and credited once by its last holder. ``keep``
        pages (a preemption pin) stay resident: their references transfer
        to the pin, as ``paged.release_slots_keep`` does on device."""
        ret = self._slot_pages[s][b]
        if self.sharing:
            for p in self._slot_own_idx[s].pop(b, []):
                if p in keep:
                    ret -= 1           # stays resident under the pin
                    continue
                self._page_ref[s][p] -= 1
                if self._page_ref[s][p] <= 0:
                    self._drop_index_page(s, p)
                else:
                    ret -= 1           # survives under someone else's map
            for p in self._slot_shared_in[s].pop(b, []):
                if p in keep:
                    continue           # adopted ref transferred to the pin
                self._page_ref[s][p] -= 1
                if self._page_ref[s][p] <= 0:
                    self._drop_index_page(s, p)
                    ret += 1           # last holder frees the original
        self.free_pages[s] += ret
        self._slot_pages[s][b] = 0

    # ---------------------------------------------------------------- faults
    # The three injectable launch sites (serving/faults.py) all follow the
    # same discipline: the injection point sits BEFORE any device mutation,
    # so a fault means the launch never happened — the site's work stays
    # queued (admission re-queues its takes explicitly; prefill/decode work
    # was never dequeued) and is retried after an exponential backoff of
    # 2**fails quanta. max_retries consecutive failures raise FaultError
    # out of run() with every reservation returned — or, on a fleet whose
    # suspect shards leave a survivor, become shard loss.

    def _inject(self, site: str) -> None:
        if self.faults is not None:
            self.faults.check(site, self._quantum, self._run_q0)

    def _site_ready(self, site: str) -> bool:
        return self._backoff.get(site, (0, 0))[1] <= self._quantum

    def _faults_pending(self) -> bool:
        return bool(self._backoff)

    # ------------------------------------------------------------ preemption
    def _try_preempt(self, req: Request) -> bool:
        """Evict ONE armed slot of a strictly lower priority class so
        ``req`` (the queue head) can be admitted; True if a slot was
        freed. The victim is the best over shards (lowest class, least
        progress, then lowest shard); its pages return to its own shard's
        pool. Admission re-evaluates the head afterwards — repeated calls
        evict at most one victim per shortfall, lowest class first."""
        if not self.cfg.preemption:
            return False
        best = None
        for s in range(self.S):
            progress = [
                (self._slot_req[s][b].max_new_tokens
                 - self.slot_budget[s][b])
                if self._slot_req[s][b] is not None else 0
                for b in range(self.B)]
            b = preempt.pick_victim(self._slot_armed[s],
                                    self._slot_prio[s], progress,
                                    req.priority)
            if b is None:
                continue
            key = (self._slot_prio[s][b], progress[b], -s)
            if best is None or key < best[0]:
                best = (key, s, b)
        if best is None:
            return False
        self._evict_slot(best[1], best[2])
        return True

    def _evict_slot(self, s: int, slot: int) -> None:
        """Evict the ARMED slot (s, slot) mid-decode (see
        serving/preempt.py for the full contract): disarm its device
        state, release its pages except the leading indexed run
        (refcounts transfer to a host pin, keeping the computed prefix
        resident and adoptable), fold the tokens generated so far into
        the request's prompt, and requeue it at the front of its priority
        band. Resume is ordinary re-admission: the folded prompt's
        leading pages hit the (pinned) prefix index, so only the unshared
        tail is recomputed — metered as 'recompute'."""
        req = self._slot_req[s][slot]
        resp = self.responses[req.rid]
        remaining = self.slot_budget[s][slot]
        preempt.fold_for_resume(req, resp, remaining)
        pinned: List[int] = []
        if self.sharing:
            held = set(self._slot_shared_in[s].get(slot, []))
            held |= set(self._slot_own_idx[s].get(slot, []))
            pinned = preempt.pinned_run(self._prompt_page_keys(req),
                                        self._prefix_index[s], held)
        mask = np.zeros((self.S, self.B), bool)
        mask[s, slot] = True
        n_keep = np.zeros((self.S, self.B), np.int32)
        n_keep[s, slot] = len(pinned)
        self._launch_alloc(_RELEASE_KEEP, _RELEASE_KEEP_FLEET, mask, n_keep)
        self._disarm([(s, slot)])
        self._return_pages(s, slot, keep=set(pinned))
        if pinned:
            self._pins[req.rid] = (s, pinned)
        self._clear_slot(s, slot)
        self._req_shard.pop(req.rid, None)
        self.preemption_count += 1
        self._enqueue(req, resume=True)

    def _occupy(self, s: int, slot: int, req: Request, budget: int,
                ctx: float, armed: bool) -> None:
        """Point slot (s, slot)'s host mirrors at ``req``."""
        self.slot_rid[s][slot] = req.rid
        self.slot_budget[s][slot] = budget
        self.slot_eos[s][slot] = req.eos_id
        self._slot_ctx[s][slot] = ctx
        self._slot_armed[s][slot] = armed
        self._slo[s][slot] = req.slo_s
        self._slot_req[s][slot] = req
        self._slot_prio[s][slot] = req.priority
        self._slot_deadline[s][slot] = req.deadline_s

    def _clear_slot(self, s: int, slot: int) -> None:
        self.slot_rid[s][slot] = -1
        self.slot_budget[s][slot] = 0
        self.slot_eos[s][slot] = None
        self._slot_ctx[s][slot] = 0.0
        self._slot_armed[s][slot] = False
        self._slo[s][slot] = None
        self._slot_req[s][slot] = None
        self._slot_prio[s][slot] = 0
        self._slot_deadline[s][slot] = None

    # ------------------------------------------------------------- deadlines
    def _cancel(self, rid: int, reason: str) -> None:
        resp = self.responses[rid]
        resp.finished = True
        resp.finish_reason = reason
        if reason == "deadline":
            self.deadline_cancelled += 1
        self._drop_pin(rid)

    def _sweep_deadlines(self) -> None:
        """Cancel every request whose deadline expired, wherever it is:
        queued (just dropped), mid-chunked-prefill (slot + reservation
        released), or armed mid-decode (disarmed, pages reclaimed in this
        same quantum). Runs at the top of each quantum, so a cancelled
        slot's pages are reusable by this quantum's own admission."""
        now = time.perf_counter()

        def expired(r: Request) -> bool:
            return (r.deadline_s is not None
                    and now - r.t_submit > r.deadline_s)

        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._cancel(req.rid, "deadline")
        if self.chunked:
            for s in range(self.S):
                for req, slot in [p for p in self._prefilling[s]
                                  if expired(p[0])]:
                    self._prefilling[s].remove((req, slot))
                    self._clear_slot(s, slot)
                    self._release_slots([(s, slot)])
                    self._cancel(req.rid, "deadline")
        doomed = [(s, b) for s in range(self.S) for b in range(self.B)
                  if self._slot_armed[s][b]
                  and self._slot_req[s][b] is not None
                  and expired(self._slot_req[s][b])]
        for s, b in doomed:
            self._disarm([(s, b)])
            rid = self.slot_rid[s][b]
            self._clear_slot(s, b)
            self._release_slots([(s, b)])
            self._cancel(rid, "deadline")

    # ------------------------------------------------------------ admission
    def _apply_pressure_clamp(self, req: Request) -> None:
        """Graceful degradation under queue pressure: once the admission
        queue is at least half full, clamp the decode budget of requests
        BELOW the best waiting class to ``pressure_clamp`` tokens. Everyone
        below the top class gets shorter answers so more requests get
        served at all — applied at admission (not submit) so a queue that
        drains before the request's turn leaves it unclamped."""
        clamp = self.cfg.pressure_clamp
        if (clamp is None or self.cfg.max_queue is None
                or 2 * len(self.queue) < self.cfg.max_queue):
            return
        top = max(r.priority for r in self.queue)
        if req.priority < top and req.max_new_tokens > clamp:
            req.max_new_tokens = clamp
            self.clamped_requests += 1

    def _stamp_admit(self, req: Request) -> None:
        """Record queue wait on FIRST admission only — a preempted
        request's wait is its original submit->admit interval; re-admission
        latency shows up in its end-to-end latency, not its queue wait."""
        if req.t_admit is not None:
            return
        req.t_admit = time.perf_counter()
        wait = req.t_admit - req.t_submit
        self.responses[req.rid].queue_wait_s = wait
        self._wait_samples.setdefault(req.priority, []).append(wait)

    def _admit(self) -> int:
        """Admit waiting requests into free slots (phase 1), FCFS from the
        queue head onto the shard placement picks (``_place``).

        Paged mode admits by worst-case page reservation (prompt + full
        decode budget, so alloc-on-write can never underflow the device
        stack): a request that doesn't fit the REMAINING pool of any shard
        keeps waiting; one whose prompt alone can never fit a shard's
        TOTAL pool is rejected outright instead of admitted-and-failed
        mid-prefill. Returns the number of requests admitted; the
        ``repro.admit`` span carries their rids.

        With ``preemption`` on, a shortfall (no free slot, or not enough
        free pages) for the queue head triggers eviction of ONE armed
        lower-priority slot per retry instead of waiting — highest-value
        work overtakes by reclaiming, never by starving FCFS within a
        class. The whole reservation pass sits behind the ``page_alloc``
        fault site: an injected fault returns every reservation and puts
        the takes back at the queue head, so a failed admission launch is
        indistinguishable from one that never ran."""
        with trace.span("admit") as sp:
            take = self._admit_requests()
            trace.tag(sp, "rids", (r.rid for r in take))
        return len(take)

    def _admit_requests(self) -> List[Request]:
        """The admission pass of ``_admit``; returns the requests taken."""
        if self._over_budget() and self.active > 0:
            return []                  # defer admissions; drain active work
        if self.queue and self.paged and not self._site_ready("page_alloc"):
            return []                  # backing off a faulted reservation
        free = [self.free_slots(s) for s in range(self.S)]
        taken = [0] * self.S
        take: List[Tuple[Request, int, Tuple[int, List[int], int]]] = []
        while self.queue:
            req = self.queue[0]
            open_slots = [len(f) - t for f, t in zip(free, taken)]
            if not any(open_slots[s] > 0 for s in self._placeable()):
                if not self._try_preempt(req):
                    break              # no slot and nobody to evict
                free = [self.free_slots(s) for s in range(self.S)]
                continue
            self._apply_pressure_clamp(req)
            s, share = 0, (0, [], 0)
            if self.paged:
                n_total = paged.pages_needed(
                    len(req.prompt) + max(req.max_new_tokens - 1, 0),
                    self.cfg.page_size)
                # pages have no ring eviction: a request whose prompt +
                # decode budget exceeds the block table (max_len) or a
                # shard's whole pool can NEVER be represented — reject it
                # instead of admitting into silent context loss (the
                # contiguous engine ring-wraps such requests; paged must
                # refuse them). The unshared worst case decides: shared
                # pages are a transient property of current residents,
                # not capacity.
                if n_total > self.max_pages_slot or n_total > self.num_pages:
                    self.queue.popleft()
                    self._reject(req)
                    continue
                placed = self._place(req, open_slots)
                if placed is None:
                    if self._try_preempt(req):
                        free = [self.free_slots(s) for s in range(self.S)]
                        continue       # evicted pages now in the pool
                    break              # keep waiting (FCFS, no overtaking)
                s, resv, share = placed
                self.free_pages[s] -= resv
                self._resv[req.rid] = resv
            taken[s] += 1
            take.append((self.queue.popleft(), s, share))
        if take and self.paged:
            try:
                self._inject("page_alloc")
            except InjectedFault:
                # the reservation launch "failed": undo it exactly — give
                # every page back and restore the takes at the queue head
                # in order. Nothing device-side happened yet by design.
                for req, s, _ in reversed(take):
                    self.free_pages[s] += self._resv.pop(req.rid)
                    self.queue.appendleft(req)
                self._site_failed("page_alloc")
                return []
            self._site_ok("page_alloc")
        if self.paged:
            for s in range(self.S):
                self.peak_pages_reserved[s] = max(
                    self.peak_pages_reserved[s],
                    self.num_pages - self.free_pages[s])
        if not take:
            return []
        for req, s, _ in take:
            self._req_shard[req.rid] = s
            self.shard_requests[s] += 1
        if not self.chunked:
            # monolithic prefill at admission (one shard): padded power-
            # of-two buckets when the model masks pad tokens exactly;
            # exact-length groups otherwise (rwkv/enc-dec). Buckets are
            # clamped to max_len — past that the cache ring must keep the
            # LAST W real tokens, so padding would evict real tokens in
            # favor of pads; those prompts prefill at exact length.
            padded = self.model.supports_padded_prefill
            groups: Dict[int, List[Request]] = {}
            for req, _, _ in take:
                L = len(req.prompt)
                if padded and L <= self.cfg.max_len:
                    b = min(sampling.prefill_bucket(
                        L, self.cfg.prefill_min_bucket), self.cfg.max_len)
                else:
                    b = L
                groups.setdefault(b, []).append(req)
            slot_iter = iter(free[0])
            for bucket, reqs in groups.items():
                self._prefill_group(bucket, reqs,
                                    [next(slot_iter) for _ in reqs])
            return [req for req, _, _ in take]
        slot_iter = [iter(f) for f in free]
        claims = [(req, s, next(slot_iter[s]), share)
                  for req, s, share in take]
        # quantum scheduler: admission only claims the slot + pages and
        # queues the request for chunk-at-a-time prefill — no prefill
        # launch here, so decode slots are never stalled by admission
        for req, s, slot, _ in claims:
            self._occupy(s, slot, req, 0, 0.0, False)  # armed after last chunk
            self._slot_pages[s][slot] = self._resv.pop(req.rid)
            self._stamp_admit(req)
            req.prefill_pos = 0
            self._prefilling[s].append((req, slot))
        # one slot-reset program for every shard, each shard's slot list
        # padded with the sentinel id B (out-of-range scatters drop ->
        # idle lanes run the same program and write nothing)
        lanes = [[slot for _, t, slot, _ in claims if t == s]
                 for s in range(self.S)]
        self.caches = self._launch(_BEGIN_CHUNKED, _BEGIN_FLEET,
                                   (self.caches,),
                                   (stack_lanes(lanes, self.B),))
        if self.sharing:
            for req, s, slot, share in claims:
                self._adopt_prefix(req, s, slot, *share)
                # the resumed request has re-adopted its pinned prefix
                # through the ordinary index path (increfs above) — the
                # pin's own references can go now, adopt-then-release so
                # the pages never transit refcount zero
                if req.rid in self._pins:
                    self._drop_pin(req.rid)
        return [req for req, _, _ in take]

    def _prefill_group(self, bucket: int, reqs: List[Request],
                       slots: List[int]) -> None:
        n = len(reqs)
        n_pad = 1                      # pow2 batch dim: prefill trace count
        while n_pad < n:               # is O(log2(max_batch) * log2(max_len))
            n_pad *= 2
        tokens = np.zeros((n_pad, bucket), np.int32)
        mask = np.zeros((n_pad, bucket), np.int32)
        for i, req in enumerate(reqs):
            L = len(req.prompt)
            tokens[i, :L] = req.prompt
            mask[i, :L] = 1
        # pad rows replicate request 0 (discarded at insertion) rather than
        # run degenerate zero-length sequences through the model
        tokens[n:] = tokens[0]
        mask[n:] = mask[0]
        first, pcache = _PREFILL(
            self.model, self.params, jnp.asarray(tokens), jnp.asarray(mask),
            self._next_key(), max_len=self.cfg.max_len,
            vocab=self.model.cfg.vocab, temperature=self.cfg.temperature)
        budgets = jnp.asarray([r.max_new_tokens - 1 for r in reqs], jnp.int32)
        eos_ids = jnp.asarray([-1 if r.eos_id is None else r.eos_id
                               for r in reqs], jnp.int32)
        slots_a = jnp.asarray(slots, jnp.int32)
        if self.paged:
            self.caches, self.cur_tokens, self.state = _INSERT_PAGED(
                self.caches, pcache, slots_a, self.cur_tokens, first,
                self.state, budgets, eos_ids,
                page_size=self.cfg.page_size)
        else:
            self.caches, self.cur_tokens, self.state = _INSERT(
                self.caches, pcache, slots_a, self.cur_tokens, first,
                self.state, budgets, eos_ids)
        first_h = np.asarray(jax.device_get(first))
        self.prefill_batches += 1
        # meter the REAL padded launch once — the device ran ONE
        # (n_pad, bucket) batch, not n exact-length singles. Real tokens
        # are attributed (useful_seq), so prefill J/token honestly carries
        # the padding + batch-shape waste; per-request energy shares go by
        # true prompt length, while each request's modeled prefill TIME is
        # the whole launch it waited on (that's its TTFT contribution).
        tot_real = sum(len(r.prompt) for r in reqs)
        rep = self._meter_prefill(n_pad, bucket, useful_seq=tot_real / n_pad)
        now = time.perf_counter()
        released: List[Tuple[int, int]] = []
        for i, (req, slot) in enumerate(zip(reqs, slots)):
            self._stamp_admit(req)
            resp = self.responses[req.rid]
            resp.prefill_s += rep.t_total
            resp.energy_j += rep.energy_j * (len(req.prompt) / tot_real)
            resp.tokens.append(int(first_h[i]))
            resp.t_emit.append(now)
            if self.paged:
                self._slot_pages[0][slot] = self._resv.pop(req.rid)
            if req.max_new_tokens <= 1:
                resp.finished = True   # prefill token was the whole budget
                resp.finish_reason = "length"
                released.append((0, slot))   # return its prompt pages now
                continue               # slot stays free (device side agrees)
            self._occupy(0, slot, req, req.max_new_tokens - 1,
                         float(len(req.prompt)), True)
        self._release_slots(released)

    def _adopt_prefix(self, req: Request, s: int, slot: int, n_pg: int,
                      phys: List[int], first_tok: int) -> None:
        """Map a matched prefix run into the freshly claimed slot on shard
        ``s`` (device increfs + logical-history rows; every other lane
        sees the sentinel slot id and writes nothing) and start its chunk
        schedule at the first unshared token."""
        self._slot_shared_in[s][slot] = []
        self._slot_own_idx[s][slot] = []
        if n_pg == 0:
            return
        slot_a = np.full((self.S,), self.B, np.int32)
        slot_a[s] = slot
        pages = np.full((self.S, self.max_pages_slot), -1, np.int32)
        pages[s, :n_pg] = phys
        n_shared = np.zeros((self.S,), np.int32)
        n_shared[s] = n_pg * self.cfg.page_size
        start = np.zeros((self.S,), np.int32)
        start[s] = first_tok
        self.caches = self._launch(_MAP_PREFIX, _MAP_PREFIX_FLEET,
                                   (self.caches,),
                                   (slot_a, pages, n_shared, start))
        req.prefill_pos = first_tok
        req.shared_prefix_tokens = first_tok
        # whole prompt shared: the first chunk recomputes the tail token
        # into a still-shared page and must copy-on-write — flag it so the
        # packer never puts two such rows in one launch
        req.cow_pending = first_tok < n_pg * self.cfg.page_size
        for p in phys:
            self._page_ref[s][p] += 1
        self._slot_shared_in[s][slot] = list(phys)
        self.prefix_hit_tokens += first_tok
        self.prefix_shared_requests += 1
        cur = sum(len(v) for d in self._slot_shared_in for v in d.values())
        self.peak_shared_mappings = max(self.peak_shared_mappings, cur)

    def _register_prefix(self, req: Request, s: int, slot: int,
                         row: np.ndarray) -> None:
        """After the LAST chunk, publish the prompt's whole pages into
        shard ``s``'s prefix index (``row`` is the slot's block-table row,
        fetched with the first-token sync — no extra device round-trip).
        First writer wins: a page already indexed under the same key (this
        slot adopted it, or a concurrent twin prefilled the same novel
        prefix) is not re-registered; the slot's private duplicate stays
        untracked."""
        own = self._slot_own_idx[s].setdefault(slot, [])
        for i, key in enumerate(self._prompt_page_keys(req)):
            p = int(row[i])
            if key not in self._prefix_index[s]:
                self._prefix_index[s][key] = p
                self._page_key[s][p] = key
                self._page_ref[s][p] = self._page_ref[s].get(p, 0) + 1
                own.append(p)

    # ------------------------------------------------------ chunked prefill
    def _prefill_quantum(self) -> int:
        """Run AT MOST ONE prefill launch — the prefill half of a
        scheduling quantum, for every shard at once. On each shard the
        launch carries the FCFS head's next chunk plus (``prefill_pack``
        > 1) the whole remainders of requests behind it while the combined
        token count fits ``prefill_chunk``, so decode slots stall for one
        chunk budget's compute regardless of how many small prompts are
        queued; shards with nothing to prefill run sentinel lanes.
        Returns the number of launches (0 or 1)."""
        if not any(self._prefilling):
            return 0
        if not self._site_ready("prefill_chunk"):
            return 0                   # backing off a faulted chunk launch
        C = self.cfg.prefill_chunk
        with trace.span("prefill.prepare"):
            packs = [pack_chunks(q, C, self.cfg.prefill_pack)
                     for q in self._prefilling]
            try:
                self._inject("prefill_chunk")
            except InjectedFault:
                # the launch never ran: the packed requests are still at
                # the head of each shard's ``_prefilling`` with their
                # prefill_pos untouched — the SAME chunks relaunch after
                # backoff, nothing is dropped
                self._site_failed("prefill_chunk")
                return 0
            self._site_ok("prefill_chunk")
            n = max(len(pk) for pk in packs)
            tokens = np.zeros((self.S, n, C), np.int32)
            mask = np.zeros((self.S, n, C), np.int32)
            slots = np.full((self.S, n), self.B, np.int32)
            for s, pk in enumerate(packs):
                for i, (_, slot, _, piece) in enumerate(pk):
                    tokens[s, i, :len(piece)] = piece
                    mask[s, i, :len(piece)] = 1
                    slots[s, i] = slot
        with trace.span("prefill.launch") as sp:
            trace.tag(sp, "rids", (req.rid for pk in packs
                                   for req, _, _, _ in pk))
            first, logits, rows, self.caches, counts = self._launch(
                _CHUNK_PREFILL, _CHUNK_FLEET, (self.caches,),
                (tokens, mask, slots), runs_model=True,
                vocab=self.model.cfg.vocab, temperature=self.cfg.temperature,
                page_size=self.cfg.page_size, sharing=self.sharing)
        self.prefill_chunks += 1
        if counts:
            self._pending_counts.append(counts)
        finished: List[Tuple[int, int]] = []   # (shard, row)
        for s, pk in enumerate(packs):
            done = 0
            for i, (req, slot, pos0, piece) in enumerate(pk):
                req.prefill_pos += len(piece)
                if self.sharing and piece:
                    # mirror the device's copy-on-write: if this chunk
                    # wrote into an adopted page still shared (refcount
                    # > 1), the device swapped in a private copy — the
                    # slot no longer maps the indexed original. Sole-owner
                    # pages are written in place and stay mapped (and
                    # indexed; the rewrite recomputes identical rows, so
                    # the entry remains valid).
                    shared = self._slot_shared_in[s].get(slot) or []
                    lp = pos0 // self.cfg.page_size
                    if lp < len(shared) and self._page_ref[s][shared[lp]] > 1:
                        self._page_ref[s][shared[lp]] -= 1
                        self._slot_shared_in[s][slot] = shared[:lp]
                    req.cow_pending = False    # its CoW (if any) just ran
                if req.prefill_pos >= len(req.prompt):
                    finished.append((s, i))
                    done += 1
            # by construction only the head can be mid-prompt after a
            # launch (packed tails always carried their whole remainder),
            # so finished rows are exactly the first ``done`` entries
            assert done in (0, len(pk)), "packed tail finished before head"
            for _ in range(done):
                self._prefilling[s].popleft()
        if not finished:
            return 1                   # intermediate chunk: no host sync
        # last chunks: the sampled tokens are the requests' first emissions
        # — ONE host sync for every request finishing in this launch
        with trace.span("prefill.fetch"):
            first_h, rows_h, pending = self._fetch(
                (first, rows, self._pending_counts))
        self._add_prefill_counts(pending)
        self.prefill_batches += 1      # one first-token host sync
        with trace.span("prefill.finish") as sp:
            trace.tag(sp, "rids", (packs[s][i][0].rid for s, i in finished))
            self._finish_prefill(packs, finished, first_h, rows_h, logits)
        return 1

    def _finish_prefill(self, packs, finished: List[Tuple[int, int]],
                        first_h: np.ndarray, rows_h: np.ndarray,
                        logits: jax.Array) -> None:
        """Requests whose last chunk just ran: register their prompt pages
        in their shard's prefix index, meter their prefill, record their
        first token, and arm their slots for decode with one launch (or
        release a slot whose budget the first token spent)."""
        now = time.perf_counter()
        released: List[Tuple[int, int]] = []
        arm: List[Tuple[int, int, int, int, int]] = []  # s, slot, tok, budget, eos
        for s, i in finished:
            req, slot, _, _ = packs[s][i]
            if self.sharing:
                self._register_prefix(req, s, slot, rows_h[s, i])
            if self.prompt_logits is not None:
                self.prompt_logits[req.rid] = self._lane_of(logits, s)[i]
            # chunking changes the schedule, not the modeled energy:
            # attribute the request's prefill at its true prompt length
            # exactly once, so modeled J/token is invariant to the
            # prefill_chunk (and prefill_pack) choice. Prefix sharing DOES
            # change the modeled energy — the shared tokens' compute
            # genuinely never ran — so their cost is subtracted while the
            # request still accounts its full prompt as served tokens
            # (operational J/prompt-token falls with every cache hit).
            rep = self._meter_prefill(
                1, len(req.prompt), skip=req.shared_prefix_tokens,
                phase="recompute" if req.preemptions else "prefill",
                shard=s)
            resp = self.responses[req.rid]
            resp.prefill_s += rep.t_total
            resp.energy_j += rep.energy_j
            if req.preemptions:
                resp.recompute_j += rep.energy_j
                self.preempted_recompute_j += rep.energy_j
            tok = int(first_h[s, i])
            resp.tokens.append(tok)
            resp.t_emit.append(now)
            budget = req.max_new_tokens - 1
            # a FRESH request's prefill-sampled token is never EOS-checked
            # (seed semantics: EOS only terminates decode); but a RESUMED
            # request's first token is logically a mid-decode emission of
            # the original request, so it must honor EOS for parity with
            # the unpreempted oracle
            eos_hit = (req.preemptions > 0 and req.eos_id is not None
                       and tok == req.eos_id)
            if budget <= 0 or eos_hit:
                resp.finished = True   # prefill token was the whole budget
                resp.finish_reason = "eos" if eos_hit else "length"
                self._clear_slot(s, slot)
                released.append((s, slot))
                continue
            arm.append((s, slot, tok, budget,
                        -1 if req.eos_id is None else req.eos_id))
            self.slot_budget[s][slot] = budget
            self._slot_ctx[s][slot] = float(len(req.prompt))
            self._slot_armed[s][slot] = True
        if arm:
            # one batched arm for every request finishing in this launch
            # (first tokens come from the host fetch above — no extra sync)
            def lanes(col, fill):
                return stack_lanes([[a[col] for a in arm if a[0] == s]
                                     for s in range(self.S)], fill)
            self.cur_tokens, self.state = self._launch(
                _ARM, _ARM_FLEET, (self.cur_tokens, self.state),
                (lanes(1, self.B), lanes(2, 0), lanes(3, 0), lanes(4, -1)))
        self._release_slots(released)

    # --------------------------------------------------------------- decode
    def _decode_chunk(self, max_steps: int) -> bool:
        """One fused on-device chunk of up to ``sync_every`` decode steps
        for every armed slot on every shard (phase 2); a single host sync
        at the end, on the (S, n, B) token/emission matrices. Slots still
        mid-chunked-prefill ride along inert (device ``active`` false,
        cursors frozen by the fused step). Returns whether a chunk
        actually launched (False while the ``decode_scan`` site backs off
        a fault — armed slots keep their state and relaunch later)."""
        if not self._site_ready("decode_scan"):
            return False               # backing off a faulted scan launch
        try:
            self._inject("decode_scan")
        except InjectedFault:
            # nothing launched: cur_tokens/state/caches are exactly the
            # pre-chunk values, so the relaunch after backoff resamples
            # the identical chunk — no token is lost or double-emitted
            self._site_failed("decode_scan")
            return False
        self._site_ok("decode_scan")
        budgets = [self.slot_budget[s][b]
                   for s in range(self.S) for b in range(self.B)
                   if self._slot_armed[s][b]]
        n = min(self.cfg.sync_every, max(max(budgets), 1),
                max(max_steps - self._steps, 1))
        with trace.span("decode.launch"):
            (self.caches, self.cur_tokens, self.state, tok_mat,
             emit_mat, counts) = self._launch(
                _FUSED_STEPS, _FUSED_FLEET,
                (self.caches, self.cur_tokens, self.state), runs_model=True,
                n_steps=n, temperature=self.cfg.temperature,
                page_size=self.cfg.page_size if self.paged else 0,
                freeze_inactive=self.chunked)
        with trace.span("decode.fetch"):
            tok_h, emit_h, counts_h, pending = self._fetch(
                (tok_mat, emit_mat, counts, self._pending_counts))
        self._add_prefill_counts(pending)
        if counts_h:
            pairs = int(counts_h["moe_pairs"].sum())
            hit = int(counts_h["moe_experts_hit"].sum())
            self.moe_decode_pairs += pairs
            self.moe_decode_experts_hit += hit
            self._quantum_counts = {"moe_decode_pairs": pairs,
                                    "moe_decode_experts_hit": hit}
        self.decode_chunks += 1
        self.peak_active = max(self.peak_active, self.active)
        with trace.span("decode.emit"):
            released = self._emit_decoded(n, tok_h, emit_h)
        # page reclamation at the chunk boundary (finished slots coasted on
        # the trash page since their done flag rose mid-chunk)
        self._release_slots(released)
        return True

    def _add_prefill_counts(self, fetched: List[Dict]) -> None:
        """Add fetched chunk counts to the totals; the list on device is
        emptied (they were fetched with this sync's tokens)."""
        self.moe_prefill_pairs += sum(int(c["moe_pairs"].sum())
                                      for c in fetched)
        self._pending_counts = []

    def _emit_decoded(self, n: int, tok_h: np.ndarray,
                      emit_h: np.ndarray) -> List[Tuple[int, int]]:
        """Hand each micro-step's emitted tokens to their responses and
        meter each shard's step; returns the finished (shard, slot)
        pairs."""
        now = time.perf_counter()
        released: List[Tuple[int, int]] = []
        for i in range(n):
            emitted_any = False
            for s in range(self.S):
                act = emit_h[s, i]
                n_active = int(act.sum())
                if n_active == 0:
                    continue           # this shard drained mid-chunk
                emitted_any = True
                self.shard_steps += 1
                ctx = float(np.mean([self._slot_ctx[s][b]
                                     for b in np.flatnonzero(act)]))
                rep = self._meter_decode(n_active, max(ctx, 1.0), shard=s)
                per_tok_t = rep.t_total / n_active
                per_tok_e = rep.energy_j / n_active
                for b in np.flatnonzero(act):
                    rid = self.slot_rid[s][b]
                    resp = self.responses[rid]
                    tok = int(tok_h[s, i, b])
                    resp.tokens.append(tok)
                    resp.t_emit.append(now)
                    resp.decode_s += per_tok_t
                    resp.energy_j += per_tok_e
                    self._slot_ctx[s][b] += 1.0
                    self.slot_budget[s][b] -= 1
                    eos_hit = (self.slot_eos[s][b] is not None
                               and tok == self.slot_eos[s][b])
                    if self.slot_budget[s][b] <= 0 or eos_hit:
                        resp.finished = True
                        resp.finish_reason = "eos" if eos_hit else "length"
                        self._clear_slot(s, b)
                        released.append((s, int(b)))
            if emitted_any:
                self._steps += 1
        return released

    def step(self, max_steps: int = 10_000) -> bool:
        """Run ONE scheduling quantum: a fault campaign's shard-loss and
        admin declarations, deferral release, deadline sweep (when any
        request declared one), drain and power-cap progress, admission,
        at most one prefill chunk launch, one fused decode scan — for
        every shard at once. Returns whether anything progressed — the
        async server drives this directly so it can interleave
        submissions and stream tokens between quanta."""
        self._quantum += 1
        with trace.quantum(self._quantum) as q:
            ev0, mig0 = self.shard_down_events, self.migrations
            if self.faults is not None:
                self._absorb_declarations()
            released = self._release_deferred() if self.deferred else 0
            if self._has_deadlines:
                self._sweep_deadlines()
            self._rebalance()
            admitted = self._admit()
            chunks = self._prefill_quantum() if self.chunked else 0
            decoded = (self._decode_chunk(max_steps) if self.decoding
                       else False)
            if self.paged:
                self._count_pages(q)
            if self._quantum_counts:
                if trace.enabled():
                    q.set_metadata(**self._quantum_counts)
                self._quantum_counts = {}
            dt = max(self._q_time)
            if dt > 0.0:
                if self.S > 1:
                    # shards run in parallel: the quantum's wall time is
                    # the slowest shard's (one meter advances it itself)
                    self.clock.hours += dt / 3600.0
                self._q_time = [0.0] * self.S
        # a recovery event IS progress: the watchdog can declare a shard
        # dead inside a launch handler (after this quantum's admission
        # pass), and the evacuees it re-queued must reach the next
        # admission pass — not be misread as an unplaceable head
        return bool(released or admitted or chunks or decoded
                    or self.shard_down_events != ev0
                    or self.migrations != mig0)

    def _count_pages(self, q) -> None:
        """Add this quantum's pages reserved (admission's worst case) and
        pages mapped (handed out by the device allocator) to their running
        totals; a traced quantum carries both as arguments."""
        reserved = self.num_pages * self.S - sum(self.free_pages)
        mapped = self.mapped_pages()
        self.reserved_page_quanta += reserved
        self.mapped_page_quanta += mapped
        if trace.enabled():
            q.set_metadata(reserved_pages=reserved, mapped_pages=mapped)

    def mapped_pages(self) -> int:
        """Pages the device allocators have handed out (``num_pages``
        minus the free-stack top, summed over shards), mirrored on the
        host without a device sync: each live slot's written pages less
        those it adopted, plus every page in a prefix index once
        (adopted, registered or pinned) less those a live slot
        registered, which its own count holds."""
        ps = self.cfg.page_size
        n = 0
        for s in range(self.S):
            for b, rid in enumerate(self.slot_rid[s]):
                if rid >= 0:
                    written = (int(self._slot_ctx[s][b])
                               if self._slot_armed[s][b]
                               else self._slot_req[s][b].prefill_pos)
                    n += -(-written // ps)
        if self.sharing:
            for s in range(self.S):
                n += len(self._page_ref[s])
                n -= sum(len(v) for v in self._slot_shared_in[s].values())
                n -= sum(len(v) for v in self._slot_own_idx[s].values())
        return n

    def _resolve_stall(self) -> None:
        """The quantum made no progress, nothing is armed, no fault site
        is backing off, yet requests wait: either preemption pins hold the
        missing pages (spill them — resume just recomputes more) or the
        head request can never fit and must fail. Shared by run() and the
        async server's drive loop."""
        live = self.health.live
        if (self.paged and self._pins
                and any(self.free_pages[s] < self.num_pages for s in live)):
            for rid in list(self._pins):
                self._drop_pin(rid)
            return
        if not self.paged or all(self.free_pages[s] == self.num_pages
                                 for s in live):
            # nothing running, every LIVE shard's whole pool free, and
            # admission still refused the head: it can never fit — per-
            # shard capacity is identical, so never-fits is the same
            # verdict on a degraded fleet — fail it rather than spin
            self._reject(self.queue.popleft())
        else:
            raise RuntimeError(        # unreachable: release returns
                "admission stalled with no active work — leaked "
                "page reservation")

    def run(self, max_steps: int = 10_000) -> List[Response]:
        """Drive until the queue drains and all slots finish.

        In chunked mode every loop iteration is one scheduling QUANTUM:
        admission claims slots/pages (no prefill launch), at most one
        prefill chunk runs, then one fused decode scan advances every
        armed slot — so a long prompt costs each decode slot one chunk of
        stall per quantum instead of its whole prefill.

        Exhausting ``max_steps`` marks every unfinished response with the
        ``"timeout"`` finish reason WITHOUT finishing it — the caller can
        see exactly which requests the budget stranded, and a later run()
        with more steps clears the mark by actually finishing them."""
        self._run_q0 = self._quantum
        while ((self.queue or self.active or self.deferred)
               and self._steps < max_steps):
            if self.step(max_steps):
                continue
            if self.decoding or self._faults_pending():
                continue               # armed slots or a site in backoff
            if self.queue:
                self._resolve_stall()
            elif self.deferred:
                # only parked work remains: sleep to the greenest window
                self._fast_forward_deferred()
        if self._steps >= max_steps:
            for r in self.responses.values():
                if not r.finished:
                    r.finish_reason = "timeout"
        return list(self.responses.values())

    # -------------------------------------------------------------- reports
    def carbon_report(self) -> str:
        return self.meter.report()

    @property
    def host_syncs(self) -> int:
        """Device->host synchronization points (decode chunk fetches plus
        one first-token fetch per prefill batch) — S shards have the same
        sync count as one."""
        return self.decode_chunks + self.prefill_batches

    def stats(self) -> Dict[str, float]:
        t = self.meter.totals
        pf = self.meter.phase("prefill")
        dc = self.meter.phase("decode")
        finished = [r for r in self.responses.values() if r.finished]
        lat = [r.prefill_s + r.decode_s for r in finished]
        p50 = float(np.median(lat)) if lat else 0.0
        # single-sample guard: a 1-request run reports its own latency
        p99 = float(np.percentile(lat, 99)) if len(lat) > 1 else p50
        # SLO attainment over finished requests that declared one
        slo_ok = slo_n = 0
        for r in finished:
            slo = self._req_slo.get(r.rid)
            if slo is not None:
                slo_n += 1
                slo_ok += (r.prefill_s + r.decode_s) <= slo
        out: Dict[str, float] = {}
        if self.paged:
            peak = sum(self.peak_pages_reserved)
            out.update({
                "paged": 1.0,
                "page_size": self.cfg.page_size,
                "pages_total": self.num_pages * self.S,
                "pages_per_shard": self.num_pages,
                "peak_pages_reserved": peak,
                "free_pages": sum(self.free_pages),
                "reserved_page_quanta": self.reserved_page_quanta,
                "mapped_page_quanta": self.mapped_page_quanta,
                # provisioned KV rows actually backing peak load — feeds
                # the embodied-carbon memory model (ROADMAP: paged pool)
                "peak_kv_rows_reserved": peak * self.cfg.page_size,
            })
        if self.chunked:
            out.update({
                "chunked": 1.0,
                "prefill_chunk": self.cfg.prefill_chunk,
                "prefill_chunks": self.prefill_chunks,
            })
        if self.counted:
            out.update({
                "moe_decode_pairs": self.moe_decode_pairs,
                "moe_decode_experts_hit": self.moe_decode_experts_hit,
                "moe_prefill_pairs": self.moe_prefill_pairs,
            })
        if self.sharing:
            out.update({
                "prefix_sharing": 1.0,
                # prompt tokens served straight from resident pages —
                # compute and pages that were never spent again
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_shared_requests": self.prefix_shared_requests,
                # peak EXTRA block-table mappings of already-provisioned
                # pages (the dedup: each is a page some other slot would
                # have forced the fleet to provision again)...
                "shared_pages": self.peak_shared_mappings,
                # ...while unique_pages is the physical footprint that
                # actually backed peak load — shared pages counted ONCE,
                # which is why peak_kv_rows_reserved (the Eq. 2-4 embodied
                # input) falls under prefix-heavy traffic
                "unique_pages": sum(self.peak_pages_reserved),
            })
        # front door: queueing, degradation, preemption, fault recovery
        out.update({
            "queue_depth": len(self.queue),
            "deferred_depth": len(self.deferred),
            "deferred_requests": self.deferred_total,
            "deferred_released": self.deferred_released,
            "deferred_forced_releases": self.deferred_forced,
            "shed_count": self.shed_count,
            "preemption_count": self.preemption_count,
            "deadline_cancelled": self.deadline_cancelled,
            "clamped_requests": self.clamped_requests,
            "fault_retries": self.fault_retries,
            "rate_limited": self.rate_limited,
            "preempted_recompute_j": self.preempted_recompute_j,
            "timeout_requests": sum(
                1 for r in self.responses.values()
                if not r.finished and r.finish_reason == "timeout"),
        })
        for p, waits in sorted(self._wait_samples.items()):
            out[f"queue_wait_p50_s_class_{p}"] = float(np.median(waits))
            out[f"queue_wait_p99_s_class_{p}"] = (
                float(np.percentile(waits, 99)) if len(waits) > 1
                else float(np.median(waits)))
        for p, n_shed in sorted(self._shed_by_class.items()):
            out[f"shed_class_{p}"] = n_shed
        for site, n in sorted(self.fault_retry_site.items()):
            out[f"fault_retries_{site}"] = n
        out.update({
            "requests": len(self.responses),
            "peak_active": self.peak_active,
            "p50_latency_s": p50,
            "p99_latency_s": p99,
            "slo_attainment": (slo_ok / slo_n) if slo_n else 1.0,
            "steps": self._steps,
            "decode_chunks": self.decode_chunks,
            "prefill_batches": self.prefill_batches,
            "host_syncs": self.host_syncs,
            "prefill_tokens": pf.tokens,
            "decode_tokens": dc.tokens,
            "prefill_j_per_token": pf.j_per_token,
            "decode_j_per_token": dc.j_per_token,
            "prefill_g_per_token": pf.g_per_token,
            "decode_g_per_token": dc.g_per_token,
            "total_energy_j": t.energy_j,
            "total_carbon_g": t.total_g,
            "embodied_fraction": (t.embodied_g / t.total_g) if t.total_g else 0.0,
        })
        # multi-criteria impact ledger (PR 9): the same per-phase
        # attribution priced in water / primary energy / ADPe —
        # docs/METHODOLOGY.md#the-impact-ledger defines each column
        out.update({
            "total_water_l": t.water_l,
            "total_primary_mj": t.primary_mj,
            "total_adpe_mg": t.adpe_mg,
            "prefill_water_l": pf.water_l,
            "decode_water_l": dc.water_l,
            "prefill_primary_mj": pf.primary_mj,
            "decode_primary_mj": dc.primary_mj,
            "prefill_adpe_mg": pf.adpe_mg,
            "decode_adpe_mg": dc.adpe_mg,
            "water_per_token_l": t.water_per_token,
        })
        self._fleet_stats(out)
        return out
