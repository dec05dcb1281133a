"""Mesh-sharded serving: the fused engine step over a JAX device mesh,
with per-shard page pools.

The paper's operational-carbon model (Eq. 1) prices serving by wall-clock
energy at a region's carbon intensity, so once the single-device hot path
is fused (PR 1-4) the remaining lever is aggregate throughput per host
overhead — and the fleet-placement work this repo targets next (GreenLLM's
disaggregated fleets, EcoServe's carbon-aware placement) presupposes an
engine whose step, KV pool, and scheduler are mesh-native. This module
shards the serving engine data-parallel over a 1-D device mesh:

  * every device-side array gains a LEADING shard axis — slot pools and
    per-attention-leaf page pools ``(S, Hkv, num_pages+1, ps, hd)``, the
    block table ``(S, B, max_pages)``, slot state ``(S, B)``, allocator
    free stacks ``(S, num_pages)`` — laid out by the logical-axis contract
    in ``repro.models.attention.serving_cache_axes`` and resolved through
    ``repro.sharding.rules.SERVING_RULES`` (shard -> the mesh's data axis);
  * the fused decode scan, the chunked-prefill step, and every insertion/
    release op run as ONE jitted program spanning the whole mesh: a
    ``shard_map`` whose body is the unmodified single-device function on
    the local shard (kernels, allocator, sampling all reused verbatim —
    no per-shard Python loop, no GSPMD guessing). One host sync per
    ``sync_every`` micro-steps serves the WHOLE fleet: the stacked
    ``(S, n_steps, B)`` token/emission matrices come back in one fetch;
  * free stacks are per shard, so alloc-on-write inside the fused scan
    stays shard-local by construction — no cross-device traffic on the
    decode hot path, which is what makes aggregate steps/s scale.

Host-side scheduling is shard-aware: admission places each request on the
shard with the most free pages (reservation accounting per shard, FCFS —
the head request never gets overtaken), the prefix index is PER SHARD
(keys carry the shard id implicitly: one index dict per shard), so
adoption never crosses shards and release/decref stays shard-local.
Requests whose prompts hit a resident prefix are steered to the shard
holding it (longest match wins, free pages break ties) — sharing is a
placement input, not just an admission discount.

Idle lanes inside a fleet-wide program are expressed with the sentinel
slot id ``B`` (one past the per-shard slot range): JAX drops out-of-range
scatters and clamps out-of-range gathers, so a lane whose ``slots`` row is
all-sentinel (plus an all-zero token mask) runs the same traced program as
a busy lane while provably writing nothing but its own trash page — the
fleet step stays a single SPMD program with no per-lane control flow.

The single-device paged engine is preserved untouched as the token-for-
token parity oracle (tests/test_sharded_parity.py), exactly as the
contiguous engine was for PR 2-4.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.energy import decode_counts, migrate_counts, step_energy
from repro.core.hardware import HardwareProfile, get_profile
from repro.core.intensity import Region, ci_at_hour, get_region
from repro.core.meter import CarbonMeter, FleetMeterView, SharedClock
from repro.core.scheduler import (FleetSlice, marginal_request_g,
                                  migration_cost_g)
from repro.launch.mesh import make_serving_mesh
from repro.models import Model
from repro.models.costing import workload_of
from repro.serving import paged, preempt, sampling, trace
from repro.serving.engine import (EngineConfig, ServingEngine,
                                  _chunk_prefill_fn, _prefill_phase_counts,
                                  pack_chunks)
from repro.serving.faults import FaultError, HealthMonitor, InjectedFault
from repro.serving.request import Request, Response
from repro.sharding.rules import serving_shardings

_SHARD = P("data")                     # leading fleet axis of every leaf


def _lane(tree):
    """Local (1, ...) shard_map view -> the single-shard (...) tree."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _unlane(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


# ------------------------------------------------------- fleet jit entries
#
# Module-level with (model, mesh) static, same as engine.py's single-device
# entries: every ShardedServingEngine sharing a Model instance and mesh
# reuses the same compiled executables. Each wraps the UNmodified
# single-device function in a shard_map body — the mesh program is the
# single-device program, replicated, with shard-local state.


def _fused_steps_fleet(model, mesh, params, caches, cur_tokens, state, keys,
                       *, n_steps, temperature, page_size):
    def body(params, caches, cur_tokens, state, keys):
        out = sampling.fused_decode_steps(
            model, params, _lane(caches), _lane(cur_tokens), _lane(state),
            keys[0], n_steps=n_steps, temperature=temperature,
            page_size=page_size, freeze_inactive=True)
        return tuple(_unlane(t) for t in out)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(), _SHARD, _SHARD, _SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(
        params, caches, cur_tokens, state, keys)


def _chunk_prefill_fleet(model, mesh, params, caches, tokens, mask, slots,
                         keys, *, vocab, temperature, page_size, sharing):
    def body(params, caches, tokens, mask, slots, keys):
        first, _, rows, caches = _chunk_prefill_fn(
            model, params, _lane(caches), tokens[0], mask[0], slots[0],
            keys[0], vocab=vocab, temperature=temperature,
            page_size=page_size, sharing=sharing)
        return first[None], rows[None], _unlane(caches)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(), _SHARD, _SHARD, _SHARD, _SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(
        params, caches, tokens, mask, slots, keys)


def _begin_fleet(mesh, caches, slots):
    def body(caches, slots):
        return _unlane(paged.begin_chunked_prefill(_lane(caches), slots[0]))

    return shard_map(body, mesh=mesh, in_specs=(_SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(caches, slots)


def _arm_fleet(mesh, cur_tokens, state, slots, firsts, budgets, eos_ids):
    def body(cur_tokens, state, slots, firsts, budgets, eos_ids):
        cur, st = sampling.arm_slots(_lane(cur_tokens), _lane(state),
                                     slots[0], firsts[0], budgets[0],
                                     eos_ids[0])
        return _unlane(cur), _unlane(st)

    return shard_map(body, mesh=mesh, in_specs=(_SHARD,) * 6,
                     out_specs=_SHARD, check_vma=False)(
        cur_tokens, state, slots, firsts, budgets, eos_ids)


def _release_fleet(mesh, caches, released):
    def body(caches, released):
        caches = _lane(caches)
        caches = dict(caches)
        caches["paged"] = paged.release_slots(caches["paged"], released[0])
        return _unlane(caches)

    return shard_map(body, mesh=mesh, in_specs=(_SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(caches, released)


def _release_keep_fleet(mesh, caches, released, n_keep):
    def body(caches, released, n_keep):
        caches = _lane(caches)
        caches = dict(caches)
        caches["paged"] = paged.release_slots_keep(caches["paged"],
                                                   released[0], n_keep[0])
        return _unlane(caches)

    return shard_map(body, mesh=mesh, in_specs=(_SHARD,) * 3,
                     out_specs=_SHARD, check_vma=False)(
        caches, released, n_keep)


def _decref_fleet(mesh, caches, pages):
    def body(caches, pages):
        caches = _lane(caches)
        caches = dict(caches)
        caches["paged"] = paged.decref_pages(caches["paged"], pages[0])
        return _unlane(caches)

    return shard_map(body, mesh=mesh, in_specs=(_SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(caches, pages)


def _disarm_fleet(mesh, state, slots):
    def body(state, slots):
        return _unlane(sampling.disarm_slots(_lane(state), slots[0]))

    return shard_map(body, mesh=mesh, in_specs=(_SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(state, slots)


def _quarantine_fleet(mesh, caches, do):
    """Declaration-time route invalidation: lanes whose ``do`` flag is
    set get their block table cleared (``paged.quarantine_table``) so the
    batch-shape-invariant writes of later fleet launches fall into the
    trash page; refcounts, free stack, and KV payloads stay untouched."""
    def body(caches, do):
        caches = _lane(caches)
        caches = dict(caches)
        caches["paged"] = paged.quarantine_table(caches["paged"], do[0])
        return _unlane(caches)

    return shard_map(body, mesh=mesh, in_specs=(_SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(caches, do)


def _scrub_fleet(mesh, caches, do):
    """Rejoin scrub: lanes whose ``do`` flag is set rebuild their pool to
    the virgin post-``paginate_cache`` state (allocator reset, cursors
    cleared — ``paged.scrub_pool``); every other lane's pool is returned
    bit-identical. One SPMD program, no per-lane control flow."""
    def body(caches, do):
        return _unlane(paged.scrub_pool(_lane(caches), do[0]))

    return shard_map(body, mesh=mesh, in_specs=(_SHARD, _SHARD),
                     out_specs=_SHARD, check_vma=False)(caches, do)


def _map_prefix_fleet(mesh, caches, slot, pages, n_shared, start_tok):
    def body(caches, slot, pages, n_shared, start_tok):
        return _unlane(paged.map_shared_prefix(
            _lane(caches), slot[0], pages[0], n_shared[0], start_tok[0]))

    return shard_map(body, mesh=mesh, in_specs=(_SHARD,) * 5,
                     out_specs=_SHARD, check_vma=False)(
        caches, slot, pages, n_shared, start_tok)


def _migrate_fleet(mesh, caches, cur_tokens, state, is_src, is_dst,
                   b_src, b_dst, src_pg, n_pages):
    """Cross-shard KV-page migration as ONE SPMD program: the source lane
    exports its slot's mapped pages + decode rows, a masked ``psum`` over
    the data axis carries the payload to every lane (compiles once for
    any (src, dst) pair — a static ``ppermute`` perm would recompile per
    pair), the destination lane pops fresh pages and lands it, and the
    source lane releases + disarms. Every OTHER lane's sentinel inputs
    (slot id ``B``, ``n_pages`` 0, flags False) make both halves provable
    no-ops: gathers clamp into masked-out rows, scatters drop, the
    release mask is all-False — the lane's pool and state come back
    bit-identical (dead lanes included, preserving the frozen-pool
    contract). Returns the migrated slot's NEW block-table row per lane
    (real on the destination lane; the host indexes it out)."""
    def body(caches, cur, state, is_src, is_dst, b_src, b_dst,
             src_pg, n_pages):
        caches = dict(_lane(caches))
        cur = _lane(cur)
        state = _lane(state)
        src, dst = is_src[0], is_dst[0]
        bs, bd = b_src[0], b_dst[0]
        B = cur.shape[0]
        bsc = jnp.clip(bs, 0, B - 1)
        payload = paged.export_slot(caches, bs, src_pg[0])
        rows = {"cur": cur[bsc], "active": state["active"][bsc],
                "budget": state["budget"][bsc], "eos": state["eos"][bsc]}

        def xfer(x):
            if x.dtype == jnp.bool_:
                masked = jnp.where(src, x.astype(jnp.int32), 0)
                return jax.lax.psum(masked, "data") != 0
            return jax.lax.psum(jnp.where(src, x, jnp.zeros_like(x)),
                                "data")

        payload = jax.tree_util.tree_map(xfer, payload)
        rows = jax.tree_util.tree_map(xfer, rows)
        # source half: hand the pages back (shared-prefix pages survive
        # under their other holders' refs) and stop the slot's sampling
        # BEFORE the next fused chunk can emit from it
        caches["paged"] = paged.release_slots(caches["paged"],
                                              jnp.arange(B) == bs)
        state = sampling.disarm_slots(state, bs[None])
        # destination half: fresh pages, rewritten row, landed payload
        caches = paged.migrate_pages(caches, bd, payload,
                                     jnp.where(dst, n_pages[0], 0))
        cur = cur.at[bd].set(rows["cur"], mode="drop")
        state = {"active": state["active"].at[bd].set(rows["active"],
                                                      mode="drop"),
                 "budget": state["budget"].at[bd].set(rows["budget"],
                                                      mode="drop"),
                 "eos": state["eos"].at[bd].set(rows["eos"], mode="drop")}
        row = caches["paged"]["tbl"][jnp.clip(bd, 0, B - 1)]
        return (_unlane(caches), _unlane(cur), _unlane(state), row[None])

    return shard_map(body, mesh=mesh, in_specs=(_SHARD,) * 9,
                     out_specs=_SHARD, check_vma=False)(
        caches, cur_tokens, state, is_src, is_dst, b_src, b_dst,
        src_pg, n_pages)


_FUSED_FLEET = jax.jit(_fused_steps_fleet, static_argnums=(0, 1),
                       static_argnames=("n_steps", "temperature",
                                        "page_size"))
_CHUNK_FLEET = jax.jit(_chunk_prefill_fleet, static_argnums=(0, 1),
                       static_argnames=("vocab", "temperature", "page_size",
                                        "sharing"))
_BEGIN_FLEET = jax.jit(_begin_fleet, static_argnums=(0,))
_ARM_FLEET = jax.jit(_arm_fleet, static_argnums=(0,))
_RELEASE_FLEET = jax.jit(_release_fleet, static_argnums=(0,))
_MAP_PREFIX_FLEET = jax.jit(_map_prefix_fleet, static_argnums=(0,))
_RELEASE_KEEP_FLEET = jax.jit(_release_keep_fleet, static_argnums=(0,))
_DECREF_FLEET = jax.jit(_decref_fleet, static_argnums=(0,))
_DISARM_FLEET = jax.jit(_disarm_fleet, static_argnums=(0,))
_QUARANTINE_FLEET = jax.jit(_quarantine_fleet, static_argnums=(0,))
_SCRUB_FLEET = jax.jit(_scrub_fleet, static_argnums=(0,))
_MIGRATE_FLEET = jax.jit(_migrate_fleet, static_argnums=(0,))


class ShardedServingEngine:
    """Data-parallel fleet of ``cfg.shards`` serving shards behind one
    queue: per-shard slot pools, page pools, and free stacks; fleet-wide
    fused programs; shard-aware host scheduling. Requires the paged pool
    and chunked prefill (``cfg.paged`` + ``cfg.prefill_chunk``) — the
    quantum scheduler is what lets one program carry every shard's prefill
    chunk and decode scan without per-shard phases. ``cfg.max_batch`` and
    ``cfg.num_pages`` are PER SHARD ("4 shards of B", "equal per-device
    pool bytes")."""

    def __init__(self, model: Model, params, cfg: EngineConfig,
                 mesh=None):
        if cfg.shards < 1:
            raise ValueError("shards must be >= 1")
        if not cfg.paged or cfg.prefill_chunk is None:
            raise ValueError(
                "mesh-sharded serving requires the paged pool + chunked "
                "prefill (paged=True, prefill_chunk set): the quantum "
                "scheduler is what packs every shard's prefill chunk and "
                "decode scan into single fleet-wide programs")
        # reuse the single-device engine's full validation (pool geometry,
        # model capability gates) on a throwaway instance, then discard its
        # device state — the fleet builds its own stacked arrays
        probe = ServingEngine(model, params, cfg)
        self.model, self.params_host, self.cfg = model, params, cfg
        self.profile: HardwareProfile = get_profile(cfg.profile)
        self.workload = workload_of(model.cfg)
        S, B = cfg.shards, cfg.max_batch
        self.S, self.B = S, B
        # ---- heterogeneous fleet: per-shard hardware profile + region.
        # The MODEL runs identically on every shard (one SPMD program);
        # heterogeneity lives entirely in attribution and placement.
        prof_names = (list(cfg.shard_profiles) if cfg.shard_profiles
                      is not None else [cfg.profile] * S)
        region_names = (list(cfg.shard_regions) if cfg.shard_regions
                        is not None else [cfg.region] * S)
        if len(prof_names) != S:
            raise ValueError(
                f"shard_profiles has {len(prof_names)} entries for "
                f"{S} shards")
        if len(region_names) != S:
            raise ValueError(
                f"shard_regions has {len(region_names)} entries for "
                f"{S} shards")
        self.shard_profile: List[HardwareProfile] = [
            get_profile(n) for n in prof_names]
        self.shard_region: List[Region] = [
            get_region(r) for r in region_names]
        # one meter PER SHARD at that shard's profile × region CI, all on
        # one fleet clock (shards run in parallel — the engine advances the
        # clock once per quantum by the slowest shard's modeled time, so
        # advances_clock=False here). Fleet totals are the exact sum of the
        # per-shard attribution via FleetMeterView; each shard's embodied
        # amortization covers ITS cfg.n_devices devices, so the fleet's
        # installed hardware is charged exactly once across the S meters.
        self.clock = SharedClock()
        self.meters: List[CarbonMeter] = [
            CarbonMeter(self.shard_profile[s], self.shard_region[s],
                        lifetime_years=cfg.lifetime_years,
                        n_devices=cfg.n_devices,
                        use_diurnal_ci=cfg.use_diurnal_ci,
                        clock=self.clock, advances_clock=False)
            for s in range(S)]
        self.meter = FleetMeterView(self.meters)
        # the carbon router scores shards through the SAME FleetSlice /
        # marginal-g machinery as the offline CIDirectedScheduler — one
        # scoring core, no drift between the table and the serving loop
        self._slices: List[FleetSlice] = [
            FleetSlice(self.shard_profile[s], self.shard_region[s],
                       lifetime_years=cfg.lifetime_years)
            for s in range(S)]
        self._q_time = [0.0] * S       # per-shard modeled time this quantum
        self.shard_requests = [0] * S  # placements per shard (stats)
        self.max_pages_slot = probe.max_pages_slot
        self.num_pages = probe.num_pages        # per shard
        self.mesh = mesh if mesh is not None else make_serving_mesh(S)

        # ---- device state: stack the single-shard tree S-wide and place
        # every leaf leading-axis over the mesh (attention.py declares the
        # logical axes; rules.py resolves them). The stack runs inside one
        # program whose outputs are already sharded, so each device
        # materializes only its own lane — an eager broadcast would first
        # stage all S copies of the pool on one device.
        def stack(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (S,) + x.shape), tree)

        def place(tree):
            out = serving_shardings(self.mesh, jax.eval_shape(stack, tree))
            return jax.jit(stack, out_shardings=out)(tree)

        self.caches = place(probe.caches)
        self.params = jax.device_put(params, jax.tree_util.tree_map(
            lambda _: NamedSharding(self.mesh, P()), params))
        self.cur_tokens = place(probe.cur_tokens)
        self.state = place(probe.state)
        del probe

        # ---- host mirrors, one entry per shard
        self.queue: deque = deque()
        self.responses: Dict[int, Response] = {}
        self.slot_rid = [[-1] * B for _ in range(S)]
        self.slot_budget = [[0] * B for _ in range(S)]
        self.slot_eos: List[List[Optional[int]]] = [[None] * B
                                                    for _ in range(S)]
        self._slot_ctx = [[0.0] * B for _ in range(S)]
        self._slot_armed = [[False] * B for _ in range(S)]
        self._slo: List[List[Optional[float]]] = [[None] * B
                                                  for _ in range(S)]
        self._req_slo: Dict[int, Optional[float]] = {}
        self.free_pages = [self.num_pages] * S
        self.peak_pages_reserved = [0] * S
        self._slot_pages = [[0] * B for _ in range(S)]
        self._prefilling: List[deque] = [deque() for _ in range(S)]
        self._req_shard: Dict[int, int] = {}
        # front-door mirrors + counters (see ServingEngine.__init__)
        self._slot_req: List[List[Optional[Request]]] = [
            [None] * B for _ in range(S)]
        self._slot_prio = [[0] * B for _ in range(S)]
        self._slot_deadline: List[List[Optional[float]]] = [
            [None] * B for _ in range(S)]
        self._has_deadlines = False
        self._quantum = 0
        self._run_q0 = 0
        self.faults = None
        self._backoff: Dict[str, Tuple[int, int]] = {}
        self.fault_retries = 0
        self.fault_retry_site: Dict[str, int] = {}
        # per-(site, shard) retry counters: every faulted launch charges
        # the shards it touched (stats() splits fault_retries out by both)
        self._fault_retry_shard: Dict[Tuple[str, int], int] = {}
        # ---- shard-loss resilience (PR 8): the fleet's health watchdog.
        # A shard is declared dead by explicit shard_down injection or
        # when max_retries consecutive faulted launches touched it while
        # a survivor exists; declaration EVACUATES its in-flight work
        # onto the live shards and invalidates every host mirror that
        # could reach the dead pool. See fail_shard()/rejoin()/audit().
        self.health = HealthMonitor(S, cfg.max_retries)
        self.shard_down_events = 0
        self.shard_evacuated = 0       # requests moved off dead shards
        self.shard_rejoins = 0
        # ---- live KV-page migration (PR 10): graceful drain, reachable
        # evacuation, and brownout power caps all ride _migrate_slot().
        # Draining shards take no new placements; their in-flight slots
        # page-copy to the survivors between quanta (zero recompute J),
        # then the empty shard hands off to fail_shard/rejoin.
        self._draining: set = set()
        self._drain_deadline: Dict[int, Optional[float]] = {}
        self._power_cap: List[Optional[float]] = [None] * S
        self.migrations = 0            # completed slot migrations
        self.migrated_pages = 0        # pages copied across shards
        self.drain_events = 0
        self.power_cap_events = 0
        # per-tenant rate limiting (submit() is borrowed, so the fleet
        # carries the same bucket state as the single-device engine)
        self._tenant_buckets: Dict[str, List[float]] = {}
        self.rate_limited = 0
        self.shed_count = 0
        self._shed_by_class: Dict[int, int] = {}
        self.preemption_count = 0
        self.deadline_cancelled = 0
        self.clamped_requests = 0
        self.preempted_recompute_j = 0.0
        self._wait_samples: Dict[int, List[float]] = {}
        # preemption pins are shard-local: rid -> (shard, [phys pages])
        self._pins: Dict[int, Tuple[int, List[int]]] = {}
        # temporal deferral (borrowed policy; the clock is the fleet's)
        self.deferred: deque = deque()
        self.deferred_rids: set = set()
        self._defer_release_h: Dict[int, float] = {}
        self._forecasters: Dict[str, object] = {}
        self.deferred_total = 0
        self.deferred_released = 0
        self.deferred_forced = 0

        self.sharing = cfg.prefix_sharing
        if self.sharing:
            # SHARD-LOCAL prefix index: one index per shard (the shard id
            # is part of the key), so adoption never crosses shards and
            # decref accounting stays local to the holder's free stack
            self._prefix_index: List[Dict[bytes, int]] = [
                {} for _ in range(S)]
            self._page_key: List[Dict[int, bytes]] = [{} for _ in range(S)]
            self._page_ref: List[Dict[int, int]] = [{} for _ in range(S)]
            self._slot_shared_in: List[Dict[int, List[int]]] = [
                {} for _ in range(S)]
            self._slot_own_idx: List[Dict[int, List[int]]] = [
                {} for _ in range(S)]
            self.prefix_hit_tokens = 0
            self.prefix_shared_requests = 0

        self._key = jax.random.PRNGKey(0)
        # step counting matches the single-device engine exactly: a fleet
        # micro-step counts toward _steps only if SOME shard emitted, and
        # shard_steps counts (micro-step, shard) pairs with >= 1 emission
        # — the honest comparand for aggregate throughput claims
        self._steps = 0
        self.shard_steps = 0
        self.decode_chunks = 0         # fleet-wide device->host syncs
        self.prefill_batches = 0       # first-token syncs
        self.prefill_chunks = 0        # fleet chunk launches
        self.peak_active = 0

    # ---------------------------------------------------------- small utils
    def _next_keys(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return jax.random.split(sub, self.S)

    def free_slots(self, s: int) -> List[int]:
        return [i for i, r in enumerate(self.slot_rid[s]) if r < 0]

    @property
    def active(self) -> int:
        return sum(1 for s in range(self.S)
                   for r in self.slot_rid[s] if r >= 0)

    @property
    def decoding(self) -> int:
        return sum(1 for s in range(self.S)
                   for a in self._slot_armed[s] if a)

    # ------------------------------------------- borrowed host-side logic
    # identical to the single-device engine — borrowed, not copied, so a
    # fix there propagates here. Queue/budget bookkeeping is device-count
    # agnostic by construction.
    _prompt_page_keys = ServingEngine._prompt_page_keys
    _over_budget = ServingEngine._over_budget
    _reject = ServingEngine._reject
    submit = ServingEngine.submit
    # front door: queue ordering, shedding, degradation, admission
    # stamping, fault-site bookkeeping, and cancellation are all pure
    # host-side policy — device-count agnostic by construction
    _enqueue = ServingEngine._enqueue
    _pick_shed_victim = ServingEngine._pick_shed_victim
    _shed = ServingEngine._shed
    _apply_pressure_clamp = ServingEngine._apply_pressure_clamp
    _stamp_admit = ServingEngine._stamp_admit
    _cancel = ServingEngine._cancel
    _inject = ServingEngine._inject
    _site_ready = ServingEngine._site_ready
    _faults_pending = ServingEngine._faults_pending
    _rate_limit = ServingEngine._rate_limit
    # _site_failed/_site_ok are OVERRIDDEN below: the fleet feeds every
    # launch outcome to the health watchdog, and retry exhaustion becomes
    # shard loss (not FaultError) whenever a survivor exists
    # temporal deferral is pure host-side policy too; only the TIME BASE
    # differs (the fleet's shared clock) — see the overrides below
    _defer = ServingEngine._defer
    _release_deferred = ServingEngine._release_deferred
    _fast_forward_deferred = ServingEngine._fast_forward_deferred
    _forecaster = ServingEngine._forecaster

    def _clock_hours(self) -> float:
        return self.clock.hours

    def _advance_clock_to(self, hours: float) -> None:
        self.clock.hours = max(self.clock.hours, hours)

    def _defer_regions(self) -> List[Region]:
        # dedup preserving order: S shards usually span few regions
        seen: Dict[str, Region] = {}
        for r in self.shard_region:
            seen.setdefault(r.name, r)
        return list(seen.values())

    # ---------------------------------------------------- per-shard metering
    # Same step counts as the single-device engine, priced at THIS shard's
    # profile and recorded on its meter; the per-quantum max of the shard
    # times advances the fleet clock (shards run in parallel).
    def _meter_prefill(self, batch: int, seq: int,
                       useful_seq: Optional[float] = None, skip: int = 0,
                       phase: str = "prefill", shard: int = 0):
        counts = _prefill_phase_counts(self.workload, batch, seq,
                                       useful_seq=useful_seq, skip=skip)
        rep = step_energy(self.shard_profile[shard], counts)
        self.meters[shard].record(phase, rep.tokens, rep.t_total,
                                  rep.energy_j)
        self._q_time[shard] += rep.t_total
        return rep

    def _meter_decode(self, batch: int, context: float, shard: int = 0):
        counts = decode_counts(self.workload, batch, context)
        rep = step_energy(self.shard_profile[shard], counts)
        self.meters[shard].record("decode", rep.tokens, rep.t_total,
                                  rep.energy_j)
        self._q_time[shard] += rep.t_total
        return rep

    def _meter_migrate(self, src: int, dst: int, kv_tokens: float) -> None:
        """Charge a page copy to the ``migrate`` phase on BOTH endpoints
        — each shard prices its own side of the transfer at its own
        profile (docs/METHODOLOGY.md: migrate is its own phase, so
        prefill/decode J per token stay invariant to migration policy).
        The copy runs on both shards concurrently, so each side's modeled
        time joins its own quantum total (the clock advances by the
        fleet max)."""
        counts = migrate_counts(self.workload, kv_tokens)
        for s in (src, dst):
            rep = step_energy(self.shard_profile[s], counts)
            self.meters[s].record("migrate", rep.tokens, rep.t_total,
                                  rep.energy_j)
            self._q_time[s] += rep.t_total

    # ------------------------------------------------------- prefix sharing
    def _match_prefix(self, req: Request, s: int) -> Tuple[int, List[int]]:
        """Longest prefix of the prompt resident in SHARD ``s``'s index."""
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

    def _register_prefix(self, req: Request, s: int, slot: int,
                         row: np.ndarray) -> None:
        own = self._slot_own_idx[s].setdefault(slot, [])
        for i, key in enumerate(self._prompt_page_keys(req)):
            p = int(row[i])
            if key not in self._prefix_index[s]:
                self._prefix_index[s][key] = p
                self._page_key[s][p] = key
                self._page_ref[s][p] = self._page_ref[s].get(p, 0) + 1
                own.append(p)

    # -------------------------------------------------------------- release
    def _release_slots(self, pairs: List[Tuple[int, int]]) -> None:
        """Return finished (shard, slot) pairs' pages: ONE fleet-wide
        release program + per-shard host reservation accounting (the same
        popper-charges-once / last-holder-credits-once flows as the
        single-device engine, applied within each shard)."""
        if not pairs:
            return
        with trace.span("release"):
            self._release_pages(pairs)

    def _release_pages(self, pairs: List[Tuple[int, int]]) -> None:
        mask = np.zeros((self.S, self.B), bool)
        for s, b in pairs:
            mask[s, b] = True
        self.caches = _RELEASE_FLEET(self.mesh, self.caches,
                                     jnp.asarray(mask))
        for s, b in pairs:
            ret = self._slot_pages[s][b]
            if self.sharing:
                for p in self._slot_own_idx[s].pop(b, []):
                    self._page_ref[s][p] -= 1
                    if self._page_ref[s][p] <= 0:
                        self._drop_index_page(s, p)
                    else:
                        ret -= 1       # survives under someone else's map
                for p in self._slot_shared_in[s].pop(b, []):
                    self._page_ref[s][p] -= 1
                    if self._page_ref[s][p] <= 0:
                        self._drop_index_page(s, p)
                        ret += 1       # last holder frees the original
            self.free_pages[s] += ret
            self._slot_pages[s][b] = 0

    # ------------------------------------------------------------ preemption
    # same eviction/resume contract as the single-device engine
    # (serving/preempt.py); pins and victims are SHARD-LOCAL, and _place's
    # longest-resident-prefix preference automatically steers a resumed
    # request back to the shard still holding its pinned pages.

    def _drop_pin(self, rid: int) -> None:
        pin = self._pins.pop(rid, None)
        if pin is None:
            return
        s, pins = pin
        if self.health.is_dead(s):
            # defensive: declaration already invalidated dead-shard pins;
            # never issue a decref against a dead pool
            return
        pages = np.full((self.S, self.max_pages_slot), -1, np.int32)
        pages[s, :len(pins)] = pins
        self.caches = _DECREF_FLEET(self.mesh, self.caches,
                                    jnp.asarray(pages))
        for p in pins:
            self._page_ref[s][p] -= 1
            if self._page_ref[s][p] <= 0:
                self._drop_index_page(s, p)
                self.free_pages[s] += 1

    def _try_preempt(self, req: Request) -> bool:
        """Evict the fleet-wide best victim (lowest class, least progress,
        shard-local page return) strictly below ``req``'s class; True if a
        slot was freed somewhere. Placement re-runs afterwards — the freed
        shard may or may not be the one ``req`` lands on, but the evicted
        pages only help its own shard (pools are per shard)."""
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
        self.caches = _RELEASE_KEEP_FLEET(self.mesh, self.caches,
                                          jnp.asarray(mask),
                                          jnp.asarray(n_keep))
        slots = np.full((self.S, 1), self.B, np.int32)
        slots[s, 0] = slot
        self.state = _DISARM_FLEET(self.mesh, self.state,
                                   jnp.asarray(slots))
        self._account_eviction(s, slot, pinned)
        if pinned:
            self._pins[req.rid] = (s, pinned)
        self._clear_slot(s, slot)
        self._req_shard.pop(req.rid, None)
        self.preemption_count += 1
        self._enqueue(req, resume=True)

    def _account_eviction(self, s: int, slot: int,
                          pinned: List[int]) -> None:
        ret = self._slot_pages[s][slot]
        if self.sharing:
            keep = set(pinned)
            for p in self._slot_own_idx[s].pop(slot, []):
                if p in keep:
                    ret -= 1           # stays resident under the pin
                    continue
                self._page_ref[s][p] -= 1
                if self._page_ref[s][p] <= 0:
                    self._drop_index_page(s, p)
                else:
                    ret -= 1           # survives under someone else's map
            for p in self._slot_shared_in[s].pop(slot, []):
                if p in keep:
                    continue           # adopted ref transferred to the pin
                self._page_ref[s][p] -= 1
                if self._page_ref[s][p] <= 0:
                    self._drop_index_page(s, p)
                    ret += 1           # last holder frees the original
        self.free_pages[s] += ret
        self._slot_pages[s][slot] = 0

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

    # ------------------------------------------------- live KV-page migration
    # The recompute-free counterpart of evacuation: a slot's mapped pages
    # are COPIED into fresh pages of a survivor's pool by one fleet
    # program (_MIGRATE_FLEET), its host mirrors move with it, and decode
    # resumes on the destination from the same context — token-for-token
    # with the undisturbed run, zero recompute J. Shared-prefix runs
    # migrate as private copies, then re-register in the destination's
    # index (copy-then-reindex): the source's index entries survive under
    # their remaining holders or fall out with the last ref, exactly as
    # an ordinary release. Three consumers: drain() (graceful shutdown),
    # reachable evacuation (fail_shard upgrade), power_cap() (brownout).

    def _fetch_tbl(self) -> np.ndarray:
        # writable copy: shed sweeps mark migrated rows cleared in place
        return np.array(jax.device_get(self.caches["paged"]["tbl"]))

    def _resv_for_move(self, s: int, b: int) -> int:
        """Worst-case reservation the DESTINATION must hold for slot
        (s, b): the request's full prompt+budget page count, with NO
        sharing discount — migrated pages land as private copies, so the
        destination pool carries them all."""
        req = self._slot_req[s][b]
        return paged.pages_needed(
            len(req.prompt) + max(req.max_new_tokens - 1, 0),
            self.cfg.page_size)

    def _pick_migration_dest(self, s: int, resv_d: int) -> Optional[int]:
        """Best survivor to receive a slot from shard ``s``: live, not
        draining, a free slot, and room for the full private reservation.
        Baseline key mirrors placement (most free pages, lowest id);
        carbon routing breaks free-page ties by the cheaper copy
        (``migration_cost_g`` at the destination's profile × current
        CI — operational only, a copy rents no embodied share)."""
        carbon = self.cfg.routing == "carbon"
        kv_tokens = float(resv_d * self.cfg.page_size)
        best = None
        for d in self.health.live:
            if d == s or d in self._draining:
                continue
            if not self.free_slots(d) or self.free_pages[d] < resv_d:
                continue
            key: Tuple = (self.free_pages[d], -d)
            if carbon:
                region = self.shard_region[d]
                ci = (ci_at_hour(region, self._clock_hours() % 24.0)
                      if self.cfg.use_diurnal_ci else region.ci_g_per_kwh)
                g, _ = migration_cost_g(self._slices[d], self.workload,
                                        kv_tokens, ci=ci)
                key = (self.free_pages[d], -g, -d)
            if best is None or key > best[0]:
                best = (key, d)
        return None if best is None else best[1]

    def _migrate_slot(self, s: int, b: int, d: int,
                      src_row: np.ndarray) -> None:
        """Move slot (s, b) to shard ``d``: one fleet program copies the
        pages + decode state and releases the source, then the host
        mirrors transfer — source credited exactly like a release
        (sharing-aware), destination claims a slot + the full private
        reservation. Armed slots re-register their prompt pages in the
        destination's prefix index from the NEW block-table row; mid-
        prefill slots re-register at prefill completion as usual."""
        req = self._slot_req[s][b]
        rid = self.slot_rid[s][b]
        pages = [int(p) for p in src_row if p >= 0]
        n = len(pages)
        slot_d = self.free_slots(d)[0]
        resv_d = self._resv_for_move(s, b)
        budget, eos = self.slot_budget[s][b], self.slot_eos[s][b]
        ctx, armed = self._slot_ctx[s][b], self._slot_armed[s][b]
        slo, prio = self._slo[s][b], self._slot_prio[s][b]
        ddl = self._slot_deadline[s][b]
        is_src = np.zeros((self.S,), bool)
        is_dst = np.zeros((self.S,), bool)
        is_src[s], is_dst[d] = True, True
        b_src = np.full((self.S,), self.B, np.int32)
        b_dst = np.full((self.S,), self.B, np.int32)
        b_src[s], b_dst[d] = b, slot_d
        pg = np.full((self.S, self.max_pages_slot), -1, np.int32)
        pg[s] = src_row
        npg = np.zeros((self.S,), np.int32)
        npg[d] = n
        (self.caches, self.cur_tokens, self.state, rows) = _MIGRATE_FLEET(
            self.mesh, self.caches, self.cur_tokens, self.state,
            jnp.asarray(is_src), jnp.asarray(is_dst), jnp.asarray(b_src),
            jnp.asarray(b_dst), jnp.asarray(pg), jnp.asarray(npg))
        dst_row = np.asarray(jax.device_get(rows))[d]
        # source credit: the device release already ran in-program; the
        # mirror flows are the same popper-charges-once / last-holder-
        # credits-once accounting as _release_slots
        ret = self._slot_pages[s][b]
        if self.sharing:
            for p in self._slot_own_idx[s].pop(b, []):
                self._page_ref[s][p] -= 1
                if self._page_ref[s][p] <= 0:
                    self._drop_index_page(s, p)
                else:
                    ret -= 1           # survives under someone else's map
            for p in self._slot_shared_in[s].pop(b, []):
                self._page_ref[s][p] -= 1
                if self._page_ref[s][p] <= 0:
                    self._drop_index_page(s, p)
                    ret += 1           # last holder frees the original
        self.free_pages[s] += ret
        self._slot_pages[s][b] = 0
        self._clear_slot(s, b)
        # destination claim: same mirror writes as admission, but the
        # slot arrives mid-flight (ctx, budget, armed state preserved)
        self.free_pages[d] -= resv_d
        self.peak_pages_reserved[d] = max(
            self.peak_pages_reserved[d],
            self.num_pages - self.free_pages[d])
        self.slot_rid[d][slot_d] = rid
        self.slot_budget[d][slot_d] = budget
        self.slot_eos[d][slot_d] = eos
        self._slot_ctx[d][slot_d] = ctx
        self._slot_armed[d][slot_d] = armed
        self._slo[d][slot_d] = slo
        self._slot_pages[d][slot_d] = resv_d
        self._slot_req[d][slot_d] = req
        self._slot_prio[d][slot_d] = prio
        self._slot_deadline[d][slot_d] = ddl
        self._req_shard[rid] = d
        if self.sharing:
            # copy-then-reindex: the landed pages are private (ref 1);
            # an armed slot's completed prompt re-registers them in the
            # DESTINATION's index first-writer-wins, so later arrivals
            # adopt from the survivor. Mid-prefill slots register at
            # prefill completion exactly like a fresh admission.
            self._slot_shared_in[d][slot_d] = []
            self._slot_own_idx[d][slot_d] = []
            if armed:
                self._register_prefix(req, d, slot_d, dst_row)
        if not armed:
            self._prefilling[s].remove((req, b))
            self._prefilling[d].append((req, slot_d))
        self._meter_migrate(s, d, float(n * self.cfg.page_size))
        self.migrations += 1
        self.migrated_pages += n

    # ------------------------------------------------------- graceful drain
    def drain(self, s: int, deadline_s: Optional[float] = None) -> int:
        """Gracefully drain shard ``s``: stop placing new work on it,
        page-copy its armed and mid-prefill slots to the survivors
        between quanta (token-for-token with the no-drain run, zero
        recompute J), then hand the empty shard to the fail_shard/rejoin
        machinery. ``deadline_s`` bounds the wait for destination
        capacity: past it the remainder force-evacuates (migrate what
        fits, fold the rest). Returns the number of slots migrated by the
        immediate first sweep."""
        if not 0 <= s < self.S:
            raise ValueError(f"shard {s} out of range for {self.S} shards")
        if self.health.is_dead(s):
            raise ValueError(f"shard {s} is dead")
        if s in self._draining:
            return 0
        if not [d for d in self.health.live
                if d != s and d not in self._draining]:
            raise FaultError(
                f"shard {s} is the last drainable shard — nowhere to "
                "migrate; fleet state is untouched")
        self._draining.add(s)
        self._drain_deadline[s] = (
            None if deadline_s is None
            else time.perf_counter() + deadline_s)
        self.drain_events += 1
        return self._drain_quantum(s)

    def _finish_drain(self, s: int) -> None:
        """The drained shard is empty: hand it to the existing shard-down
        machinery (declaration, degraded metering, audit). If the fleet
        degraded to one live shard mid-drain, the drain ABORTS instead —
        the shard stays live and placeable, loudly."""
        self._draining.discard(s)
        self._drain_deadline.pop(s, None)
        if len(self.health.live) <= 1:
            return                     # nowhere to hand off; stay live
        self.fail_shard(s)             # empty: evacuation is a no-op

    def _drain_quantum(self, s: int) -> int:
        """One drain sweep of shard ``s``: migrate every occupied slot a
        survivor can take right now; slots that don't fit stay armed and
        KEEP DECODING on ``s`` (graceful means no stalled work) until
        capacity frees. Finishes the drain when the shard empties."""
        moved = 0
        occupied = [b for b in range(self.B) if self.slot_rid[s][b] >= 0]
        tbl: Optional[np.ndarray] = None
        for b in occupied:
            d = self._pick_migration_dest(s, self._resv_for_move(s, b))
            if d is None:
                continue               # wait for capacity, keep decoding
            if tbl is None:
                # one fetch serves the sweep: migrating slot b only
                # CLEARS row b on the source (other rows untouched)
                tbl = self._fetch_tbl()
            self._migrate_slot(s, b, d, tbl[s][b])
            moved += 1
        if all(r < 0 for r in self.slot_rid[s]) and not self._prefilling[s]:
            self._finish_drain(s)
        return moved

    def _drain_sweep(self) -> int:
        """Per-quantum drain progress for every draining shard; expired
        drain deadlines force-evacuate the remainder through fail_shard
        (reachable: migrate what fits, fold the rest)."""
        moved = 0
        now = time.perf_counter()
        for s in sorted(self._draining):
            if self.health.is_dead(s):
                self._draining.discard(s)
                self._drain_deadline.pop(s, None)
                continue
            ddl = self._drain_deadline.get(s)
            if ddl is not None and now > ddl:
                self._draining.discard(s)
                self._drain_deadline.pop(s, None)
                if len(self.health.live) > 1:
                    self.fail_shard(s)
                continue
            moved += self._drain_quantum(s)
        return moved

    # ------------------------------------------------------ brownout power cap
    def power_cap(self, s: int, watts: Optional[float]) -> int:
        """Impose (or, with ``watts=None``, lift) a brownout power cap on
        shard ``s``: the shard keeps serving but sheds its lowest-
        priority slots — by page migration when a survivor has room, by
        the preemption fold otherwise — until its modeled draw fits under
        the cap, and placement refuses work that would push it back over.
        The meters re-denominate by construction: shed work's tokens and
        joules are recorded wherever the work actually runs, so the
        capped shard's metered draw tracks its real (reduced) load.
        Returns the number of slots shed immediately."""
        if not 0 <= s < self.S:
            raise ValueError(f"shard {s} out of range for {self.S} shards")
        if watts is None:
            self._power_cap[s] = None
            return 0
        idle = self.shard_profile[s].idle_w
        if watts < idle:
            raise ValueError(
                f"cap {watts:.1f} W is below shard {s}'s idle draw "
                f"{idle:.1f} W — an idle device already violates it")
        self._power_cap[s] = float(watts)
        self.power_cap_events += 1
        return self._shed_to_cap(s)

    def _modeled_draw(self, s: int) -> float:
        """Shard ``s``'s modeled electrical draw at its CURRENT load:
        the max of its decode-step and prefill-chunk power (the quantum
        interleaves both; power is a peak, not an average), idle draw
        when empty — same ``step_power`` model the meters price."""
        draw = self.shard_profile[s].idle_w
        armed = [b for b in range(self.B) if self._slot_armed[s][b]]
        if armed:
            ctx = float(np.mean([self._slot_ctx[s][b] for b in armed]))
            rep = step_energy(self.shard_profile[s],
                              decode_counts(self.workload, len(armed),
                                            max(ctx, 1.0)))
            draw = max(draw, rep.power_w)
        if self._prefilling[s]:
            counts = _prefill_phase_counts(self.workload, 1,
                                           self.cfg.prefill_chunk)
            draw = max(draw, step_energy(self.shard_profile[s],
                                         counts).power_w)
        return draw

    def _prospective_draw(self, s: int, req: Request) -> float:
        """Draw of shard ``s`` if ``req`` were placed on it: one more
        armed slot at the blended context, and its prefill chunk — the
        placement gate a capped shard applies before accepting work."""
        armed = [b for b in range(self.B) if self._slot_armed[s][b]]
        ctxs = [self._slot_ctx[s][b] for b in armed]
        ctx = max(float(np.mean(ctxs + [float(len(req.prompt))])), 1.0)
        rep = step_energy(self.shard_profile[s],
                          decode_counts(self.workload, len(armed) + 1,
                                        ctx))
        counts = _prefill_phase_counts(
            self.workload, 1,
            min(len(req.prompt), self.cfg.prefill_chunk))
        pf = step_energy(self.shard_profile[s], counts)
        return max(rep.power_w, pf.power_w, self.shard_profile[s].idle_w)

    def _shed_to_cap(self, s: int) -> int:
        """Shed slots off capped shard ``s`` lowest-priority-first until
        its modeled draw fits: migrate when a survivor has room, fold
        (ordinary preemption eviction) armed slots otherwise. Stops —
        loudly visible in stats as a still-over-cap shard — when only
        unmovable mid-prefill work remains and no survivor can take it
        (folding a slot that has emitted nothing is just a restart, which
        the next admission pass may well place back here)."""
        cap = self._power_cap[s]
        shed = 0
        tbl: Optional[np.ndarray] = None
        while cap is not None and self._modeled_draw(s) > cap:
            occupied = [b for b in range(self.B)
                        if self.slot_rid[s][b] >= 0]
            if not occupied:
                break                  # idle draw alone: nothing to shed
            victims = sorted(
                occupied,
                key=lambda b: (self._slot_prio[s][b],
                               len(self.responses[
                                   self.slot_rid[s][b]].tokens)))
            moved = False
            for b in victims:
                d = self._pick_migration_dest(s, self._resv_for_move(s, b))
                if d is not None:
                    if tbl is None:
                        tbl = self._fetch_tbl()
                    self._migrate_slot(s, b, d, tbl[s][b])
                    tbl[s][b] = -1     # row cleared by the migration
                    shed += 1
                    moved = True
                    break
                if self._slot_armed[s][b]:
                    self._evict_slot(s, b)
                    shed += 1
                    moved = True
                    break
            if not moved:
                break
        return shed

    def _absorb_admin(self, plan) -> None:
        """Absorb a scheduled admin event from a fault campaign: drains
        and power caps are declarations the engine applies mid-run,
        skipping shards where the action is moot (dead, already draining,
        or the last drainable one) — a random campaign must be
        survivable by construction, like injected shard loss."""
        s = plan.shard
        if self.health.is_dead(s) or s in self._draining:
            return
        if plan.site == "drain":
            if [d for d in self.health.live
                    if d != s and d not in self._draining]:
                self.drain(s)
            return
        prof = self.shard_profile[s]
        watts = (plan.watts if plan.watts is not None
                 else prof.idle_w + 0.5 * (prof.tdp_w - prof.idle_w))
        self.power_cap(s, max(watts, prof.idle_w))

    # -------------------------------------------------- shard-loss resilience
    # The fleet's fault domain is a whole shard, not just a launch site:
    # one lost device strands every armed slot, reservation, pinned page,
    # and index entry on it. Declaration (explicit shard_down injection or
    # the health watchdog) EVACUATES the in-flight work onto the survivors
    # through the preemption fold, invalidates every host mirror that
    # could reach the dead pool (no adoption, release, or decref ever
    # targets it again — the lane rides subsequent SPMD programs as an
    # all-sentinel idle lane), and the degraded fleet keeps serving with
    # embodied rent re-denominated onto the live devices. rejoin() scrubs
    # the pool on device and makes the shard placeable the next quantum.

    @property
    def live_shards(self) -> List[int]:
        return self.health.live

    def _site_shards(self, site: str) -> List[int]:
        """Live shards a launch at ``site`` touches THIS quantum — the
        watchdog's attribution unit. The admission reservation pass
        (page_alloc) is host-side and not attributable to one device, so
        it touches every live shard: its exhaustion still means
        FaultError, never a misdirected shard declaration."""
        live = self.health.live
        if site == "prefill_chunk":
            touched = [s for s in live if self._prefilling[s]]
        elif site == "decode_scan":
            touched = [s for s in live if any(self._slot_armed[s])]
        else:
            touched = list(live)
        return touched if touched else list(live)

    def _site_failed(self, site: str) -> None:
        """Fleet twin of ``ServingEngine._site_failed``: same backoff and
        counters, but every faulted launch also charges the shards it
        touched, and retry EXHAUSTION becomes shard loss — not a fleet-
        wide FaultError — whenever the suspect shards leave a survivor."""
        touched = self._site_shards(site)
        fails = self._backoff.get(site, (0, 0))[0] + 1
        self.fault_retries += 1
        self.fault_retry_site[site] = self.fault_retry_site.get(site, 0) + 1
        for s in touched:
            self._fault_retry_shard[(site, s)] = (
                self._fault_retry_shard.get((site, s), 0) + 1)
        suspect = self.health.record_fault(touched)
        if fails > self.cfg.max_retries:
            if suspect and len(suspect) < len(self.health.live):
                # the watchdog converts "this site would wedge the run"
                # into "these shards are lost": evacuate, clear the
                # site's backoff (the bad devices are out of the launch),
                # and keep serving on the survivors
                self._backoff.pop(site, None)
                for s in suspect:
                    # a watchdog-declared shard stopped answering — it
                    # cannot serve a page copy, so evacuation folds
                    self.fail_shard(s, reachable=False)
                return
            raise FaultError(
                f"site {site!r} failed {fails} consecutive launches "
                f"(max_retries={self.cfg.max_retries}) touching every "
                "live shard; in-flight requests are re-queued and "
                "reservations returned")
        self._backoff[site] = (fails, self._quantum + 2 ** fails)

    def _site_ok(self, site: str) -> None:
        # a successful launch breaks its shards' consecutive-fault chains
        self.health.record_ok(self._site_shards(site))
        self._backoff.pop(site, None)

    def fail_shard(self, s: int, reachable: bool = True) -> int:
        """Declare shard ``s`` dead and evacuate its in-flight work onto
        the survivors; returns the number of evacuated requests. Queued
        and deferred work is untouched (it owns nothing shard-local).
        ``reachable`` says whether the shard can still serve a page copy:
        an EXPLICIT declaration (operator action, drain hand-off) leaves
        the device answering, so in-flight slots page-migrate with zero
        recompute J where a survivor has room; watchdog declarations and
        injected shard_down pass ``reachable=False`` — a shard that
        stopped answering gets the PR-8 fold path. The choice is made
        per-request (``preempt.evacuation_mode``). Raises FaultError if
        ``s`` is the last live shard — a fleet with nowhere to evacuate
        fails loudly with state consistent."""
        if not 0 <= s < self.S:
            raise ValueError(f"shard {s} out of range for {self.S} shards")
        if self.health.is_dead(s):
            return 0
        if len(self.health.live) <= 1:
            raise FaultError(
                f"shard {s} is the last live shard — nowhere to "
                "evacuate; queue and responses are intact")
        self.health.declare_down(s, self._quantum)
        self._draining.discard(s)      # a dying shard's drain is moot
        self._drain_deadline.pop(s, None)
        self._power_cap[s] = None
        self.shard_down_events += 1
        n = self._evacuate_shard(s, reachable)
        # degraded metering: the dead device keeps depreciating, so its
        # embodied rent re-denominates onto the live devices' work
        self.meter.set_live(self.health.live)
        self.audit()
        return n

    def _evacuate_shard(self, s: int, reachable: bool = True) -> int:
        """Move every in-flight request off shard ``s`` and invalidate
        its host mirrors ATOMICALLY (one host-side pass, no quantum runs
        in between). When the shard is REACHABLE, slots a survivor can
        hold page-migrate first (zero recompute); the remainder — and
        everything, when unreachable — takes the fold/restart path. After
        the migrate pass no release/decref program ever targets the dead
        pool again: the lane rides subsequent SPMD programs all-idle."""
        migrated = 0
        if reachable:
            tbl: Optional[np.ndarray] = None
            for b in [b for b in range(self.B)
                      if self.slot_rid[s][b] >= 0]:
                emitted = len(self.responses[self.slot_rid[s][b]].tokens)
                d = self._pick_migration_dest(s, self._resv_for_move(s, b))
                if preempt.evacuation_mode(reachable, emitted,
                                           d is not None) != "migrate":
                    continue
                if tbl is None:
                    tbl = self._fetch_tbl()
                self._migrate_slot(s, b, d, tbl[s][b])
                migrated += 1
        armed = [b for b in range(self.B) if self._slot_armed[s][b]]
        if armed:
            slots = np.full((self.S, len(armed)), self.B, np.int32)
            slots[s, :len(armed)] = armed
            self.state = _DISARM_FLEET(self.mesh, self.state,
                                       jnp.asarray(slots))
        # route-invalidate the dead lane: later fleet launches stay
        # batch-shape invariant (every slot writes a row per micro-step),
        # so without a cleared block table the dead lane's still-mapped
        # slots would scatter garbage into real pages of the dead pool.
        # Clearing ONLY tbl sends those writes to the trash page; ref,
        # free, top, and every KV payload page stay bit-identical.
        do = np.zeros((self.S,), bool)
        do[s] = True
        self.caches = _QUARANTINE_FLEET(self.mesh, self.caches,
                                        jnp.asarray(do))
        # pins are residencies in the dead pool: invalidated with NO
        # decref — the resumed requests simply re-prefill on a survivor
        for rid in [r for r, (ps, _) in self._pins.items() if ps == s]:
            del self._pins[rid]
        # armed slots go through the preemption fold (emitted tokens into
        # the prompt, budget = remaining; resume prefill meters as
        # "recompute") — greedy decode depends only on context, so the
        # fail-free fleet is the token-for-token evacuation oracle. Mid-
        # prefill requests have emitted NOTHING (first token arrives with
        # the last chunk): nothing to fold, they restart from token 0.
        requeue: List[Request] = []
        for b in armed:
            req = self._slot_req[s][b]
            preempt.fold_for_resume(req, self.responses[req.rid],
                                    self.slot_budget[s][b])
            requeue.append(req)
            self._req_shard.pop(req.rid, None)
            self._clear_slot(s, b)
        for req, b in self._prefilling[s]:
            req.prefill_pos = 0
            req.prefix_keys = None
            req.shared_prefix_tokens = 0
            req.cow_pending = False
            requeue.append(req)
            self._req_shard.pop(req.rid, None)
            self._clear_slot(s, b)
        self._prefilling[s].clear()
        # class-front re-admission, reversed so the list order survives
        # the front inserts (armed before mid-prefill, FCFS within each)
        for req in reversed(requeue):
            self._enqueue(req, resume=True)
        # wholesale mirror reset: the shard owes nothing and owns nothing
        # until rejoin; the mirror anticipates the rejoin scrub so the
        # recovered shard is placeable the quantum after rejoin()
        for b in range(self.B):
            if self.slot_rid[s][b] >= 0 or self._slot_req[s][b] is not None:
                self._clear_slot(s, b)
        self._slot_pages[s] = [0] * self.B
        self.free_pages[s] = self.num_pages
        if self.sharing:
            self._prefix_index[s].clear()
            self._page_key[s].clear()
            self._page_ref[s].clear()
            self._slot_shared_in[s].clear()
            self._slot_own_idx[s].clear()
        self.shard_evacuated += len(requeue) + migrated
        return len(requeue) + migrated

    def rejoin(self, s: int) -> None:
        """Re-enter a recovered shard: one fleet program scrubs ITS pool
        to the virgin allocator state (``paged.scrub_pool`` — nothing
        from before the failure is trusted; every other lane's pool is
        bit-identical), the host mirrors are already virgin since
        declaration, and the shard is placeable from the next quantum
        with an empty prefix index."""
        if not 0 <= s < self.S:
            raise ValueError(f"shard {s} out of range for {self.S} shards")
        if not self.health.is_dead(s):
            raise ValueError(f"shard {s} is not dead")
        do = np.zeros((self.S,), bool)
        do[s] = True
        self.caches = _SCRUB_FLEET(self.mesh, self.caches,
                                   jnp.asarray(do))
        self.health.declare_up(s, self._quantum)
        self.shard_rejoins += 1
        self.meter.set_live(self.health.live)
        self.audit()

    def audit(self) -> None:
        """Production consistency check — the test-suite invariants
        promoted into the engine, run after every recovery event (and
        callable any time the fleet is between quanta):

          * per live shard, device ``ref[p]`` == live block-table
            mappings of ``p`` plus host pins (refcount exactness);
          * per live shard, ``top`` + #uniquely-mapped == num_pages
            (conservation: no page both free and mapped, none leaked);
          * the host reservation mirror never promises more free pages
            than the device free stack holds (reservations are worst-
            case, so mirror <= device top);
          * dead shards' host mirrors hold NOTHING that could reach the
            dead pool: no occupied slot, no prefilling work, no index
            entry, no pin.

        Costs one device->host fetch of the fleet allocator — recovery
        events are rare, quanta are not, so this never sits on the hot
        path. Raises RuntimeError on any violation."""
        a = jax.device_get(self.caches["paged"])
        tbl = np.asarray(a["tbl"])
        ref = np.asarray(a["ref"])
        top = np.asarray(a["top"])
        n_pg = ref.shape[1]
        for s in range(self.S):
            if self.health.is_dead(s):
                if any(r >= 0 for r in self.slot_rid[s]):
                    raise RuntimeError(
                        f"audit: dead shard {s} has occupied slots")
                if self._prefilling[s]:
                    raise RuntimeError(
                        f"audit: dead shard {s} has prefilling work")
                if self.sharing and (self._prefix_index[s]
                                     or self._page_ref[s]):
                    raise RuntimeError(
                        f"audit: dead shard {s} has live index entries")
                if any(ps == s for ps, _ in self._pins.values()):
                    raise RuntimeError(
                        f"audit: dead shard {s} holds preemption pins")
                continue
            counts = np.zeros(n_pg, np.int64)
            for b in range(self.B):
                for p in tbl[s][b]:
                    if p >= 0:
                        counts[p] += 1
            for ps, pages in self._pins.values():
                if ps == s:
                    for p in pages:
                        counts[p] += 1
            if not (ref[s] == counts).all():
                bad = np.flatnonzero(ref[s] != counts)
                raise RuntimeError(
                    f"audit: shard {s} refcount drift at pages "
                    f"{bad.tolist()}: device {ref[s][bad].tolist()} vs "
                    f"mapped+pinned {counts[bad].tolist()}")
            if int(top[s]) + int((counts > 0).sum()) != n_pg:
                raise RuntimeError(
                    f"audit: shard {s} page conservation broken: top="
                    f"{int(top[s])} + mapped={int((counts > 0).sum())} "
                    f"!= {n_pg}")
            if self.free_pages[s] > int(top[s]):
                raise RuntimeError(
                    f"audit: shard {s} reservation mirror promises "
                    f"{self.free_pages[s]} free pages but the device "
                    f"free stack holds {int(top[s])}")
        # fleet-wide page conservation (PR 10): Σ top + Σ referenced ==
        # S·P, counted from REFCOUNTS so frozen dead pools (quarantine
        # clears only tbl) and scrubbed pools satisfy it too — a
        # migration that leaked a page on either endpoint, or freed one
        # twice, breaks the sum even when each shard's local books
        # happen to balance
        fleet = int(top.sum()) + int((ref > 0).sum())
        if fleet != self.S * n_pg:
            raise RuntimeError(
                f"audit: fleet-wide page conservation broken: "
                f"sum(top) + sum(ref>0) = {fleet} != "
                f"{self.S} * {n_pg} = {self.S * n_pg}")

    # ------------------------------------------------------------- deadlines
    def _sweep_deadlines(self) -> None:
        now = time.perf_counter()

        def expired(r: Request) -> bool:
            return (r.deadline_s is not None
                    and now - r.t_submit > r.deadline_s)

        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._cancel(req.rid, "deadline")
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
            slots = np.full((self.S, 1), self.B, np.int32)
            slots[s, 0] = b
            self.state = _DISARM_FLEET(self.mesh, self.state,
                                       jnp.asarray(slots))
            rid = self.slot_rid[s][b]
            self._clear_slot(s, b)
            self._release_slots([(s, b)])
            self._cancel(rid, "deadline")

    # ------------------------------------------------------------ admission
    def _shard_score(self, req: Request, s: int, resv: int,
                     shared_tokens: int) -> Tuple[bool, float]:
        """Marginal gCO2 of serving ``req`` on shard ``s`` right now:
        phase-specific operational J at the shard's profile priced at its
        region's CURRENT CI, plus embodied rent over the request's page
        reservation (prefix hits discount both the recomputed prefill
        tokens and the reserved pages). Returns (slo_ok, grams)."""
        region = self.shard_region[s]
        ci = (ci_at_hour(region, self._clock_hours() % 24.0)
              if self.cfg.use_diurnal_ci else region.ci_g_per_kwh)
        g, t_est = marginal_request_g(
            self._slices[s], self.workload,
            prefill_tokens=max(len(req.prompt) - shared_tokens, 0),
            decode_tokens=max(req.max_new_tokens, 1),
            resv_frac=resv / self.num_pages, ci=ci,
            n_devices=self.cfg.n_devices)
        slo_ok = req.slo_s is None or t_est <= req.slo_s
        return slo_ok, g

    def _place(self, req: Request):
        """Placement policy. Eligibility is policy-INDEPENDENT: shards
        with a free slot whose pool fits the request's reservation.

        ``routing="free_pages"`` (baseline): longest resident prefix of
        the prompt (sharing only), then most free pages, then lowest
        shard id.

        ``routing="carbon"``: lowest marginal gCO2 (``_shard_score``),
        SLO-feasible shards strictly first; exact carbon ties fall back
        to the free_pages key — so a homogeneous fleet (equal profiles,
        regions, and prefix state score identically) reproduces the
        baseline's placement bit-for-bit, which is the parity oracle's
        lever. Compute-rich shards win prefill-heavy requests (their
        marginal prefill J is lower), memory-rich amortized shards win
        decode-heavy ones (lower TDP × longer residency beats idle-power
        burn), and low-CI regions discount everything — GreenLLM's
        disaggregation as a one-line scoring rule.

        SLO-PINNED requests (``req.slo_s`` set) are the exception: they
        keep the baseline's load-first ordering among SLO-feasible
        shards, with marginal gCO2 demoted to a tie-break below free
        pages. Chasing the greenest shard concentrates work, and
        concentration queues prefills — a latency tax the pinned class
        by definition cannot pay — so only flexible (unpinned) work
        follows carbon, which is where nearly all the grams are anyway
        once the deferral queue batches it into the CI valley.

        Returns (shard, resv, (n_pg, phys, first_tok)) or None if the
        head can't be placed."""
        L = len(req.prompt)
        ps = self.cfg.page_size
        n_total = paged.pages_needed(L + max(req.max_new_tokens - 1, 0), ps)
        carbon = self.cfg.routing == "carbon"
        best = None
        for s in range(self.S):
            if self.health.is_dead(s) or s in self._draining:
                continue               # degraded fleet: dead or draining
            if not self.free_slots(s):  # shards take no new placements
                continue
            if (self._power_cap[s] is not None
                    and self._prospective_draw(s, req)
                    > self._power_cap[s]):
                continue               # capped shard: refuse work that
                                       # would push its draw back over
            if self.sharing:
                n_pg, phys = self._match_prefix(req, s)
                first_tok = min(n_pg * ps, L - 1)
                resv = n_total - first_tok // ps
                share = (n_pg, phys, first_tok)
            else:
                resv, share = n_total, (0, [], 0)
            if resv > self.free_pages[s]:
                continue
            key = (share[0], self.free_pages[s], -s)
            if carbon:
                slo_ok, g = self._shard_score(req, s, resv, share[2])
                if req.slo_s is None:
                    key = (slo_ok, -g) + key
                else:
                    # latency-pinned: load-first among SLO-feasible
                    # shards, greener shard only breaks free-page ties
                    key = (slo_ok, share[0], self.free_pages[s], -g, -s)
            if best is None or key > best[0]:
                best = (key, s, resv, share)
        return None if best is None else best[1:]

    def _admit(self) -> int:
        """FCFS head-of-queue admission onto the best shard: claim a slot
        + a worst-case page reservation on that shard, queue the request
        for chunked prefill there, and reset all newly claimed slots with
        ONE fleet-wide begin program. Never-fits requests (prompt + budget
        exceeding a shard's whole pool or block table) are rejected up
        front — per-shard pools mean per-shard capacity limits."""
        with trace.span("admit") as sp:
            take = self._admit_requests()
            trace.tag(sp, "rids", (r.rid for r in take))
        return len(take)

    def _admit_requests(self) -> List[Request]:
        """The admission pass of ``_admit``; returns the requests taken."""
        if self._over_budget() and self.active > 0:
            return []
        if self.queue:
            # the fleet's reservation pass sits behind the same
            # ``page_alloc`` fault site as the single-device engine's; the
            # injection point is BEFORE any claim, so a fault needs no
            # rollback — the whole pass simply didn't run this quantum
            if not self._site_ready("page_alloc"):
                return []
            try:
                self._inject("page_alloc")
            except InjectedFault:
                self._site_failed("page_alloc")
                return []
            self._site_ok("page_alloc")
        admitted: List[Tuple[Request, int, int]] = []
        adoptions: List[Tuple[Request, int, int, Tuple]] = []
        while self.queue:
            req = self.queue[0]
            L = len(req.prompt)
            n_total = paged.pages_needed(
                L + max(req.max_new_tokens - 1, 0), self.cfg.page_size)
            if n_total > self.max_pages_slot or n_total > self.num_pages:
                self.queue.popleft()
                self._reject(req)
                continue
            self._apply_pressure_clamp(req)
            placed = self._place(req)
            if placed is None:
                if self._try_preempt(req):
                    continue           # a lower-class slot just yielded
                break                  # keep waiting (FCFS, no overtaking)
            s, resv, share = placed
            self.queue.popleft()
            slot = self.free_slots(s)[0]
            self.free_pages[s] -= resv
            self.peak_pages_reserved[s] = max(
                self.peak_pages_reserved[s],
                self.num_pages - self.free_pages[s])
            self.slot_rid[s][slot] = req.rid
            self.slot_budget[s][slot] = 0    # armed after the last chunk
            self.slot_eos[s][slot] = req.eos_id
            self._slot_ctx[s][slot] = 0.0
            self._slo[s][slot] = req.slo_s
            self._slot_pages[s][slot] = resv
            self._slot_req[s][slot] = req
            self._slot_prio[s][slot] = req.priority
            self._slot_deadline[s][slot] = req.deadline_s
            self._stamp_admit(req)
            self._req_shard[req.rid] = s
            self.shard_requests[s] += 1
            req.prefill_pos = 0
            self._prefilling[s].append((req, slot))
            admitted.append((req, s, slot))
            if self.sharing:
                adoptions.append((req, s, slot, share))
        if not admitted:
            return []
        # one fleet-wide slot-reset program: per-shard slot lists padded
        # with the sentinel id B (out-of-range scatters drop -> idle lanes
        # run the same program and write nothing)
        per_shard: List[List[int]] = [[] for _ in range(self.S)]
        for _, s, slot in admitted:
            per_shard[s].append(slot)
        k = max(len(v) for v in per_shard)
        slots = np.full((self.S, k), self.B, np.int32)
        for s, v in enumerate(per_shard):
            slots[s, :len(v)] = v
        self.caches = _BEGIN_FLEET(self.mesh, self.caches,
                                   jnp.asarray(slots))
        if self.sharing:
            for req, s, slot, (n_pg, phys, first_tok) in adoptions:
                self._adopt_prefix(req, s, slot, n_pg, phys, first_tok)
                # adopt-then-release: the resumed request now holds its
                # pinned prefix through the ordinary index increfs
                if req.rid in self._pins:
                    self._drop_pin(req.rid)
        return [req for req, _, _ in admitted]

    def _adopt_prefix(self, req: Request, s: int, slot: int, n_pg: int,
                      phys: List[int], first_tok: int) -> None:
        """Shard-local adoption: incref the matched run into the slot's
        block table on shard ``s`` only — every other lane of the fleet
        program sees the sentinel slot id and writes nothing."""
        self._slot_shared_in[s][slot] = []
        self._slot_own_idx[s][slot] = []
        if n_pg == 0:
            return
        slot_a = np.full((self.S,), self.B, np.int32)
        slot_a[s] = slot
        pages = np.full((self.S, self.max_pages_slot), -1, np.int32)
        pages[s, :n_pg] = phys
        n_sh = np.zeros((self.S,), np.int32)
        n_sh[s] = n_pg * self.cfg.page_size
        st = np.zeros((self.S,), np.int32)
        st[s] = first_tok
        self.caches = _MAP_PREFIX_FLEET(
            self.mesh, self.caches, jnp.asarray(slot_a), jnp.asarray(pages),
            jnp.asarray(n_sh), jnp.asarray(st))
        req.prefill_pos = first_tok
        req.shared_prefix_tokens = first_tok
        # whole prompt shared -> the first chunk will copy-on-write; the
        # per-shard packer admits one such row per launch (pack_chunks)
        req.cow_pending = first_tok < n_pg * self.cfg.page_size
        for p in phys:
            self._page_ref[s][p] += 1
        self._slot_shared_in[s][slot] = list(phys)
        self.prefix_hit_tokens += first_tok
        self.prefix_shared_requests += 1

    # ------------------------------------------------------ chunked prefill
    def _prefill_quantum(self) -> int:
        """One fleet-wide prefill launch per quantum: EVERY shard's FCFS
        head chunk (packed up to ``prefill_pack`` requests per shard when
        their combined tokens fit ``prefill_chunk``) rides one program.
        Shards with nothing to prefill run sentinel lanes. Returns the
        number of launches (0 or 1)."""
        if not self._site_ready("prefill_chunk"):
            return 0                   # backing off a faulted chunk launch
        C = self.cfg.prefill_chunk
        with trace.span("prefill.prepare"):
            packs = [pack_chunks(self._prefilling[s], C,
                                 self.cfg.prefill_pack)
                     for s in range(self.S)]
            n = max(len(p) for p in packs)
            if n == 0:
                return 0
            try:
                self._inject("prefill_chunk")
            except InjectedFault:
                # nothing launched: every shard's packed requests are
                # still at the head of its _prefilling deque, prefill_pos
                # untouched
                self._site_failed("prefill_chunk")
                return 0
            self._site_ok("prefill_chunk")
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
            first, rows, self.caches = _CHUNK_FLEET(
                self.model, self.mesh, self.params, self.caches,
                jnp.asarray(tokens), jnp.asarray(mask), jnp.asarray(slots),
                self._next_keys(), vocab=self.model.cfg.vocab,
                temperature=self.cfg.temperature,
                page_size=self.cfg.page_size, sharing=self.sharing)
        self.prefill_chunks += 1
        finished: List[Tuple[int, int]] = []   # (shard, row)
        for s, pk in enumerate(packs):
            done = 0
            for i, (req, slot, pos0, piece) in enumerate(pk):
                req.prefill_pos += len(piece)
                if self.sharing and piece:
                    shared = self._slot_shared_in[s].get(slot) or []
                    lp = pos0 // self.cfg.page_size
                    if lp < len(shared) and self._page_ref[s][shared[lp]] > 1:
                        self._page_ref[s][shared[lp]] -= 1
                        self._slot_shared_in[s][slot] = shared[:lp]
                    req.cow_pending = False   # its CoW (if any) just ran
                if req.prefill_pos >= len(req.prompt):
                    finished.append((s, i))
                    done += 1
            assert done in (0, len(pk)), "packed tail finished before head"
            for _ in range(done):
                self._prefilling[s].popleft()
        if not finished:
            return 1                   # intermediate chunks: no host sync
        # ONE first-token fetch for every request finishing fleet-wide
        with trace.span("prefill.fetch"):
            first_h, rows_h = jax.device_get((first, rows))
        first_h, rows_h = np.asarray(first_h), np.asarray(rows_h)
        self.prefill_batches += 1
        with trace.span("prefill.finish") as sp:
            trace.tag(sp, "rids", (packs[s][i][0].rid for s, i in finished))
            self._finish_prefill(packs, finished, first_h, rows_h)
        return 1

    def _finish_prefill(self, packs, finished: List[Tuple[int, int]],
                        first_h: np.ndarray, rows_h: np.ndarray) -> None:
        """Requests whose last chunk just ran, fleet-wide: prefix index,
        metering, first tokens, one fleet-wide arm, releases."""
        now = time.perf_counter()
        arm: List[Tuple[int, int, int, int, int]] = []
        released: List[Tuple[int, int]] = []
        for s, i in finished:
            req, slot, _, _ = packs[s][i]
            if self.sharing:
                self._register_prefix(req, s, slot, rows_h[s, i])
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
            # resumed requests EOS-check their recomputed first token —
            # it is logically a mid-decode emission (engine.py comment)
            eos_hit = (req.preemptions > 0 and req.eos_id is not None
                       and tok == req.eos_id)
            if budget <= 0 or eos_hit:
                resp.finished = True   # prefill token was the whole budget
                resp.finish_reason = "eos" if eos_hit else "length"
                self.slot_rid[s][slot] = -1
                self._slo[s][slot] = None
                self._slot_req[s][slot] = None
                self._slot_prio[s][slot] = 0
                self._slot_deadline[s][slot] = None
                released.append((s, slot))
                continue
            eos = -1 if req.eos_id is None else req.eos_id
            arm.append((s, slot, tok, budget, eos))
            self.slot_budget[s][slot] = budget
            self._slot_ctx[s][slot] = float(len(req.prompt))
            self._slot_armed[s][slot] = True
        if arm:
            k = max(sum(1 for a in arm if a[0] == s) for s in range(self.S))
            slots_a = np.full((self.S, k), self.B, np.int32)
            firsts = np.zeros((self.S, k), np.int32)
            budgets = np.zeros((self.S, k), np.int32)
            eos_ids = np.full((self.S, k), -1, np.int32)
            fill = [0] * self.S
            for s, slot, tok, budget, eos in arm:
                slots_a[s, fill[s]] = slot
                firsts[s, fill[s]] = tok
                budgets[s, fill[s]] = budget
                eos_ids[s, fill[s]] = eos
                fill[s] += 1
            self.cur_tokens, self.state = _ARM_FLEET(
                self.mesh, self.cur_tokens, self.state,
                jnp.asarray(slots_a), jnp.asarray(firsts),
                jnp.asarray(budgets), jnp.asarray(eos_ids))
        self._release_slots(released)

    # --------------------------------------------------------------- decode
    def _decode_chunk(self, max_steps: int) -> bool:
        """One fused chunk of up to ``sync_every`` micro-steps for EVERY
        armed slot on EVERY shard — one program, one host sync on the
        stacked (S, n, B) token/emission matrices for the whole fleet.
        Returns whether a chunk actually launched (False while the
        ``decode_scan`` site backs off a fault)."""
        if not self._site_ready("decode_scan"):
            return False
        try:
            self._inject("decode_scan")
        except InjectedFault:
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
             emit_mat) = _FUSED_FLEET(
                self.model, self.mesh, self.params, self.caches,
                self.cur_tokens, self.state, self._next_keys(), n_steps=n,
                temperature=self.cfg.temperature,
                page_size=self.cfg.page_size)
        with trace.span("decode.fetch"):
            tok_h, emit_h = jax.device_get((tok_mat, emit_mat))
        self.decode_chunks += 1
        self.peak_active = max(self.peak_active, self.active)
        with trace.span("decode.emit"):
            released = self._emit_decoded(n, tok_h, emit_h)
        self._release_slots(released)
        return True

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
                        self.slot_rid[s][b] = -1
                        self._slot_armed[s][b] = False
                        self._slo[s][b] = None
                        self._slot_req[s][b] = None
                        self._slot_prio[s][b] = 0
                        self._slot_deadline[s][b] = None
                        released.append((s, int(b)))
            if emitted_any:
                self._steps += 1
        return released

    def _resolve_stall(self) -> None:
        """Fleet twin of ``ServingEngine._resolve_stall``: spill pins or
        fail the unplaceable head."""
        live = self.health.live
        if self._pins and any(self.free_pages[s] < self.num_pages
                              for s in live):
            for rid in list(self._pins):
                self._drop_pin(rid)
            return
        if all(self.free_pages[s] == self.num_pages for s in live):
            # nothing running, every LIVE shard's whole pool free, and
            # placement still refused the head: it can never fit on the
            # (possibly degraded) fleet — per-shard capacity is identical,
            # so never-fits is the same verdict degraded or whole
            self._reject(self.queue.popleft())
        else:
            raise RuntimeError(        # unreachable: release returns
                "admission stalled with no active work — leaked "
                "page reservation")

    def step(self, max_steps: int = 10_000) -> bool:
        """One FLEET scheduling quantum (same contract as the single-
        device ``ServingEngine.step``): deferral release, deadline sweep,
        admission, one fleet-wide chunk launch, one fused scan. The fleet
        clock then advances by the SLOWEST shard's modeled time this
        quantum — shards run in parallel, so that max is the quantum's
        wall time (summing per-shard times would run the diurnal day S
        times too fast)."""
        self._quantum += 1
        with trace.quantum(self._quantum):
            return self._fleet_quantum(max_steps)

    def _fleet_quantum(self, max_steps: int) -> bool:
        ev0 = self.shard_down_events
        mig0 = self.migrations
        if self.faults is not None:
            # injected shard loss fires at the quantum boundary, BEFORE
            # any launch — the engine absorbs it (evacuate + degrade),
            # it never surfaces as an exception. Injection models a
            # crashed device: NOT reachable, evacuation folds.
            for s in self.faults.shard_down_fires(self._quantum,
                                                  self._run_q0):
                if not self.health.is_dead(s):
                    self.fail_shard(s, reachable=False)
            # scheduled admin events (drain / power_cap campaigns)
            for plan in self.faults.admin_fires(self._quantum,
                                                self._run_q0):
                self._absorb_admin(plan)
        released = self._release_deferred() if self.deferred else 0
        if self._has_deadlines:
            self._sweep_deadlines()
        if self._draining:
            self._drain_sweep()
        for s in self.health.live:
            # brownout re-enforcement: load that grew back over a live
            # cap (e.g. a slot's context deepened) sheds again
            if self._power_cap[s] is not None:
                self._shed_to_cap(s)
        admitted = self._admit()
        chunks = self._prefill_quantum()
        decoded = self._decode_chunk(max_steps) if self.decoding else False
        dt = max(self._q_time)
        if dt > 0.0:
            self.clock.hours += dt / 3600.0
            self._q_time = [0.0] * self.S
        # a recovery event IS progress: the watchdog can declare a shard
        # dead inside a launch handler (after this quantum's admission
        # pass), and the evacuees it re-queued must reach the next
        # admission pass — not be misread as an unplaceable head
        return bool(released or admitted or chunks or decoded
                    or self.shard_down_events != ev0
                    or self.migrations != mig0)

    def run(self, max_steps: int = 10_000) -> List[Response]:
        """Drive until the queue drains and every shard's slots finish.
        Each loop iteration is one FLEET quantum: admission claims slots
        and per-shard reservations, one chunk launch advances every
        shard's prefilling head, one fused scan advances every armed slot
        everywhere — still exactly one decode sync per quantum."""
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
        """Fleet-wide device->host sync points: one per decode chunk plus
        one per first-token fetch — S shards, the same sync count as ONE
        fused engine (that is the scaling claim)."""
        return self.decode_chunks + self.prefill_batches

    def stats(self) -> Dict[str, float]:
        t = self.meter.totals
        pf = self.meter.phase("prefill")
        dc = self.meter.phase("decode")
        finished = [r for r in self.responses.values() if r.finished]
        lat = [r.prefill_s + r.decode_s for r in finished]
        p50 = float(np.median(lat)) if lat else 0.0
        p99 = float(np.percentile(lat, 99)) if len(lat) > 1 else p50
        out: Dict[str, float] = {
            "shards": self.S,
            "paged": 1.0,
            "page_size": self.cfg.page_size,
            "pages_total": self.num_pages * self.S,
            "pages_per_shard": self.num_pages,
            "peak_pages_reserved": sum(self.peak_pages_reserved),
            "free_pages": sum(self.free_pages),
            "peak_kv_rows_reserved":
                sum(self.peak_pages_reserved) * self.cfg.page_size,
            "chunked": 1.0,
            "prefill_chunk": self.cfg.prefill_chunk,
            "prefill_chunks": self.prefill_chunks,
            "requests": len(self.responses),
            "peak_active": self.peak_active,
            "p50_latency_s": p50,
            "p99_latency_s": p99,
            "steps": self._steps,
            "shard_steps": self.shard_steps,
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
            "embodied_fraction":
                (t.embodied_g / t.total_g) if t.total_g else 0.0,
            # multi-criteria impact ledger (PR 9) — fleet totals are the
            # exact sum of the per-shard rows below
            # (docs/METHODOLOGY.md#the-impact-ledger)
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
        }
        if self.sharing:
            out.update({
                "prefix_sharing": 1.0,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_shared_requests": self.prefix_shared_requests,
            })
        # heterogeneous fleet: routing policy + per-shard attribution
        # (each shard metered at its own profile × region CI; the fleet
        # totals above are the exact sum of these rows)
        out["carbon_routing"] = 1.0 if self.cfg.routing == "carbon" else 0.0
        for s in range(self.S):
            st = self.meters[s].totals
            out[f"shard{s}_requests"] = self.shard_requests[s]
            out[f"shard{s}_tokens"] = st.tokens
            out[f"shard{s}_energy_j"] = st.energy_j
            out[f"shard{s}_carbon_g"] = st.total_g
            out[f"shard{s}_g_per_token"] = st.g_per_token
            out[f"shard{s}_water_l"] = st.water_l
            out[f"shard{s}_primary_mj"] = st.primary_mj
            out[f"shard{s}_adpe_mg"] = st.adpe_mg
            out[f"shard{s}_dead"] = 1.0 if self.health.is_dead(s) else 0.0
        # shard-loss resilience: watchdog state + recovery counters
        out.update({
            "live_shards": len(self.health.live),
            "dead_shards": self.S - len(self.health.live),
            "shard_down_events": self.shard_down_events,
            "shard_evacuated": self.shard_evacuated,
            "shard_rejoins": self.shard_rejoins,
        })
        # live KV-page migration: drain/brownout counters + the migrate
        # phase's energy (its own meter phase, so prefill/decode J per
        # token stay invariant — docs/METHODOLOGY.md)
        mg = self.meter.phase("migrate")
        out.update({
            "migrations": self.migrations,
            "migrated_pages": self.migrated_pages,
            "drain_events": self.drain_events,
            "migrate_j": mg.energy_j,
            "power_cap_events": self.power_cap_events,
        })
        for s in range(self.S):
            if self._power_cap[s] is not None:
                out[f"shard{s}_power_cap_w"] = self._power_cap[s]
        # front door (same keys as the single-device engine)
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
        # fault attribution: per-site, and per (site, shard) so a bench
        # or operator can see WHICH device the retries clustered on
        for site, n in sorted(self.fault_retry_site.items()):
            out[f"fault_retries_{site}"] = n
        for (site, s), n in sorted(self._fault_retry_shard.items()):
            out[f"shard{s}_fault_retries_{site}"] = n
        for p, waits in sorted(self._wait_samples.items()):
            out[f"queue_wait_p50_s_class_{p}"] = float(np.median(waits))
            out[f"queue_wait_p99_s_class_{p}"] = (
                float(np.percentile(waits, 99)) if len(waits) > 1
                else float(np.median(waits)))
        for p, n_shed in sorted(self._shed_by_class.items()):
            out[f"shed_class_{p}"] = n_shed
        return out
