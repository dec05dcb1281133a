"""The fleet side of the serving engine: a mesh of S data-parallel shards
behind one queue, with per-shard page pools.

The paper's operational-carbon model (Eq. 1) prices serving by wall-clock
energy at a region's carbon intensity, so once the single-device hot path
is fused the remaining lever is aggregate throughput per host overhead —
and fleet placement (GreenLLM's disaggregated fleets, EcoServe's
carbon-aware placement) presupposes an engine whose step, KV pool and
scheduler are mesh-native. ``ServingEngine`` (serving/engine.py) is that
engine for any ``cfg.shards`` = S >= 1; a single device is S = 1. This
module holds what exists only across shards:

  * ``over_mesh``: the shard_map form of a single-device program. Every
    device-side array of a fleet gains a LEADING shard axis — per-leaf
    page pools ``(S, Hkv, num_pages+1, ps, hd)``, the block table
    ``(S, B, max_pages)``, slot state ``(S, B)``, allocator free stacks
    ``(S, num_pages)`` — laid out by ``repro.models.attention.
    serving_cache_axes`` and resolved through ``repro.sharding.rules.
    SERVING_RULES`` (shard -> the mesh's data axis). The mesh program's
    body is the unmodified single-device function on the local shard, so
    one host sync per ``sync_every`` micro-steps serves the whole fleet,
    and alloc-on-write stays shard-local: no cross-device traffic on the
    decode hot path;
  * ``FleetOps``, the engine's fleet side: the launcher (``_launch``:
    a program's single-device jit entry on the unstacked state at S = 1,
    its ``over_mesh`` form otherwise), placement (most free pages or
    marginal gCO2, longest resident prefix first — the prefix index is
    PER SHARD, so adoption never crosses shards), deferral to the
    greenest window of the fleet's regions, live KV-page migration,
    graceful drain, brownout power caps, and shard-loss resilience
    (``fail_shard`` / ``rejoin`` / ``audit``).

Idle lanes inside a fleet-wide program are expressed with the sentinel
slot id ``B`` (one past the per-shard slot range): JAX drops out-of-range
scatters and clamps out-of-range gathers, so a lane whose ``slots`` row is
all-sentinel (plus an all-zero token mask) runs the same traced program as
a busy lane while provably writing nothing but its own trash page — the
fleet step stays a single SPMD program with no per-lane control flow.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.energy import (decode_counts, migrate_counts, prefill_counts,
                               step_energy)
from repro.core.forecast import CIForecaster
from repro.core.hardware import HardwareProfile, get_profile
from repro.core.intensity import Region, ci_at_hour, ci_trace, get_region
from repro.core.meter import CarbonMeter, FleetMeterView, SharedClock
from repro.core.scheduler import (FleetSlice, marginal_request_g,
                                  migration_cost_g)
from repro.launch.mesh import make_serving_mesh
from repro.serving import paged, preempt, sampling
from repro.serving.faults import FaultError, HealthMonitor
from repro.serving.request import Request
from repro.sharding.rules import serving_shardings

_SHARD = P("data")                     # leading fleet axis of every leaf


def _lane(tree):
    """Local (1, ...) shard_map view -> the single-shard (...) tree."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _unlane(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def over_mesh(fn, name: str, runs_model: bool = False):
    """The shard_map form of the single-device program ``fn``, named
    ``name`` (the device trace names a program by it): each shard runs
    ``fn`` unmodified on its own lane. It takes ``(mesh, *lanes)``, or
    ``(model, mesh, params, *lanes)`` where ``runs_model``, with
    ``params`` replicated; every lane argument and every output carries
    the leading shard axis. Keyword arguments pass through to ``fn``."""
    def sharded(mesh, fixed, head, lanes, static):
        n = len(fixed)

        def body(*xs):
            return _unlane(fn(*head, *xs[:n], *_lane(xs[n:]), **static))

        return shard_map(body, mesh=mesh,
                         in_specs=(P(),) * n + (_SHARD,) * len(lanes),
                         out_specs=_SHARD, check_vma=False)(*fixed, *lanes)

    if runs_model:
        def program(model, mesh, params, *lanes, **static):
            return sharded(mesh, (params,), (model,), lanes, static)
    else:
        def program(mesh, *lanes, **static):
            return sharded(mesh, (), (), lanes, static)
    program.__name__ = program.__qualname__ = name
    return program


def stack_lanes(rows: Sequence[Sequence[int]], fill: int) -> np.ndarray:
    """Per-shard rows as one (S, k) int32 array, short rows padded with
    ``fill`` (the sentinel slot id B drops out of every scatter)."""
    out = np.full((len(rows), max(len(r) for r in rows)), fill, np.int32)
    for s, r in enumerate(rows):
        out[s, :len(r)] = r
    return out


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


_MIGRATE_FLEET = jax.jit(_migrate_fleet, static_argnums=(0,))
# declaration-time route invalidation: lanes whose ``do`` flag is set get
# their block table cleared (``paged.quarantine_table``) so the batch-
# shape-invariant writes of later fleet launches fall into the trash page;
# refcounts, free stack and KV payloads stay untouched
_QUARANTINE_FLEET = jax.jit(over_mesh(paged.quarantine_table,
                                      "_quarantine_fleet"),
                            static_argnums=(0,))
# rejoin scrub: lanes whose ``do`` flag is set rebuild their pool to the
# virgin post-``paginate_cache`` state (``paged.scrub_pool``); every other
# lane's pool is returned bit-identical
_SCRUB_FLEET = jax.jit(over_mesh(paged.scrub_pool, "_scrub_fleet"),
                       static_argnums=(0,))


class FleetOps:
    """The fleet side of ``ServingEngine``, written once for any shard
    count: the per-shard accounting and device state, the launcher that
    runs a program by the shard count, placement, temporal deferral over
    the fleet's regions, live KV-page migration, graceful drain, brownout
    power caps and shard-loss resilience. A mixin: the state it reads
    (host mirrors indexed by shard, the device trees) is the engine's."""

    def _init_fleet(self) -> None:
        """The per-shard accounting, placement, deferral and resilience
        state; raises ValueError for per-shard lists of the wrong
        length."""
        S, cfg = self.S, self.cfg
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
        # ---- heterogeneous fleet: per-shard hardware profile + region.
        # The MODEL runs identically on every shard (one SPMD program);
        # heterogeneity lives entirely in attribution and placement.
        self.shard_profile: List[HardwareProfile] = [
            get_profile(n) for n in prof_names]
        self.shard_region: List[Region] = [
            get_region(r) for r in region_names]
        # one meter PER SHARD at that shard's profile × region CI, all on
        # one clock. One shard's meter advances the clock with each
        # record; S parallel shards must not advance the day S times, so
        # a fleet's clock advances once per quantum by the slowest
        # shard's modeled time (step()). Fleet totals are the exact sum
        # of the per-shard attribution via FleetMeterView; each shard's
        # embodied amortization covers ITS cfg.n_devices devices, so the
        # installed hardware is charged exactly once across the S meters.
        self.clock = SharedClock()
        self.meters: List[CarbonMeter] = [
            CarbonMeter(self.shard_profile[s], self.shard_region[s],
                        lifetime_years=cfg.lifetime_years,
                        n_devices=cfg.n_devices,
                        use_diurnal_ci=cfg.use_diurnal_ci,
                        clock=self.clock, advances_clock=S == 1)
            for s in range(S)]
        self.meter = self.meters[0] if S == 1 else FleetMeterView(self.meters)
        self._q_time = [0.0] * S       # per-shard modeled time this quantum
        # the carbon router scores shards through the SAME FleetSlice /
        # marginal-g machinery as the offline CIDirectedScheduler — one
        # scoring core, no drift between the table and the serving loop
        self._slices: List[FleetSlice] = [
            FleetSlice(self.shard_profile[s], self.shard_region[s],
                       lifetime_years=cfg.lifetime_years)
            for s in range(S)]
        self.shard_requests = [0] * S  # placements per shard (stats)
        self._req_shard: Dict[int, int] = {}
        # shard_steps counts (micro-step, shard) pairs with >= 1 emission
        # — the honest comparand for aggregate throughput claims
        self.shard_steps = 0
        # ---- shard-loss resilience: the fleet's health watchdog. A shard
        # is declared dead by explicit shard_down injection or when
        # max_retries consecutive faulted launches touched it while a
        # survivor exists; declaration EVACUATES its in-flight work onto
        # the live shards and invalidates every host mirror that could
        # reach the dead pool. See fail_shard()/rejoin()/audit().
        self.health = HealthMonitor(S, cfg.max_retries)
        self.shard_down_events = 0
        self.shard_evacuated = 0       # requests moved off dead shards
        self.shard_rejoins = 0
        # per-(site, shard) retry counters: every faulted launch charges
        # the shards it touched (stats() splits fault_retries out by both)
        self._fault_retry_shard: Dict[Tuple[str, int], int] = {}
        # ---- live KV-page migration: graceful drain, reachable
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
        # temporal deferral: held requests own NOTHING (no slot, no pages,
        # no queue position) until the CI forecaster's greenest window
        # opens at the virtual clock, or deadline pressure forces release
        self.deferred: deque = deque()
        self.deferred_rids: set = set()
        self._defer_release_h: Dict[int, float] = {}
        self._forecasters: Dict[str, CIForecaster] = {}
        self.deferred_total = 0
        self.deferred_released = 0
        self.deferred_forced = 0

    def _place_over_mesh(self, state, params, mesh):
        """One shard's device state and the params as the engine holds
        them: as they are at S = 1; on a fleet, the state stacked S-wide
        with every leaf placed leading-axis over ``mesh`` (None = the
        first S devices; attention.py declares the logical axes,
        rules.py resolves them) and the params replicated. The stack runs
        inside one program whose outputs are already sharded, so each
        device materializes only its own lane — an eager broadcast would
        first stage all S copies of the pool on one device."""
        S = self.S
        self.mesh = None
        if S == 1:
            return state, params
        self.mesh = mesh if mesh is not None else make_serving_mesh(S)

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (S,) + x.shape), tree)

        out = serving_shardings(self.mesh, jax.eval_shape(stack, state))
        params = jax.device_put(params, jax.tree_util.tree_map(
            lambda _: NamedSharding(self.mesh, P()), params))
        return jax.jit(stack, out_shardings=out)(state), params

    # ------------------------------------------------------- device programs
    def _launch(self, one, fleet, trees, lanes=(), runs_model=False,
                **static):
        """Run a device program on this engine's state: ``one``, the
        single-device jit entry, on the unstacked shard at S = 1, else
        ``fleet``, its shard_map form, over the mesh. ``trees`` are the
        device trees it takes first; ``lanes`` are host arrays with a
        leading shard axis, of which S = 1 passes shard 0's. A program
        that ``runs_model`` also takes (model, params) first and a PRNG
        key last: at S = 1 the split of the engine key itself, so sampled
        tokens hold, else that key split once per shard."""
        args = [*trees, *(jnp.asarray(x[0] if self.S == 1 else x)
                          for x in lanes)]
        if runs_model:
            key = self._next_key()
            args.append(key if self.S == 1
                        else jax.random.split(key, self.S))
        if self.S == 1:
            head = (self.model, self.params) if runs_model else ()
            return one(*head, *args, **static)
        head = ((self.model, self.mesh, self.params) if runs_model
                else (self.mesh,))
        return fleet(*head, *args, **static)

    def _launch_alloc(self, one, fleet, *lanes) -> None:
        """``_launch`` an allocator program on the pool's ``paged`` tree."""
        self.caches = dict(self.caches)
        self.caches["paged"] = self._launch(one, fleet,
                                            (self.caches["paged"],), lanes)

    def _fetch(self, tree):
        """Program outputs on the host, each with its leading shard axis
        (an unstacked S = 1 output gains it)."""
        out = jax.device_get(tree)
        if self.S == 1:
            out = jax.tree_util.tree_map(lambda x: np.asarray(x)[None], out)
        return out

    def _lane_of(self, x: jax.Array, s: int) -> jax.Array:
        """Shard ``s``'s part of a program output left on device."""
        return x if self.S == 1 else x[s]

    def _placeable(self) -> List[int]:
        """Shards that take new placements: live and not draining."""
        return [s for s in self.health.live if s not in self._draining]

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
            self._record(s, "migrate", counts)

    # ------------------------------------------------------------ placement
    def _shard_score(self, req: Request, s: int, resv: int,
                     shared_tokens: int) -> Tuple[bool, float]:
        """Marginal gCO2 of serving ``req`` on shard ``s`` right now:
        phase-specific operational J at the shard's profile priced at its
        region's CURRENT CI, plus embodied rent over the request's page
        reservation (prefix hits discount both the recomputed prefill
        tokens and the reserved pages). Returns (slo_ok, grams)."""
        region = self.shard_region[s]
        ci = (ci_at_hour(region, self.clock.hours % 24.0)
              if self.cfg.use_diurnal_ci else region.ci_g_per_kwh)
        g, t_est = marginal_request_g(
            self._slices[s], self.workload,
            prefill_tokens=max(len(req.prompt) - shared_tokens, 0),
            decode_tokens=max(req.max_new_tokens, 1),
            resv_frac=resv / self.num_pages, ci=ci,
            n_devices=self.cfg.n_devices)
        slo_ok = req.slo_s is None or t_est <= req.slo_s
        return slo_ok, g

    def _place(self, req: Request,
               open_slots: Optional[List[int]] = None):
        """Placement policy. Eligibility is policy-INDEPENDENT: placeable
        shards with an open slot (``open_slots[s]`` > 0, by default the
        free slots) whose pool fits the request's worst-case reservation,
        less its resident prefix with sharing.

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
        if open_slots is None:
            open_slots = [len(self.free_slots(s)) for s in range(self.S)]
        carbon = self.cfg.routing == "carbon"
        best = None
        for s in self._placeable():
            if open_slots[s] <= 0:
                continue
            if (self._power_cap[s] is not None
                    and self._prospective_draw(s, req)
                    > self._power_cap[s]):
                continue               # capped shard: refuse work that
                                       # would push its draw back over
            if self.sharing:
                # reserve only the UNSHARED worst case: the pages this
                # request will itself pop — novel prompt pages + decode
                # budget + (when the whole prompt is shared) the one
                # copy-on-write pop for the recomputed tail token.
                # Matching is re-done on every admission pass: the index
                # fills as earlier residents finish prefilling.
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

    # ---------------------------------------------------- temporal deferral
    # Batch-class requests wait for the grid's greenest window (paper §4's
    # temporal lever): the CI forecaster picks the lowest-mean-CI window in
    # the look-ahead horizon at submit time, the request is parked owning
    # nothing, and it re-enters the FCFS queue when the engine's virtual
    # clock reaches that window — or earlier, when defer_deadline_frac of
    # its deadline budget has elapsed (the rest is reserved for service).

    def _defer_regions(self) -> List[Region]:
        # dedup preserving order: S shards usually span few regions
        seen: Dict[str, Region] = {}
        for r in self.shard_region:
            seen.setdefault(r.name, r)
        return list(seen.values())

    def _forecaster(self, region: Region) -> CIForecaster:
        fc = self._forecasters.get(region.name)
        if fc is None:
            # fit on two synthetic days of the region's diurnal trace —
            # the stand-in for yesterday's telemetry feed
            hours = np.arange(0.0, 48.0)
            fc = CIForecaster().fit(hours, ci_trace(region, hours))
            self._forecasters[region.name] = fc
        return fc

    def _defer(self, req: Request) -> None:
        """Park ``req`` until the greenest forecast window across the
        fleet's regions opens (fixed at submit — a day-ahead commitment,
        so release order within a class stays FCFS)."""
        now_h = self.clock.hours
        best = now_h
        best_ci = None
        for region in self._defer_regions():
            start, mean_ci = self._forecaster(region).greenest_window(
                now_h, horizon_h=self.cfg.defer_horizon_h)
            if best_ci is None or mean_ci < best_ci:
                best, best_ci = start, mean_ci
        self._defer_release_h[req.rid] = best
        self.deferred.append(req)
        self.deferred_rids.add(req.rid)
        self.deferred_total += 1

    def _release_deferred(self) -> int:
        """Move due (window open) or forced (deadline pressure) requests
        from the deferral queue into the admission queue. Releases are
        prefix-closed per priority class: if request i of a class is
        released, everything of that class ahead of it is too — deferral
        can never reorder same-class FCFS."""
        if not self.deferred:
            return 0
        now_h = self.clock.hours
        now_s = time.perf_counter()
        frac = self.cfg.defer_deadline_frac
        last_eligible: Dict[int, int] = {}
        forced_rids: set = set()
        for i, req in enumerate(self.deferred):
            due = now_h >= self._defer_release_h[req.rid]
            forced = (req.deadline_s is not None
                      and now_s - req.t_submit >= frac * req.deadline_s)
            if due or forced:
                last_eligible[req.priority] = i
                if forced and not due:
                    forced_rids.add(req.rid)
        if not last_eligible:
            return 0
        kept: deque = deque()
        released = 0
        for i, req in enumerate(self.deferred):
            cut = last_eligible.get(req.priority, -1)
            if i <= cut:
                self.deferred_rids.discard(req.rid)
                self._defer_release_h.pop(req.rid, None)
                self.deferred_released += 1
                if req.rid in forced_rids:
                    self.deferred_forced += 1
                self._enqueue(req)
                released += 1
            else:
                kept.append(req)
        self.deferred = kept
        return released

    def _fast_forward_deferred(self) -> None:
        """Nothing runnable remains but deferred work is parked: sleep the
        virtual clock forward to the earliest release window and release.
        (The modeled clock only advances with work, so an otherwise-idle
        engine must jump to the window rather than busy-wait toward it.)"""
        h = min(self._defer_release_h[r.rid] for r in self.deferred)
        self.clock.hours = max(self.clock.hours, h)
        self._release_deferred()

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
        return np.array(self._fetch(self.caches["paged"]["tbl"]))

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
        for d in self._placeable():
            if d == s:
                continue
            if not self.free_slots(d) or self.free_pages[d] < resv_d:
                continue
            key: Tuple = (self.free_pages[d], -d)
            if carbon:
                region = self.shard_region[d]
                ci = (ci_at_hour(region, self.clock.hours % 24.0)
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
        pages = [int(p) for p in src_row if p >= 0]
        n = len(pages)
        slot_d = self.free_slots(d)[0]
        resv_d = self._resv_for_move(s, b)
        budget = self.slot_budget[s][b]
        ctx, armed = self._slot_ctx[s][b], self._slot_armed[s][b]
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
        # source credit: the device release already ran in-program
        self._return_pages(s, b)
        self._clear_slot(s, b)
        # destination claim: same mirror writes as admission, but the
        # slot arrives mid-flight (ctx, budget, armed state preserved)
        self.free_pages[d] -= resv_d
        self.peak_pages_reserved[d] = max(
            self.peak_pages_reserved[d],
            self.num_pages - self.free_pages[d])
        self._occupy(d, slot_d, req, budget, ctx, armed)
        self._slot_pages[d][slot_d] = resv_d
        self._req_shard[req.rid] = d
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
        if not [d for d in self._placeable() if d != s]:
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

    def _rebalance(self) -> None:
        """Per-quantum drain progress and brownout re-enforcement. An
        expired drain deadline force-evacuates the remainder through
        fail_shard (reachable: migrate what fits, fold the rest); load
        that grew back over a live cap (a slot's context deepened) sheds
        again."""
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
            self._drain_quantum(s)
        for s in self.health.live:
            if self._power_cap[s] is not None:
                self._shed_to_cap(s)

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
            counts = prefill_counts(self.workload, 1, self.cfg.prefill_chunk)
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
        counts = prefill_counts(self.workload, 1,
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

    def _absorb_declarations(self) -> None:
        """A fault campaign's declarations at the quantum boundary,
        BEFORE any launch. Injected shard loss models a crashed device
        (NOT reachable: evacuation folds) and is absorbed, never raised.
        Scheduled drains and power caps skip shards where the action is
        moot (dead, already draining, or the last drainable one) — a
        random campaign must be survivable by construction."""
        for s in self.faults.shard_down_fires(self._quantum, self._run_q0):
            if not self.health.is_dead(s):
                self.fail_shard(s, reachable=False)
        for plan in self.faults.admin_fires(self._quantum, self._run_q0):
            s = plan.shard
            if self.health.is_dead(s) or s in self._draining:
                continue
            if plan.site == "drain":
                if [d for d in self._placeable() if d != s]:
                    self.drain(s)
                continue
            prof = self.shard_profile[s]
            watts = (plan.watts if plan.watts is not None
                     else prof.idle_w + 0.5 * (prof.tdp_w - prof.idle_w))
            self.power_cap(s, max(watts, prof.idle_w))

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
        """Back off ``site`` and charge the shards the launch touched to
        the health watchdog; retry EXHAUSTION becomes shard loss whenever
        the suspect shards leave a survivor, and FaultError otherwise."""
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
                f"(max_retries={self.cfg.max_retries}); in-flight requests "
                "are re-queued and reservations returned")
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
        stopped answering gets the fold path. The choice is made
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
            self._disarm([(s, b) for b in armed])
        # route-invalidate the dead lane: later fleet launches stay
        # batch-shape invariant (every slot writes a row per micro-step),
        # so without a cleared block table the dead lane's still-mapped
        # slots would scatter garbage into real pages of the dead pool.
        # Clearing ONLY tbl sends those writes to the trash page; ref,
        # free, top, and every KV payload page stay bit-identical.
        do = np.zeros((self.S,), bool)
        do[s] = True
        self.caches = dict(self.caches)
        self.caches["paged"] = _QUARANTINE_FLEET(
            self.mesh, self.caches["paged"], jnp.asarray(do))
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
        self.caches = _SCRUB_FLEET(self.mesh, self.caches, jnp.asarray(do))
        self.health.declare_up(s, self._quantum)
        self.shard_rejoins += 1
        self.meter.set_live(self.health.live)
        self.audit()

    def audit(self) -> None:
        """Production consistency check — the test-suite invariants
        promoted into the engine, run after every recovery event (and
        callable any time the engine is between quanta):

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

        Costs one device->host fetch of the allocator — recovery events
        are rare, quanta are not, so this never sits on the hot path.
        Raises RuntimeError on any violation."""
        a = self._fetch(self.caches["paged"])
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
        # fleet-wide page conservation: Σ top + Σ referenced == S·P,
        # counted from REFCOUNTS so frozen dead pools (quarantine clears
        # only tbl) and scrubbed pools satisfy it too — a migration that
        # leaked a page on either endpoint, or freed one twice, breaks
        # the sum even when each shard's local books happen to balance
        fleet = int(top.sum()) + int((ref > 0).sum())
        if fleet != self.S * n_pg:
            raise RuntimeError(
                f"audit: fleet-wide page conservation broken: "
                f"sum(top) + sum(ref>0) = {fleet} != "
                f"{self.S} * {n_pg} = {self.S * n_pg}")

    # -------------------------------------------------------------- reports
    def _fleet_stats(self, out: Dict[str, float]) -> None:
        """The fleet's rows of ``stats()``: routing policy, per-shard
        attribution (each shard metered at its own profile × region CI;
        the fleet totals are the exact sum of these rows), watchdog state
        and recovery counters, and the migrate phase (its own meter
        phase, so prefill/decode J per token stay invariant —
        docs/METHODOLOGY.md)."""
        out["shards"] = self.S
        out["shard_steps"] = self.shard_steps
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
            if self._power_cap[s] is not None:
                out[f"shard{s}_power_cap_w"] = self._power_cap[s]
        out.update({
            "live_shards": len(self.health.live),
            "dead_shards": self.S - len(self.health.live),
            "shard_down_events": self.shard_down_events,
            "shard_evacuated": self.shard_evacuated,
            "shard_rejoins": self.shard_rejoins,
            "migrations": self.migrations,
            "migrated_pages": self.migrated_pages,
            "drain_events": self.drain_events,
            "migrate_j": self.meter.phase("migrate").energy_j,
            "power_cap_events": self.power_cap_events,
        })
        # fault attribution per (site, shard): WHICH device the retries
        # clustered on
        for (site, s), n in sorted(self._fault_retry_shard.items()):
            out[f"shard{s}_fault_retries_{site}"] = n


def __getattr__(name: str):
    # ``ShardedServingEngine`` is the engine's former fleet name, which
    # perfbench/run.py still imports from here: the same class
    if name == "ShardedServingEngine":
        from repro.serving.engine import ServingEngine
        return ServingEngine
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
