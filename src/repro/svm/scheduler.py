"""Lane pool: multi-source repacked batched dispatch with incremental
admission.

A fixed-width batch would advance every lane until the LAST lane
converges — converged lanes freeze but still flow through the vmapped
body. This module drives the engine's chunk programs by a **schedule**
over a pool of lanes that may span SEVERAL kernel sources (e.g. one RBF
matrix per gamma of a hyper-parameter grid):

* **repacking** — between chunks, converged lanes are *retired* (their
  state finalized into an ``SMOResult`` keyed by original lane id) and the
  live lanes gathered into a compact batch, so device work tracks
  ``sum_h n_iter_h`` instead of ``width * max_h n_iter_h``;
* **source bucketing** — every lane carries a *source key*; the selected
  lanes are grouped by source and ONE batched program is dispatched per
  (source, width) bucket. Lanes of different sources never share a
  program (their kernel operands differ), but they share the pool's
  admission, width budget and fairness accounting — this is what
  dissolves the per-gamma row barrier in ``run_grid``;
* **width bucketing** — a group's packed width is rounded up to a multiple
  of ``lane_quantum`` (widths 1 and 2 stay exact), padding with inert
  ``done`` lanes, so distinct jit programs stay O(peak_width / quantum)
  per source shape instead of one retrace per live-width;
* **degradation** — a group of 1 uses the *single-lane* sequential
  program (the same ``chunk_jit`` the scalar ``solve`` path uses), so a
  straggler tail costs sequential-solver time, not a vmapped batch of one;
* **width capping** (``max_width``) — the TOTAL dispatch width per chunk
  is bounded by a *measured* cost model (``svm/cost_model.py`` loads
  ``results/cost_model.json``, written per (backend, source kind) by
  ``scripts/measure_cost_model.py``; absent entries fall back to the
  historical verdict): XLA CPU pays a ~1.5-2x per-lane-iteration penalty
  for ANY vmapped width (measured flat from width 2 up), so on CPU the
  measured default is width-1 round-robin through the sequential program
  (total device work still tracks
  ``sum_h n_iter_h``). The capped rotation is **source-sticky**: the most
  recently dispatched source keeps the width budget while it has live
  lanes (its kernel matrix stays cache-hot; a per-chunk rotation across
  sources restreams a cold ~n^2 operand every chunk — measured ~5%
  slower), least-served lanes first within it. Accelerator backends
  amortize dispatch overhead across lanes and default to unbounded width;
* **admission** — a lane may be added with a *dependency* on another
  lane's result plus a seed transform (``seed_fn(prev_result) ->
  (alpha0, f0)``), and/or a pure *ordering* edge (``after``) that holds an
  explicitly-started lane until another lane retires. Dependencies may
  cross sources (a gamma-row cell seeding from its C-neighbour in another
  bucket is legal); a lane is admitted the moment its edges retire;
* **kernel residency** — a source may be declared as a *factory*
  (``svm/sources.py:KernelSpec``) instead of a dense matrix: the pool's
  ``SourceCache`` materializes it on the first dispatch that needs it,
  under a ``max_resident``/``cache_bytes`` budget, evicting the resident
  source with the fewest remaining unretired lanes (schedule distance;
  the sticky source only as a last resort). Eviction writes the source's
  packed batch back to its lanes first. Selection is budget-aware at
  every width: a chunk dispatches at most budget-many managed sources
  (sticky/resident preferred) even when ``max_width=0`` selects all live
  lanes, and width-capped selection prefers lanes whose source is already
  resident — so a budgeted pool drains each kernel before paying for the
  next one instead of thrashing. Re-materialization is bit-identical (a
  spec is a pure function of its inputs), preserving the bit-parity
  invariant below.

Because each lane's iterate sequence depends only on its own
(source, mask, C, state) — the engine body freezes ``done`` lanes, lanes of
one program share one source, and ``vmap`` keeps lanes independent —
per-lane results are **bit-identical** to sequential ``engine.solve`` runs
regardless of the packing schedule and of which sources share the pool
(covered by tests/test_scheduler.py and tests/test_study.py).

Checkpointing: ``snapshot_lanes()`` serializes every admitted lane's
(alpha, f, n_iter, done) stacked **in lane-id order**, not packed
position, so a mid-batch snapshot survives any repack/resume boundary;
``core/study.py:run_plan`` wires it to the checkpoint manager.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.svm import cost_model
from repro.svm import shrink as shrink_mod
from repro.svm.engine import (EngineState, SMOResult, chunk_batched_jit,
                              chunk_batched_sources_jit, chunk_jit, finalize,
                              init_state, stack_sources)
from repro.svm.precision import STATE_DTYPE
from repro.svm.sources import SourceCache, is_factory


def bucket_width(w: int, quantum: int = 4) -> int:
    """Packed width for ``w`` live lanes: 1 and 2 are exact (the straggler
    tail, where padding would be pure overhead), wider batches round up to
    the next multiple of ``quantum`` so the number of distinct compiled
    programs stays bounded by ``peak_width / quantum + 2``."""
    if w <= 2:
        return max(w, 1)
    q = max(int(quantum), 1)
    return -(-w // q) * q


def possible_widths(peak: int, quantum: int = 4,
                    max_width: int = 0) -> tuple[int, ...]:
    """Every distinct packed width the pool can dispatch for a source
    whose live-lane count ranges over 1..``peak`` under a ``max_width``
    cap (0 = unbounded): the compile-shape enumeration the plan analyzer
    (``repro.analysis.plan_check``) maps onto jitted programs — width 1
    is the single-lane program, each bucketed width >= 2 one batched
    program. Kept next to :func:`bucket_width` so prediction and
    execution cannot drift apart."""
    cap = int(peak) if not max_width else min(int(peak), int(max_width))
    return tuple(sorted({bucket_width(w, quantum)
                         for w in range(1, max(cap, 1) + 1)}))


def order_capped(lanes, *, sticky, resident, served, source) -> list:
    """Width-capped dispatch priority — the PURE form of the pool's
    sticky > resident > cold ordering, shared with the schedule simulator
    (``repro.analysis.plan_sim``) so prediction and execution cannot
    drift. ``lanes`` is any sequence; ``source(lane)`` names its source
    key, ``resident(key)``/``served(lane)`` supply the pool-or-simulated
    residency and fairness state. Each tier is stable-sorted by
    ``served`` (ties keep input order — the pool's insertion order)."""
    stick = [ln for ln in lanes if source(ln) == sticky]
    near = [ln for ln in lanes
            if source(ln) != sticky and resident(source(ln))]
    far = [ln for ln in lanes
           if source(ln) != sticky and not resident(source(ln))]
    return (sorted(stick, key=served) + sorted(near, key=served)
            + sorted(far, key=served))


def select_capped(lanes, *, max_width, sticky, resident, served, source,
                  tenant, tenant_served) -> list:
    """Pure form of ``LanePool._cap_select``: single-tenant inputs take
    the historical sticky/resident/served order truncated to the width
    budget; multi-tenant inputs fair-share it — per-tenant ordering by
    the same policy, tenants interleaved round-robin least-served first.
    ``tenant(lane)`` tags a lane, ``tenant_served`` maps tag -> lane-chunk
    count. Shared with the schedule simulator."""
    tenants = list(dict.fromkeys(tenant(ln) for ln in lanes))
    order = dict(sticky=sticky, resident=resident, served=served,
                 source=source)
    if len(tenants) <= 1:
        return order_capped(lanes, **order)[:max_width]
    per = {t: order_capped([ln for ln in lanes
                            if tenant(ln) is t or tenant(ln) == t], **order)
           for t in tenants}
    tenants.sort(key=lambda t: tenant_served.get(t, 0))
    out: list = []
    while len(out) < max_width and any(per.values()):
        for t in tenants:
            if per[t] and len(out) < max_width:
                out.append(per[t].pop(0))
    return out


def budget_sources(srcs, *, budgeted, pinned, resident, sticky, nbytes,
                   fits) -> set:
    """Pure form of ``LanePool._budget_sources``: which of the candidate
    source keys may dispatch this chunk under the residency budget.
    Pinned sources always; managed sources greedily in sticky > resident
    > cold priority (stable: input order breaks ties) while the budget
    rule (``fits(count, bytes)``) admits the next one. Shared with the
    schedule simulator."""
    srcs = list(dict.fromkeys(srcs))
    if not budgeted or len(srcs) <= 1:
        return set(srcs)
    allowed = {s for s in srcs if pinned(s)}
    managed = sorted((s for s in srcs if s not in allowed),
                     key=lambda s: (s != sticky, not resident(s)))
    taken: list = []
    used = 0
    for s in managed:
        nb = nbytes(s)
        if taken and not fits(len(taken) + 1, used + nb):
            break
        taken.append(s)
        used += nb
    return allowed | set(taken)


def snapshot_nbytes(n: int, itemsize: int, lane_count: int,
                    shrink: bool = False) -> int:
    """Estimated serialized bytes of one pool snapshot record
    (``snapshot_lanes``): per lane, stacked ``alpha`` + ``f`` rows
    (``2 * n * itemsize``), an ``n_iter`` scalar (8) and a ``done`` flag
    (1); shrink-enabled pools add the ``active`` mask (n), the
    ``shrunk``/``no_shrink`` flags and the int32 ``unshrinks`` counter.
    The simulator prices checkpoint write volume with this — an estimate
    of array payload, not serialization framing."""
    per = 2 * int(n) * int(itemsize) + 8 + 1
    if shrink:
        per += int(n) + 1 + 1 + 4
    return int(lane_count) * per


@dataclasses.dataclass
class _Lane:
    id: Any
    source: Any                           # key into the pool's sources
    train_mask: jnp.ndarray
    C: float
    max_iter: int
    state: EngineState | None = None      # admitted, not yet retired
    dep: Any = None                       # lane id this lane seeds from
    seed_fn: Callable | None = None       # SMOResult -> (alpha0, f0)
    after: Any = None                     # ordering-only admission edge
    alpha0: Any = None                    # deferred start (held by ``after``)
    f0: Any = None
    n_iter0: int = 0
    result: SMOResult | None = None       # set at retirement
    served: int = 0                       # chunks dispatched (park fairness)
    tenant: Any = None                    # fair-share accounting group
    seed_s: float = 0.0                   # admission-transform wall time
    solve_s: float = 0.0                  # dispatch wall time attributed here
    shrink: Any = None                    # shrink.LaneShrink when enabled
    shrink0: Any = None                   # restored ledger (active, flags)


class LanePool:
    """Queue of independent solve lanes over MULTIPLE kernel sources,
    driven to convergence by repacked, source-bucketed, incrementally-
    admitted chunk dispatch. See the module docstring for the scheduling
    policy; per-lane results are bit-identical to sequential solves.

    ``sources`` maps a source key to a kernel source, or to a *factory*
    (e.g. ``sources.KernelSpec``) that declares one without computing it:
    factory entries materialize on demand through the pool's
    :class:`~repro.svm.sources.SourceCache` under the
    ``max_resident``/``cache_bytes`` budget and are evicted by schedule
    distance (DESIGN.md §Kernel-source cache), so pool memory scales with
    the budget instead of the source count. ``y`` is the label vector
    shared by every source, or a dict keyed like ``sources`` when sources
    carry different instance sets. ``on_result(lane_id, result)`` streams
    retirements (long studies consume results as they land);
    ``on_lane_chunk(lane_id, state)`` observes every still-live lane after
    each of its chunks (the per-lane mid-checkpoint hook).
    """

    def __init__(self, sources, y, *, tol: float = 1e-3, wss: str = "2",
                 chunk_iters: int = 2048, lane_quantum: int = 4,
                 max_width: int | None = None,
                 max_resident: int = 0, cache_bytes: int = 0,
                 on_snapshot=None, snapshot_every: int = 1,
                 on_result=None, on_lane_chunk=None,
                 shrink_every: int | str = 0, shrink_quantum: int = 128,
                 shrink_caps=None, shrink_on_seed: bool = True,
                 on_trace=None):
        if not isinstance(sources, dict):
            raise ValueError("sources must be a {key: source} dict")
        # an EMPTY pool is legal: a long-lived daemon constructs the pool
        # once and admits sources/lanes as plans arrive (add_source)
        kinds = {cost_model.source_kind(s) for s in sources.values()}
        if max_width is None:
            # measured cost model (results/cost_model.json, written by
            # scripts/measure_cost_model.py): per-(backend, source-kind)
            # width verdict, combined conservatively across this pool's
            # kinds. Falls back to the historical default when unmeasured:
            # CPU's vmapped batch loses at every width > 1, accelerators
            # want full width.
            max_width = cost_model.pick_max_width(kinds=kinds)
        self.max_width = int(max_width)   # 0 = unbounded
        if shrink_every == "auto":
            # backend-gated default: shrinking trades smaller per-iteration
            # operands for extra compiled programs (one per cap bucket) and
            # host-side lifecycle sync — the cost-model sweep decides
            # whether smaller-cap programs are actually faster here
            shrink_every = shrink_mod.DEFAULT_SHRINK_EVERY \
                if cost_model.pick_shrink(kinds=kinds) else 0
        self.shrink_every = int(shrink_every)
        self.shrink_quantum = int(shrink_quantum)
        self.shrink_caps = tuple(int(c) for c in shrink_caps) \
            if shrink_caps else None
        self.shrink_on_seed = bool(shrink_on_seed)
        self._frac_log: list[float] = []  # (cap or n)/n per lane-dispatch
        self.sources = dict(sources)
        self._ys = {k: (y[k] if isinstance(y, dict) else y)
                    for k in self.sources}
        if on_snapshot is not None and \
                len({np.shape(yv) for yv in self._ys.values()}) > 1:
            # snapshot_lanes stacks every lane's (alpha, f) into one (L, n)
            # tree — fail at construction, not at the first checkpoint
            raise ValueError(
                "snapshotting requires every source to share one instance "
                "set (homogeneous y shapes); got "
                f"{sorted({np.shape(yv) for yv in self._ys.values()})}")
        self.tol = tol
        self.wss = wss
        self.chunk_iters = int(chunk_iters)
        self.lane_quantum = int(lane_quantum)
        self.on_snapshot = on_snapshot
        self.snapshot_every = max(int(snapshot_every), 1)
        self.on_result = on_result
        self.on_lane_chunk = on_lane_chunk
        # schedule trace hook: when set, the pool (and its cache) emit the
        # typed event grammar of DESIGN.md §Schedule simulator — the
        # instrumented dry-run the simulator's output is asserted against.
        # Assignable after construction (the daemon's pool outlives any
        # one trace consumer).
        self.on_trace = on_trace
        self._lanes: dict[Any, _Lane] = {}
        self._order: list[Any] = []       # insertion order = packing order
        self.results: dict[Any, SMOResult] = {}
        self._tenant_served: dict[Any, int] = {}   # fair-share accounting
        self.seed_time = 0.0              # admission transforms (paper "init.")
        self.chunk_count = 0
        self._width_log: list[tuple[int, int]] = []   # (live, dispatched)
        self._programs: set[tuple] = set()            # (source, width) seen
        self._src_live: dict[Any, list] = {}          # key -> [sum, n, peak]
        self._sticky: Any = None          # last dispatched source (affinity)
        # packed-batch cache per source: rebuilt when a group's membership
        # changes (the previous pack is evicted — states written back — so
        # no progress is ever lost to a stale ``lane.state``)
        self._packed: dict[Any, tuple] = {}  # key -> (ids, payload)
        # kernel residency: factory entries materialize on demand under the
        # cache budget and are evicted by schedule distance (fewest
        # remaining lanes first, the sticky source last); dense entries are
        # pinned — see svm/sources.py and DESIGN.md §Kernel-source cache.
        # Evicting a source also drops its packed-batch cache (states are
        # written back to the lanes first, so no progress is lost).
        self.cache = SourceCache(
            self.sources, max_resident=max_resident, cache_bytes=cache_bytes,
            wss=wss, distance=self._source_distance,
            sticky=lambda: self._sticky, on_evict=self._on_source_evict,
            on_trace=self._trace)
        for key, entry in self.sources.items():
            # every entry answers ``fused`` cheaply now (pinned sources
            # directly, specs by declaration — a pallas_rbf spec is fused
            # without compute), so the check runs at construction for
            # all of them; factory *products* are re-checked at
            # materialization anyway (the same rule, deferred)
            self.cache.check_fused(key, entry)

    def _trace(self, *event) -> None:
        """Emit one schedule trace event (a plain tuple) to ``on_trace``.
        The cache funnels its materialize/evict events through here too,
        so assigning ``pool.on_trace`` after construction captures the
        full grammar."""
        if self.on_trace is not None:
            self.on_trace(tuple(event))

    def y_of(self, source_key) -> jnp.ndarray:
        return self._ys[source_key]

    def resolve_source(self, source_key):
        """The usable kernel source for ``source_key``, materialized through
        the residency cache (the pool's own dispatch, the study's seed
        transforms and its eval groups all read kernels through here)."""
        return self.cache.get(source_key)

    def _source_distance(self, source_key) -> int:
        """Schedule distance of a resident source = how many of its lanes
        are still unretired (live or pending admission). The source with
        the FEWEST remaining lanes is the one the schedule needs least —
        it is evicted first."""
        return sum(1 for lane in self._lanes.values()
                   if lane.source == source_key and lane.result is None)

    def _on_source_evict(self, source_key) -> None:
        """A source's kernel is about to be dropped: flush its packed-batch
        cache back into the lanes so no solver progress rides on the
        evicted operand."""
        if source_key in self._packed:
            self._writeback(source_key)

    def _budget_sources(self, lanes) -> set:
        """The sources allowed to dispatch this chunk under the residency
        budget: pinned sources always, managed sources in sticky >
        resident > cold priority (stable: insertion order breaks ties),
        truncated to the budget. Without this, an unbounded-width schedule
        would dispatch EVERY live source's group each chunk and a budget
        below the live source count would re-materialize kernels every
        chunk — with it, the pool drains resident kernels first and
        materialization count tracks the source count, not the chunk
        count, under every width policy. Defers to the pure
        :func:`budget_sources` the simulator replays."""
        return budget_sources(
            [ln.source for ln in lanes], budgeted=self.cache.budgeted,
            pinned=self.cache.pinned, resident=self.cache.resident,
            sticky=self._sticky, nbytes=self.cache.nbytes_of,
            fits=self.cache.fits)

    def _source_key(self, source) -> Any:
        if source is not None:
            if source not in self.sources:
                raise ValueError(f"unknown source key {source!r}")
            return source
        if len(self.sources) == 1:
            return next(iter(self.sources))
        raise ValueError("a multi-source pool needs an explicit source key "
                         "per lane")

    # ------------------------------------------------------- source lifecycle

    def add_source(self, key, entry, y) -> None:
        """Admit a source into a LIVE pool (the daemon's per-plan intake —
        the constructor path for pools whose workload arrives over time).
        Same rules as construction: the fused/WSS check runs now, factory
        entries stay unmaterialized until a dispatch needs them."""
        if key in self.sources:
            raise ValueError(f"duplicate source key {key!r}")
        self.cache.check_fused(key, entry)
        self.sources[key] = entry
        self._ys[key] = y
        self.cache.add_entry(key, entry)

    def remove_source(self, key) -> None:
        """Drop a source whose lanes have all retired (a drained study's
        kernels leave residency so other tenants' budgets recover the
        bytes). Refuses while any unretired lane still reads it."""
        live = [ln.id for ln in self._lanes.values()
                if ln.source == key and ln.result is None]
        if live:
            raise ValueError(
                f"source {key!r} still has unretired lanes {live!r}")
        self._packed.pop(key, None)
        if self._sticky == key:
            self._sticky = None
        self.sources.pop(key, None)
        self._ys.pop(key, None)
        self._src_live.pop(key, None)
        self.cache.remove_entry(key)

    def remove_lanes(self, lane_ids) -> None:
        """Forget RETIRED lanes (a drained study leaves the pool so its
        ids never collide with a later admission). Live/pending lanes
        refuse — cancellation is not yet a pool primitive (ROADMAP)."""
        ids = set(lane_ids)
        for lane_id in ids:
            lane = self._lanes.get(lane_id)
            if lane is not None and lane.result is None:
                raise ValueError(f"lane {lane_id!r} is not retired")
        for lane_id in ids:
            self._lanes.pop(lane_id, None)
            self.results.pop(lane_id, None)
        self._order = [i for i in self._order if i not in ids]

    # ---------------------------------------------------------- lane intake

    def add(self, lane_id, train_mask, C, alpha0=None, f0=None, *,
            source=None, n_iter0: int = 0, max_iter: int = 10_000_000,
            dep=None, seed_fn=None, after=None, shrink0=None,
            tenant=None) -> None:
        """Register a lane. Either give its start point (``alpha0``/``f0``,
        optionally ``n_iter0`` when resuming a snapshot) or a dependency
        (``dep`` = another lane id, ``seed_fn`` mapping that lane's
        ``SMOResult`` to this lane's (alpha0, f0)) — the lane is then
        admitted when the dependency retires. ``after`` adds a pure
        ordering edge: the lane (even an explicitly-started one) is held
        until that lane retires — sequential protocols (the paper's fold
        chain) express their ordering without faking a seed dependency.

        ``shrink0`` restores a snapshotted shrink ledger:
        ``(active_mask_or_None, no_shrink, unshrinks)`` — a restored lane
        re-enters its compact bucket (or its endgame flags) instead of
        re-running the admission handoff, which is what makes a mid-shrink
        resume replay the uninterrupted trajectory bit-exactly."""
        if lane_id in self._lanes:
            raise ValueError(f"duplicate lane id {lane_id!r}")
        if (dep is None) == (alpha0 is None):
            raise ValueError("give exactly one of alpha0/f0 or dep/seed_fn")
        if (alpha0 is None) != (f0 is None):
            raise ValueError("alpha0 and f0 must be given together "
                             "(f0 = init_f(K, y, alpha0))")
        if dep is not None and seed_fn is None:
            raise ValueError("a dependent lane needs a seed_fn")
        key = self._source_key(source)
        lane = _Lane(id=lane_id, source=key, train_mask=train_mask, C=C,
                     max_iter=int(max_iter), dep=dep, seed_fn=seed_fn,
                     after=after, shrink0=shrink0, tenant=tenant)
        if alpha0 is not None:
            if after is None:
                # intake must not force kernels into residency: the state
                # needs no source at all
                lane.state = init_state(train_mask, alpha0, f0,
                                        n_iter0=n_iter0)
                self._attach_shrink(lane)
            else:   # held: built at admission, when ``after`` retires
                lane.alpha0, lane.f0, lane.n_iter0 = alpha0, f0, int(n_iter0)
        self._lanes[lane_id] = lane
        self._order.append(lane_id)
        if lane.state is not None:
            self._trace("admit", lane_id, key)

    def _attach_shrink(self, lane: _Lane) -> None:
        """Build a lane's shrink ledger the moment its state exists (the
        handoff, like intake, never materializes a kernel). A restored
        ledger (``shrink0``) takes precedence; otherwise the seeding ->
        shrinking handoff evaluates the heuristic on the seeded (alpha0,
        f0) so bound-locked seeded alphas start shrunk."""
        if not self.shrink_every:
            return
        y = self._ys[lane.source]
        ls = shrink_mod.LaneShrink(int(np.shape(y)[0]),
                                   every=self.shrink_every,
                                   quantum=self.shrink_quantum,
                                   caps=self.shrink_caps)
        lane.shrink = ls
        if lane.shrink0 is not None:
            active, no_shrink, unshrinks = lane.shrink0
            ls.no_shrink = bool(no_shrink)
            ls.unshrinks = int(unshrinks)
            lane.shrink0 = None
            if active is not None:
                active = jnp.asarray(active, bool) & \
                    jnp.asarray(lane.train_mask, bool)
                ls.mark(active, int(jnp.sum(active)))
            return
        if self.shrink_on_seed:
            shrink_mod.seed_shrink(ls, y, lane.train_mask, lane.C,
                                   lane.state, tol=self.tol)

    def add_result(self, lane_id, result: SMOResult, *,
                   tenant=None) -> None:
        """Register an already-solved lane (a restored ``done`` snapshot):
        it participates as a seed dependency but is never dispatched."""
        if lane_id in self._lanes:
            raise ValueError(f"duplicate lane id {lane_id!r}")
        lane = _Lane(id=lane_id, source=None, train_mask=None, C=None,
                     max_iter=0, result=result, tenant=tenant)
        self._lanes[lane_id] = lane
        self._order.append(lane_id)
        self.results[lane_id] = result
        self._trace("given", lane_id)

    def lane_times(self, lane_id) -> tuple[float, float]:
        """(seed_s, solve_s) wall time attributed to one lane: its admission
        transform, and its share of every chunk it was dispatched in."""
        lane = self._lanes[lane_id]
        return lane.seed_s, lane.solve_s

    # ------------------------------------------------------------ scheduling

    def _admit(self) -> None:
        """Admit every pending lane whose edges have retired: run its seed
        transform (timed as init/seed work) and build its state."""
        for lane_id in self._order:
            lane = self._lanes[lane_id]
            if lane.state is not None or lane.result is not None:
                continue
            if lane.after is not None and lane.after not in self.results:
                continue
            if lane.dep is None:          # explicit start held by ``after``
                lane.state = init_state(lane.train_mask, lane.alpha0,
                                        lane.f0, n_iter0=lane.n_iter0)
                lane.alpha0 = lane.f0 = None
                self._attach_shrink(lane)
                self._trace("admit", lane_id, lane.source)
                continue
            if lane.dep not in self.results:
                continue
            # a seed transform may materialize its kernel through the cache
            # (lazy K resolution, core/study.py); that wall time is KERNEL
            # time, not seed time — subtract the cache's delta so the
            # paper's "init." column stays a seeding measurement
            k0 = self.cache.kernel_time
            with obs.span("repro.pool.seed", lane=lane_id,
                          transform=getattr(lane.seed_fn, "transform",
                                            None)) as sp:
                alpha0, f0 = lane.seed_fn(self.results[lane.dep])
                jax.block_until_ready((alpha0, f0))
                sp.attrs["kernel_s"] = self.cache.kernel_time - k0
            dt = sp.seconds - sp.attrs["kernel_s"]
            lane.seed_s += dt
            self.seed_time += dt
            lane.state = init_state(lane.train_mask, alpha0, f0)
            self._attach_shrink(lane)
            self._trace("admit", lane_id, lane.source)

    def _live(self) -> list[_Lane]:
        return [self._lanes[i] for i in self._order
                if self._lanes[i].state is not None
                and self._lanes[i].result is None]

    def _retire(self, lane: _Lane) -> None:
        with obs.span("repro.pool.retire", lane=lane.id):
            lane.result = finalize(lane.state, self._ys[lane.source],
                                   lane.train_mask, lane.C, self.tol)
            self.results[lane.id] = lane.result
            if self.on_trace is not None:  # int() syncs — only when tracing
                self._trace("retire", lane.id, int(lane.result.n_iter))
            if self.on_result is not None:
                self.on_result(lane.id, lane.result)

    def _pack(self, key, live: list[_Lane]) -> None:
        """Gather a source group's live lanes into a compact batch of
        bucketed width; pad positions replicate lane 0 with ``done`` set
        (inert: the engine body passes done lanes through untouched, and
        the while_loop's ``any(~done)`` ignores them)."""
        width = bucket_width(len(live), self.lane_quantum)
        states = [ln.state for ln in live]
        masks = [ln.train_mask for ln in live]
        Cs = [ln.C for ln in live]
        caps = [ln.max_iter for ln in live]
        for _ in range(width - len(live)):
            pad = live[0].state
            states.append(pad._replace(done=jnp.ones((), bool)))
            masks.append(live[0].train_mask)
            Cs.append(live[0].C)
            caps.append(0)
        payload = (jnp.stack(masks),
                   jnp.asarray(Cs, STATE_DTYPE),
                   jnp.asarray(caps, jnp.int64),
                   EngineState.stack(states))
        self._packed[key] = (tuple(ln.id for ln in live), payload)
        self._trace("pack", key, tuple(ln.id for ln in live))

    def _writeback(self, key) -> None:
        """Write a source's packed states back into its lanes and drop the
        packed cache — required before the group's membership changes
        (retire, park rotation, admission), a member dispatches solo, or
        the source's kernel is evicted from residency."""
        ids, payload = self._packed.pop(key)
        states = payload[3]
        for i, lane_id in enumerate(ids):
            self._lanes[lane_id].state = states.lane(i)

    def _cap_order(self, selected: list[_Lane]) -> list[_Lane]:
        """Width-capped dispatch priority within one fair-share group.
        Selection is SOURCE-STICKY: the most recently dispatched source
        keeps the width budget while it has live lanes — its kernel
        operands stay cache-hot, where a per-chunk rotation across
        sources was measured ~5% slower on CPU (each chunk restreamed a
        cold ~n^2 kernel matrix). Within the sticky source (and for any
        leftover width), least-served lanes go first (stable sort:
        insertion order breaks ties), so every lane of the serving source
        keeps advancing at chunk granularity; other sources advance when
        the sticky one drains or leaves width to spare. Leftover width is
        RESIDENCY-AWARE: lanes whose kernel is already materialized beat
        lanes that would force a materialization (and, under a budget, an
        eviction) — a budgeted pool drains each resident source before
        paying for the next kernel, so materialization count tracks the
        source count, not the chunk count. Dense (pinned) sources are
        always resident, so single-matrix pools keep the exact pre-cache
        ordering. Defers to the pure :func:`order_capped` the simulator
        replays."""
        return order_capped(selected, sticky=self._sticky,
                            resident=self.cache.resident,
                            served=lambda ln: ln.served,
                            source=lambda ln: ln.source)

    def _cap_select(self, selected: list[_Lane]) -> list[_Lane]:
        """Park the overflow for one chunk. Single-tenant pools (every
        lane untagged, or one tag — all pre-daemon callers) take the
        historical path verbatim. Multi-tenant pools FAIR-SHARE the width
        budget: each tenant's lanes are ordered by the same sticky/
        resident/served policy, then tenants are interleaved round-robin
        — least-served tenant first — so one tenant's wide grid cannot
        starve another's two folds, while each tenant's own lanes still
        drain source-sticky. Defers to the pure :func:`select_capped` the
        simulator replays."""
        return select_capped(selected, max_width=self.max_width,
                             sticky=self._sticky,
                             resident=self.cache.resident,
                             served=lambda ln: ln.served,
                             source=lambda ln: ln.source,
                             tenant=lambda ln: ln.tenant,
                             tenant_served=self._tenant_served)

    def run(self) -> dict[Any, SMOResult]:
        """Drive every lane to retirement; returns {lane_id: SMOResult}."""
        while self.step():
            pass
        pending = [i for i in self._order
                   if self._lanes[i].result is None]
        if pending:
            raise RuntimeError(
                f"lanes {pending} wait on dependencies that never "
                "retire (missing or cyclic dep)")
        return dict(self.results)

    def step(self) -> bool:
        """One scheduling round: admit ready lanes, select under the
        budget/width policy, dispatch one chunk per (source, width)
        group. Returns False when nothing is runnable — every lane
        retired, or the rest wait on edges that have not retired (the
        daemon's idle condition; ``run`` turns pending-forever into the
        missing/cyclic-dep error)."""
        self._admit()
        live = self._live()
        if not live:
            return False
        selected = live
        if len(self.sources) > 1 and self.cache.budgeted:
            # residency budget first: only budget-many managed sources
            # dispatch per chunk (sticky/resident preferred), so even
            # an unbounded-width schedule drains kernels instead of
            # thrashing the cache
            allowed = self._budget_sources(live)
            if len(allowed) < len({ln.source for ln in live}):
                selected = [ln for ln in live if ln.source in allowed]
        if self.max_width and len(selected) > self.max_width:
            selected = self._cap_select(selected)
        for lane in selected:
            lane.served += 1
            self._tenant_served[lane.tenant] = \
                self._tenant_served.get(lane.tenant, 0) + 1
        groups: dict[Any, list[_Lane]] = {}
        for lane in selected:
            # under shrinking, lanes bucket by (source, cap): a shrunk
            # lane migrates to the smaller-shape compact program of its
            # cap bucket, and only same-cap lanes can share a stacked
            # dispatch (their operand shapes match)
            gkey = (lane.source, lane.shrink.cap) if self.shrink_every \
                else lane.source
            groups.setdefault(gkey, []).append(lane)
        if len(self.sources) > 1:
            counts: dict[Any, int] = {}
            for lane in live:
                counts[lane.source] = counts.get(lane.source, 0) + 1
            for key, c in counts.items():
                rec = self._src_live.setdefault(key, [0, 0, 0])
                rec[0] += c
                rec[1] += 1
                rec[2] = max(rec[2], c)
        # affinity follows the chunk's PRIMARY group (selected[0]'s
        # source) — not the last group dispatched, which under a split
        # selection would hand stickiness to the overflow source
        self._sticky = selected[0].source
        chunk = self.chunk_count
        dispatched = 0
        for gkey, lanes in groups.items():
            width = (1 if len(lanes) == 1
                     else bucket_width(len(lanes), self.lane_quantum))
            dispatched += width
            if self.shrink_every:
                key, cap = gkey
                n = int(np.shape(self._ys[key])[0])
                self._programs.add((key, width, cap or n))
                for lane in lanes:
                    self._frac_log.append((cap or n) / n)
            else:
                key, cap = gkey, 0
                self._programs.add((key, width))
            ids = tuple(ln.id for ln in lanes)
            self._trace("dispatch", chunk, key, cap, width, ids)
            # dispatch may materialize the group's kernel through the
            # cache; that delta is kernel time, not solve time
            k0 = self.cache.kernel_time
            with obs.span("repro.pool.dispatch", chunk=chunk, width=width,
                          lanes=ids) as sp:
                if self.shrink_every:
                    self._step_shrink(key, cap, lanes)
                elif len(lanes) == 1:
                    self._step_single(lanes[0])
                else:
                    self._step_batched(key, lanes)
                sp.attrs["kernel_s"] = self.cache.kernel_time - k0
            dt = sp.seconds - sp.attrs["kernel_s"]
            for lane in lanes:
                lane.solve_s += dt / len(lanes)
        self._width_log.append((len(live), dispatched))
        if self.on_trace is not None:
            if any(ln.tenant is not None for ln in selected):
                shares: dict[Any, int] = {}
                for lane in selected:
                    shares[lane.tenant] = shares.get(lane.tenant, 0) + 1
                self._trace("shares", chunk, tuple(sorted(
                    (repr(t), c) for t, c in shares.items())))
            self._trace("resident", chunk,
                        self.cache.pinned_bytes + self.cache.resident_bytes)
        self.chunk_count += 1
        if self.on_lane_chunk is not None:
            for lane in selected:
                if lane.result is None:
                    self.on_lane_chunk(lane.id, self._lane_state(lane))
        if self.on_snapshot is not None and \
                self.chunk_count % self.snapshot_every == 0:
            if self.on_trace is not None:
                ids = [i for i in self._order
                       if self._lanes[i].state is not None
                       or self._lanes[i].result is not None]
                first = self._lanes[ids[0]]
                ref = (first.result.alpha if first.result is not None
                       else first.state.alpha)
                self._trace("checkpoint", chunk, tuple(ids),
                            snapshot_nbytes(int(ref.shape[0]),
                                            ref.dtype.itemsize, len(ids),
                                            bool(self.shrink_every)))
            self.on_snapshot(self)
        return True

    def _step_single(self, lane: _Lane) -> None:
        """Dispatch width 1: the sequential single-lane program
        (bit-identical to ``engine.solve``'s chunks) — no vmap overhead on
        a straggler or a width-capped round-robin schedule."""
        cached = self._packed.get(lane.source)
        if cached is not None and lane.id in cached[0]:
            self._writeback(lane.source)
        src, y = self.resolve_source(lane.source), self._ys[lane.source]
        lane.state = chunk_jit(src, y, lane.train_mask, lane.C,
                               self.tol, jnp.asarray(lane.max_iter, jnp.int64),
                               lane.state, n_iters=self.chunk_iters,
                               wss=self.wss)
        with obs.span("repro.pool.wait"):
            done = bool(lane.state.done)
        if done:
            self._retire(lane)

    def _step_batched(self, key, lanes: list[_Lane]) -> None:
        """One chunk over one source's selected lanes. A membership change
        (vs the cached pack) first evicts the cache — packed states flow
        back into the lanes — so repacking always starts from the freshest
        state."""
        ids = tuple(ln.id for ln in lanes)
        cached = self._packed.get(key)
        if cached is None or cached[0] != ids:
            if cached is not None:
                self._writeback(key)
            self._pack(key, lanes)
        # resolve BEFORE reading the pack: materializing this source may
        # evict another source (flushing ITS pack), never this group's
        src = self.resolve_source(key)
        masks, Cs, caps, states = self._packed[key][1]
        states = chunk_batched_jit(src, self._ys[key], masks,
                                   Cs, self.tol, caps, states,
                                   n_iters=self.chunk_iters, wss=self.wss)
        self._packed[key] = (ids, (masks, Cs, caps, states))
        with obs.span("repro.pool.wait"):
            done = np.asarray(states.done[:len(lanes)])   # one (w,) transfer
        if done.any():
            self._writeback(key)
            for flag, lane in zip(done, lanes):
                if flag:
                    self._retire(lane)

    def _step_shrink(self, key, cap: int, lanes: list[_Lane]) -> None:
        """One chunk over a shrink-enabled ``(source, cap)`` group, then
        the per-lane shrink lifecycle. ``cap == 0`` lanes run the normal
        full-set programs with their iteration cap pinned to the next
        heuristic boundary; shrunk lanes run the SAME chunk programs over
        their gathered compact operands at the relaxed ``10*tol`` (lanes
        of one bucket each carry their own gathered rows, so width > 1
        dispatches through ``chunk_batched_sources_jit``). States are
        packed fresh and written back every chunk — shrunk groups change
        membership as lanes migrate between cap buckets, so a packed-batch
        cache would thrash; the full-state mirror (``lane.state``) is kept
        fresh by ``shrink.advance``'s scatter, which is what snapshots and
        ``on_lane_chunk`` observe."""
        src, y = self.resolve_source(key), self._ys[key]
        for lane in lanes:
            if lane.shrink.cap and lane.shrink.idx is None:
                lane.shrink.enter(src, y, lane.state)
        if cap == 0:
            with obs.span("repro.pool.wait"):
                it_caps = [ln.shrink.it_cap(int(ln.state.n_iter),
                                            ln.max_iter) for ln in lanes]
            if len(lanes) == 1:
                ln = lanes[0]
                ln.state = chunk_jit(src, y, ln.train_mask, ln.C, self.tol,
                                     jnp.asarray(it_caps[0], jnp.int64),
                                     ln.state, n_iters=self.chunk_iters,
                                     wss=self.wss)
            else:
                width = bucket_width(len(lanes), self.lane_quantum)
                states = [ln.state for ln in lanes]
                masks = [ln.train_mask for ln in lanes]
                Cs = [ln.C for ln in lanes]
                for _ in range(width - len(lanes)):
                    states.append(lanes[0].state._replace(
                        done=jnp.ones((), bool)))
                    masks.append(lanes[0].train_mask)
                    Cs.append(lanes[0].C)
                    it_caps.append(0)
                out = chunk_batched_jit(
                    src, y, jnp.stack(masks), jnp.asarray(Cs, STATE_DTYPE),
                    self.tol, jnp.asarray(it_caps, jnp.int64),
                    EngineState.stack(states), n_iters=self.chunk_iters,
                    wss=self.wss)
                for i, ln in enumerate(lanes):
                    ln.state = out.lane(i)
        else:
            stol = 10.0 * self.tol
            with obs.span("repro.pool.wait"):
                it_caps = [ln.shrink.it_cap(int(ln.shrink.cstate.n_iter),
                                            ln.max_iter) for ln in lanes]
            if len(lanes) == 1:
                ls = lanes[0].shrink
                ls.cstate = chunk_jit(ls.csrc, ls.cy, ls.cmask, lanes[0].C,
                                      stol, jnp.asarray(it_caps[0], jnp.int64),
                                      ls.cstate, n_iters=self.chunk_iters,
                                      wss=self.wss)
            else:
                width = bucket_width(len(lanes), self.lane_quantum)
                srcs = [ln.shrink.csrc for ln in lanes]
                cys = [ln.shrink.cy for ln in lanes]
                cmasks = [ln.shrink.cmask for ln in lanes]
                cstates = [ln.shrink.cstate for ln in lanes]
                Cs = [ln.C for ln in lanes]
                for _ in range(width - len(lanes)):
                    pad = lanes[0].shrink
                    srcs.append(pad.csrc)
                    cys.append(pad.cy)
                    cmasks.append(pad.cmask)
                    cstates.append(pad.cstate._replace(
                        done=jnp.ones((), bool)))
                    Cs.append(lanes[0].C)
                    it_caps.append(0)
                out = chunk_batched_sources_jit(
                    stack_sources(srcs), jnp.stack(cys), jnp.stack(cmasks),
                    jnp.asarray(Cs, STATE_DTYPE), stol,
                    jnp.asarray(it_caps, jnp.int64),
                    EngineState.stack(cstates), n_iters=self.chunk_iters,
                    wss=self.wss)
                for i, ln in enumerate(lanes):
                    ln.shrink.cstate = out.lane(i)
        for ln in lanes:
            ln.state, verdict = shrink_mod.advance(
                ln.shrink, src, y, ln.train_mask, ln.C, ln.state,
                tol=self.tol, max_iter=ln.max_iter)
            if verdict == "retire":
                self._retire(ln)

    # ---------------------------------------------------------- observability

    def _lane_state(self, lane: _Lane) -> EngineState:
        """Current state of a live lane, reading through the packed cache."""
        cached = self._packed.get(lane.source)
        if cached is not None and lane.id in cached[0]:
            return cached[1][3].lane(cached[0].index(lane.id))
        return lane.state

    def tenant_stats(self) -> dict:
        """Per-tenant accounting: lane counts by lifecycle stage plus the
        fair-share ``served`` counter (lane-chunks dispatched). The
        daemon's ``status`` answer and the fairness tests read this."""
        stats: dict[Any, dict] = {}

        def rec(t):
            return stats.setdefault(
                t, {"lanes": 0, "live": 0, "pending": 0, "retired": 0,
                    "served": 0})

        for lane in self._lanes.values():
            r = rec(lane.tenant)
            r["lanes"] += 1
            if lane.result is not None:
                r["retired"] += 1
            elif lane.state is not None:
                r["live"] += 1
            else:
                r["pending"] += 1
        for t, n in self._tenant_served.items():
            rec(t)["served"] = n
        return stats

    def snapshot_lanes(self, *, only=None):
        """(lane_ids, tree) of every admitted-or-retired lane, stacked in
        lane-id (insertion) order — NOT packed position — so a mid-batch
        checkpoint restores by original lane id across any repack/resume
        boundary. ``tree`` = {alpha (L, n), f (L, n), n_iter (L,),
        done (L,)}; pending (unadmitted) lanes are omitted — their seeds
        re-derive from the retired results in the snapshot. ``only``
        restricts the snapshot to a membership test over lane ids (the
        daemon checkpoints each study's lanes separately: one tenant's
        instance set need not be shape-homogeneous with another's).

        Shrink-enabled pools additionally persist the per-lane shrink
        ledger — ``active`` (L, n) masks, ``shrunk``/``no_shrink`` (L,)
        flags and the ``unshrinks`` (L,) counter — so a mid-shrink resume
        re-enters the exact compact bucket (under ANY schedule shape or
        cap quantum) instead of re-deriving decisions; a live shrunk
        lane's mirror has fresh alpha everywhere and fresh f on active
        rows, which is exactly what re-gathering needs. ``shrink_every=0``
        pools emit the historical four-key tree byte-identically."""
        ids, alphas, fs, iters, dones = [], [], [], [], []
        actives, shrunks, noshrinks, unshrinks = [], [], [], []
        for lane_id in self._order:
            if only is not None and lane_id not in only:
                continue
            lane = self._lanes[lane_id]
            if lane.result is not None:
                src, done = lane.result, True
            elif lane.state is not None:
                src, done = self._lane_state(lane), False
            else:
                continue
            ids.append(lane_id)
            alphas.append(src.alpha)
            fs.append(src.f)
            iters.append(src.n_iter)
            dones.append(done)
            if self.shrink_every:
                ls = lane.shrink if lane.result is None else None
                if ls is not None and ls.shrunk:
                    actives.append(ls.active)
                else:
                    actives.append(jnp.ones(src.alpha.shape[0], bool))
                shrunks.append(bool(ls is not None and ls.shrunk))
                noshrinks.append(bool(ls is not None and ls.no_shrink))
                unshrinks.append(0 if ls is None else int(ls.unshrinks))
        if not ids:       # nothing admitted yet (daemon pre-first-chunk)
            return [], {}
        tree = {"alpha": jnp.stack(alphas), "f": jnp.stack(fs),
                "n_iter": jnp.stack(iters), "done": jnp.asarray(dones)}
        if self.shrink_every:
            tree["active"] = jnp.stack(actives)
            tree["shrunk"] = jnp.asarray(shrunks)
            tree["no_shrink"] = jnp.asarray(noshrinks)
            tree["unshrinks"] = jnp.asarray(unshrinks, jnp.int32)
        return ids, tree

    @property
    def occupancy(self) -> dict:
        """Schedule shape over the run. ``mean_live_width`` counts
        *runnable* lanes per chunk (the demand); ``mean_packed_width`` /
        ``peak_width`` count the *dispatched* program width summed over the
        chunk's source groups (after width capping and pad bucketing).
        live >> packed is the width-capped round-robin regime (CPU);
        live == packed == peak means retirement never compacted the batch
        (lanes converged together). Multi-source pools additionally report
        ``per_source`` live-width stats — the per-gamma demand profile that
        makes a straggler row visible in artifact diffs."""
        if not self._width_log:
            return {"chunks": 0, "mean_live_width": 0.0,
                    "mean_packed_width": 0.0, "peak_width": 0,
                    "programs": 0}
        lives = [w for w, _ in self._width_log]
        packed = [p for _, p in self._width_log]
        occ = {"chunks": len(self._width_log),
               "mean_live_width": round(sum(lives) / len(lives), 3),
               "mean_packed_width": round(sum(packed) / len(packed), 3),
               "peak_width": max(packed),
               "programs": len(self._programs)}
        if self.shrink_every:
            # HBM-roofline hook: a lane-dispatch at cap streams cap/n of
            # the full operand bytes, so this mean scales the bytes each
            # iteration reads
            occ["shrink_lane_chunks"] = len(self._frac_log)
            occ["mean_active_frac"] = round(
                sum(self._frac_log) / max(len(self._frac_log), 1), 4)
        if len(self.sources) > 1:
            occ["per_source"] = {
                str(key): {"chunks": n,
                           "mean_live_width": round(s / max(n, 1), 3),
                           "peak_live_width": peak}
                for key, (s, n, peak) in self._src_live.items()}
        return occ

