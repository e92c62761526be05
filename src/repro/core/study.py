"""Study API: one declarative lane-graph entry point over a multi-source
scheduler pool.

The paper's speedups come from chaining solves through seed transforms
(alpha seeding across folds); Joulani et al. frame incremental-learning CV
as one dependency structure over reusable partial solutions. This module
says that structure ONCE, declaratively: a ``Plan`` is a graph of
``LaneSpec``s (train mask, C, kernel-source key, seed dependency +
transform name) plus ``EvalSpec``s, and ``run_plan`` executes it on a
multi-source ``LanePool`` (DESIGN.md §Study API). ``run_cv``,
``run_cv_batched``, ``run_loo`` and ``run_grid`` are thin plan builders
over this entry point — bit-identical to their pre-redesign outputs under
every schedule.

Plan grammar (each lane is exactly one of):

* **start lane** — ``alpha0``/``f0`` (+ optional ``n_iter0`` when resuming
  a snapshot): dispatched immediately, or held by an ``after`` ordering
  edge (sequential protocols, e.g. the paper's fold chain, express their
  order without faking a seed dependency);
* **dependent lane** — ``dep`` (another lane id) + ``transform`` (a name
  in ``seeding.TRANSFORMS``) + ``params``: admitted the moment the
  dependency retires, started at ``transform(K, y, C, dep_result,
  **params)``. Dependencies may cross kernel sources;
* **given lane** — ``result``: an already-solved ``SMOResult`` (a restored
  fold) that participates as a seed dependency but never dispatches.

Because transforms are referenced by NAME + params instead of closures,
the lane graph is data: the caller rebuilds the identical plan on resume,
and the checkpoint only has to persist per-lane (alpha, f, n_iter, done)
keyed by lane id (``StudyCheckpoint``; records default to
``retain_class="study"``, lane ids are stable under resume, and a snapshot
written under one schedule shape restores under any other).

``EvalSpec``s declare held-out evaluations; ``run_plan`` batches them into
one jitted program per (source, test-size) group — a whole study's
evaluation is a handful of device calls.

Plan sources may be **factories** (``svm/sources.py:KernelSpec``) instead
of dense matrices: the pool materializes them on demand under the plan's
``max_resident``/``cache_bytes`` budget (schedule-distance eviction —
DESIGN.md §Kernel-source cache). Seed transforms and eval groups resolve
their K through the same cache, so a study's memory scales with the
budget, not the source count. The whole lane graph (edge targets,
transform names, source keys, dep/after acyclicity) is validated at
``run_plan`` entry — a typo'd edge fails by name immediately instead of
surfacing as a drain-time RuntimeError hours into a large study.
"""
from __future__ import annotations

import base64
import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import seeding
from repro.svm import shrink as shrink_mod
from repro.svm.engine import (DenseKernel, EngineState, SMOResult,
                              finalize)
from repro.svm.precision import kdot
from repro.svm.scheduler import LanePool
from repro.svm.sources import KernelSpec, is_factory
from repro.svm.smo import init_f
from repro.svm.svc import bias_from_solution, predict

#: study records live above every run_cv fold step (< _FOLD_STRIDE * k)
#: and every run_cv_batched batch step (_FOLD_STRIDE**2 + chunks), so all
#: three record kinds can share one checkpoint directory without step
#: collisions (``save`` replaces an existing step dir).
STUDY_BASE = 2 * 1_000_000 ** 2


@dataclasses.dataclass
class LaneSpec:
    """One node of the lane graph. See the module docstring for which
    field combinations are legal; ``source`` may be omitted in a
    single-source plan."""
    id: Any
    source: Any = None
    train_mask: Any = None
    C: float | None = None
    alpha0: Any = None
    f0: Any = None
    n_iter0: int = 0
    max_iter: int = 10_000_000
    dep: Any = None
    transform: str | None = None
    params: dict = dataclasses.field(default_factory=dict)
    after: Any = None
    result: Any = None


@dataclasses.dataclass
class EvalSpec:
    """Held-out evaluation of one lane: correct-count of ``predict`` over
    ``test_idx`` rows of the lane's kernel source."""
    lane: Any
    test_idx: Any


@dataclasses.dataclass
class Plan:
    """A declarative study: kernel sources, the lane graph, evaluations,
    and the schedule knobs forwarded to the ``LanePool``."""
    sources: dict
    y: Any                                # shared labels, or {source_key: y}
    lanes: list = dataclasses.field(default_factory=list)
    evals: list = dataclasses.field(default_factory=list)
    tol: float = 1e-3
    wss: str = "2"
    chunk_iters: int = 4096
    lane_quantum: int = 4
    max_width: int | None = None
    #: kernel-source residency budget (0 = unbounded): sources declared as
    #: factories (svm/sources.py:KernelSpec) materialize on demand and at
    #: most ``max_resident`` kernels / ``cache_bytes`` bytes stay resident
    #: (schedule-distance eviction — DESIGN.md §Kernel-source cache)
    max_resident: int = 0
    cache_bytes: int = 0
    #: kernel-source backend for the plan's declared ``KernelSpec``s:
    #: ``"dense"`` leaves them as declared; ``"pallas_rbf"`` rewrites every
    #: dense-RBF spec to the row-streaming kind (``svm/engine.py:PallasRBF``
    #: — nbytes = X bytes, fused, requires ``wss="1"``), so one knob flips
    #: a whole plan between n²-resident and row-streaming execution
    source_backend: str = "dense"
    #: active-set shrinking (``svm/shrink.py``): 0 = off (bit-identical to
    #: the pre-shrinking pool), an int = heuristic period in iterations,
    #: ``"auto"`` = backend-gated by the measured cost model
    #: (``cost_model.pick_shrink``). ``shrink_quantum`` buckets compact
    #: capacities (``shrink_caps`` declares an explicit ladder instead —
    #: what exact-program-count CI cells use); ``shrink_on_seed`` applies
    #: the seeding->shrinking handoff at admission
    shrink_every: int | str = 0
    shrink_quantum: int = 128
    shrink_caps: Any = None
    shrink_on_seed: bool = True
    #: support-vector-only evaluation: gather ``alpha > 0`` rows (the
    #: fixed-shape nonzero idiom at a ``shrink.bucket_cap`` capacity)
    #: before the eval matvec instead of multiplying through zero rows;
    #: dense-K groups only, falls back to the full path otherwise
    sv_eval: bool = False

    def lane(self, id, **kwargs) -> LaneSpec:
        spec = LaneSpec(id=id, **kwargs)
        self.lanes.append(spec)
        return spec

    def evaluate(self, lane, test_idx) -> None:
        self.evals.append(EvalSpec(lane, test_idx))

    def source_key_of(self, spec: LaneSpec) -> Any:
        if spec.source is not None:
            return spec.source
        if len(self.sources) == 1:
            return next(iter(self.sources))
        raise ValueError(f"lane {spec.id!r} needs a source key in a "
                         "multi-source plan")

    def y_of(self, key) -> jnp.ndarray:
        return self.y[key] if isinstance(self.y, dict) else self.y


@dataclasses.dataclass
class StudyCheckpoint:
    """Checkpoint wiring for ``run_plan``: every ``every``-th chunk, all
    admitted lanes' (alpha, f, n_iter, done) are saved stacked in lane-id
    order under ``retain_class`` at steps counting up from ``base_step``.
    ``meta`` is the plan identity — verified on resume, so a snapshot from
    a different study (or different solver parameters, which are part of
    the run identity: retired lanes carry fixed points at the snapshot's
    tolerance/budget) is rejected instead of silently mixed in."""
    manager: Any
    every: int = 1
    retain_class: str = "study"
    phase: str = "study_mid"
    base_step: int = STUDY_BASE
    meta: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LaneStat:
    """Per-lane execution account: iterations, convergence, the admission
    transform's wall time (the paper's "init."), the lane's share of its
    dispatch chunks, and whether it was restored pre-solved."""
    n_iter: int
    converged: bool
    seed_s: float
    solve_s: float
    restored: bool = False


@dataclasses.dataclass
class StudyResult:
    results: dict                         # lane id -> SMOResult
    stats: dict                           # lane id -> LaneStat
    evals: dict                           # lane id -> (correct, total)
    occupancy: dict
    seed_time: float
    solve_time: float                     # pool wall time minus seed_time
    restored: frozenset                   # lanes already done at pool start
    #: kernel-source cache account: materialization count/wall-time and
    #: peak residency (sources, bytes) — all zeros for all-dense plans
    source_stats: dict = dataclasses.field(default_factory=dict)
    #: pre-execution static analysis (``repro.analysis.plan_check``):
    #: compile-shape enumeration, budget feasibility, advisory findings;
    #: None when ``run_plan(..., analysis="off")``
    analysis: Any = None
    #: fair-share accounting tag the lanes ran under (the daemon sets it
    #: to the submitting client's tenant id; None for in-process runs)
    tenant: Any = None


@jax.jit
def _eval_lanes_jit(K, y, test_idx, train_masks, Cs, res):
    """Held-out correct-count for a batch of lanes — the same
    bias_from_solution + predict pipeline as the sequential CV path,
    vmapped so a whole eval group is ONE device program."""
    def one(ti, mask, C, r):
        b = bias_from_solution(r, y, mask, C)
        pred = predict(K[ti], y, r.alpha, b)
        return jnp.sum(pred == y[ti])

    return jax.vmap(one)(test_idx, train_masks, Cs, res)


@functools.partial(jax.jit, static_argnames=("cap",))
def _eval_lanes_sv_jit(K, y, test_idx, train_masks, Cs, res, cap):
    """Support-vector-only variant of ``_eval_lanes_jit``: each lane
    gathers its ``alpha > 0`` rows (the same fixed-shape
    ``nonzero(size=cap, fill_value=n)`` compact-gather idiom the
    shrinking scheduler uses — pad columns clamp to the last row and are
    zero-weighted) and the decision matvec contracts over ``cap`` support
    vectors instead of all n training rows. Same ``>= 0`` prediction
    convention as ``svc.predict``; summation order over the support set
    differs from the full matvec, so this path carries the usual allclose
    guarantee, not bit parity — which is why it is opt-in
    (``Plan.sv_eval``)."""
    def one(ti, mask, C, r):
        b = bias_from_solution(r, y, mask, C)
        sv = r.alpha > 0
        svi = jnp.nonzero(sv, size=cap, fill_value=y.shape[0])[0]
        coef = jnp.where(jnp.arange(cap) < jnp.sum(sv),
                         r.alpha[svi] * y[svi], 0.0)
        dec = kdot(K[ti][:, svi], coef) + b
        pred = jnp.where(dec >= 0, 1, -1)
        return jnp.sum(pred == y[ti])

    return jax.vmap(one)(test_idx, train_masks, Cs, res)


@jax.jit
def _eval_lanes_rows_jit(K_rows, y, test_idx, train_masks, Cs, res):
    """Row-slab variant for K-less (row-streaming) sources: ``K_rows``
    (b, t, n) holds each lane's test rows, computed by ``rows_at`` —
    O(t*n) transient per group, never n² resident."""
    def one(Kr, ti, mask, C, r):
        b = bias_from_solution(r, y, mask, C)
        pred = predict(Kr, y, r.alpha, b)
        return jnp.sum(pred == y[ti])

    return jax.vmap(one)(K_rows, test_idx, train_masks, Cs, res)


def _freeze(x):
    """JSON round-trips tuples as lists; lane ids are hashable keys, so
    freeze them back on restore."""
    return tuple(_freeze(v) for v in x) if isinstance(x, list) else x


# --------------------------------------------------------------------------
# Wire serialization: the study-service plan/result format. A Plan is
# already data (transforms by NAME, checkpoints by lane id), so the wire
# format is a direct JSON image of the dataclasses, with arrays carried as
# ``{"__nd__": 1, dtype, shape, data: base64(raw bytes)}`` — an EXACT bit
# round-trip, which is what lets a served study stay bit-identical to the
# in-process ``run_plan`` of the same plan. ``plan_from_dict`` is the
# hostile-input half: it re-freezes ids, and rejects unknown transform
# names, unknown source kinds and non-finite hyperparameters AT PARSE TIME
# with the same by-name errors as ``_validate_plan`` — a daemon never
# holds an unparseable plan object in memory waiting for admission to
# notice.
# --------------------------------------------------------------------------

#: the source kinds a wire plan may declare (svm/kernels.py dense kinds
#: plus the row-streaming Pallas source)
WIRE_SOURCE_KINDS = ("rbf", "linear", "pallas_rbf")


def _nd_to_wire(a) -> dict:
    a = np.ascontiguousarray(np.asarray(a))
    return {"__nd__": 1, "dtype": str(a.dtype), "shape": list(a.shape),
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def _nd_from_wire(d) -> np.ndarray:
    a = np.frombuffer(base64.b64decode(d["data"]), dtype=np.dtype(d["dtype"]))
    return a.reshape([int(s) for s in d["shape"]]).copy()


def _to_wire(v):
    """JSON-encodable image of a plan field value: arrays via the nd
    codec, tuples as lists (re-frozen on parse), numpy scalars unboxed.
    Python floats survive JSON exactly (shortest-round-trip repr)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (np.bool_, np.integer, np.floating)):
        return v.item()
    if isinstance(v, (np.ndarray, jax.Array)):
        return _nd_to_wire(v)
    if isinstance(v, (list, tuple)):
        return [_to_wire(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _to_wire(val) for k, val in v.items()}
    raise TypeError(f"cannot serialize {type(v).__name__!r} value {v!r}")


def _from_wire(v):
    """Inverse of ``_to_wire``; lists come back as TUPLES (wire lists only
    occur where hashability matters: ids, params, shrink_caps)."""
    if isinstance(v, dict):
        if v.get("__nd__") == 1:
            return _nd_from_wire(v)
        return {k: _from_wire(val) for k, val in v.items()}
    if isinstance(v, list):
        return tuple(_from_wire(x) for x in v)
    return v


def _check_finite(value, what: str):
    """Parse-time hyperparameter gate: a NaN/inf C, gamma or tol would
    pass every structural check and then poison a shared pool's solves."""
    if value is None:
        return None
    value = float(value)
    if not math.isfinite(value):
        raise ValueError(f"{what}: non-finite value {value!r}")
    return value


def result_to_dict(r: SMOResult) -> dict:
    """Wire image of an ``SMOResult`` (bit-exact: arrays via the nd
    codec, scalars as JSON numbers)."""
    return {"alpha": _nd_to_wire(r.alpha), "f": _nd_to_wire(r.f),
            "n_iter": int(r.n_iter), "converged": bool(r.converged),
            "b_up": float(r.b_up), "b_low": float(r.b_low)}


def result_from_dict(d: dict) -> SMOResult:
    return SMOResult(
        alpha=jnp.asarray(_nd_from_wire(d["alpha"])),
        f=jnp.asarray(_nd_from_wire(d["f"])),
        n_iter=jnp.asarray(int(d["n_iter"]), jnp.int64),
        converged=jnp.asarray(bool(d["converged"])),
        b_up=jnp.asarray(float(d["b_up"])),
        b_low=jnp.asarray(float(d["b_low"])))


def _source_to_wire(key, entry) -> dict:
    if isinstance(entry, KernelSpec):
        return {"kind_tag": "spec", "X": _nd_to_wire(entry.X),
                "gamma": float(entry.gamma), "kind": entry.kind,
                "backend": entry.backend,
                "n": None if entry.n is None else int(entry.n)}
    K = getattr(entry, "K", None)
    if K is not None and not is_factory(entry):
        return {"kind_tag": "dense", "K": _nd_to_wire(K)}
    raise TypeError(
        f"source {key!r}: only KernelSpec and dense-K sources serialize "
        f"(got {type(entry).__name__!r}) — opaque sources cannot cross "
        "the wire")


def _source_from_wire(key, d: dict):
    tag = d.get("kind_tag")
    if tag == "dense":
        K = jnp.asarray(_nd_from_wire(d["K"]))
        return DenseKernel(K)
    if tag != "spec":
        raise ValueError(f"source {key!r}: unknown source entry tag "
                         f"{tag!r} (have 'spec', 'dense')")
    kind = d.get("kind")
    if kind not in WIRE_SOURCE_KINDS:
        raise ValueError(f"source {key!r}: unknown source kind {kind!r} "
                         f"(have {sorted(WIRE_SOURCE_KINDS)})")
    gamma = _check_finite(d.get("gamma", 1.0), f"source {key!r}: gamma")
    return KernelSpec(jnp.asarray(_nd_from_wire(d["X"])), gamma=gamma,
                      kind=kind, backend=d.get("backend", "jnp"),
                      n=None if d.get("n") is None else int(d["n"]))


def plan_to_dict(plan: Plan) -> dict:
    """JSON-encodable image of a ``Plan``. Source/y keys ride as
    ``[key, value]`` pairs (JSON objects cannot key by tuple/float);
    ``plan_from_dict`` re-freezes them."""
    y = plan.y
    y_wire = {"__ymap__": 1,
              "items": [[_to_wire(k), _nd_to_wire(v)]
                        for k, v in y.items()]} \
        if isinstance(y, dict) else _nd_to_wire(y)
    lanes = []
    for spec in plan.lanes:
        lanes.append({
            "id": _to_wire(spec.id), "source": _to_wire(spec.source),
            "train_mask": None if spec.train_mask is None
            else _nd_to_wire(spec.train_mask),
            "C": None if spec.C is None else float(spec.C),
            "alpha0": None if spec.alpha0 is None
            else _nd_to_wire(spec.alpha0),
            "f0": None if spec.f0 is None else _nd_to_wire(spec.f0),
            "n_iter0": int(spec.n_iter0), "max_iter": int(spec.max_iter),
            "dep": _to_wire(spec.dep), "transform": spec.transform,
            "params": _to_wire(dict(spec.params)),
            "after": _to_wire(spec.after),
            "result": None if spec.result is None
            else result_to_dict(spec.result)})
    return {"__plan__": 1,
            "sources": [[_to_wire(k), _source_to_wire(k, v)]
                        for k, v in plan.sources.items()],
            "y": y_wire,
            "lanes": lanes,
            "evals": [[_to_wire(ev.lane), _nd_to_wire(ev.test_idx)]
                      for ev in plan.evals],
            "tol": float(plan.tol), "wss": plan.wss,
            "chunk_iters": int(plan.chunk_iters),
            "lane_quantum": int(plan.lane_quantum),
            "max_width": None if plan.max_width is None
            else int(plan.max_width),
            "max_resident": int(plan.max_resident),
            "cache_bytes": int(plan.cache_bytes),
            "source_backend": plan.source_backend,
            "shrink_every": plan.shrink_every,
            "shrink_quantum": int(plan.shrink_quantum),
            "shrink_caps": _to_wire(plan.shrink_caps),
            "shrink_on_seed": bool(plan.shrink_on_seed),
            "sv_eval": bool(plan.sv_eval)}


def plan_from_dict(d: dict) -> Plan:
    """Parse a wire plan, rejecting hostile content at PARSE time: unknown
    transform names and source kinds, and non-finite hyperparameters (C,
    gamma, tol) raise the same by-name errors ``_validate_plan`` uses —
    before any object that could reach a pool exists. Structural rules
    (edge targets, cycles, duplicate ids) remain ``_validate_plan``'s
    job; admission calls it via ``check_plan``."""
    if not isinstance(d, dict) or d.get("__plan__") != 1:
        raise ValueError("not a wire plan (missing '__plan__': 1)")
    sources = {}
    for key_w, entry_w in d.get("sources", ()):
        key = _from_wire(key_w)
        if key in sources:
            raise ValueError(f"duplicate source key {key!r}")
        sources[key] = _source_from_wire(key, entry_w)
    y_w = d.get("y")
    if isinstance(y_w, dict) and y_w.get("__ymap__") == 1:
        y = {_from_wire(k): jnp.asarray(_nd_from_wire(v))
             for k, v in y_w["items"]}
    else:
        y = jnp.asarray(_nd_from_wire(y_w))
    tol = _check_finite(d.get("tol", 1e-3), "tol")
    if tol <= 0:
        raise ValueError(f"tol: non-positive value {tol!r}")
    lanes = []
    for lw in d.get("lanes", ()):
        lid = _from_wire(lw.get("id"))
        transform = lw.get("transform")
        if transform is not None and transform not in seeding.TRANSFORMS:
            raise ValueError(f"lane {lid!r}: unknown transform "
                             f"{transform!r} (have "
                             f"{sorted(seeding.TRANSFORMS)})")
        C = _check_finite(lw.get("C"), f"lane {lid!r}: C")
        params = _from_wire(lw.get("params") or {})
        for pk, pv in params.items():
            if isinstance(pv, float):
                _check_finite(pv, f"lane {lid!r}: params[{pk!r}]")
        lanes.append(LaneSpec(
            id=lid, source=_from_wire(lw.get("source")),
            train_mask=None if lw.get("train_mask") is None
            else jnp.asarray(_nd_from_wire(lw["train_mask"])),
            C=C,
            alpha0=None if lw.get("alpha0") is None
            else jnp.asarray(_nd_from_wire(lw["alpha0"])),
            f0=None if lw.get("f0") is None
            else jnp.asarray(_nd_from_wire(lw["f0"])),
            n_iter0=int(lw.get("n_iter0", 0)),
            max_iter=int(lw.get("max_iter", 10_000_000)),
            dep=_from_wire(lw.get("dep")), transform=transform,
            params=params, after=_from_wire(lw.get("after")),
            result=None if lw.get("result") is None
            else result_from_dict(lw["result"])))
    evals = [EvalSpec(_from_wire(lane_w),
                      jnp.asarray(_nd_from_wire(idx_w)))
             for lane_w, idx_w in d.get("evals", ())]
    shrink_every = d.get("shrink_every", 0)
    if shrink_every != "auto":
        shrink_every = int(shrink_every)
    caps = _from_wire(d.get("shrink_caps"))
    return Plan(sources=sources, y=y, lanes=lanes, evals=evals,
                tol=tol, wss=str(d.get("wss", "2")),
                chunk_iters=int(d.get("chunk_iters", 4096)),
                lane_quantum=int(d.get("lane_quantum", 4)),
                max_width=None if d.get("max_width") is None
                else int(d["max_width"]),
                max_resident=int(d.get("max_resident", 0)),
                cache_bytes=int(d.get("cache_bytes", 0)),
                source_backend=str(d.get("source_backend", "dense")),
                shrink_every=shrink_every,
                shrink_quantum=int(d.get("shrink_quantum", 128)),
                shrink_caps=caps,
                shrink_on_seed=bool(d.get("shrink_on_seed", True)),
                sv_eval=bool(d.get("sv_eval", False)))


def _make_seed_fn(plan: Plan, spec: LaneSpec, resolve):
    """Build the pool-facing seed closure for a dependent lane. ``resolve``
    maps a source key to a USABLE source at call time (the pool's residency
    cache) — K is looked up lazily, at admission, so factory sources only
    materialize when a lane of theirs actually seeds."""
    fn = seeding.TRANSFORMS[spec.transform]
    key = plan.source_key_of(spec)
    y, C, params = plan.y_of(key), spec.C, dict(spec.params)

    def seed(prev):
        source = resolve(key)
        K = getattr(source, "K", None)
        if K is None:
            # kernel-free transforms (seeding.py marks them) never touch
            # K; f0 comes from the source's streaming matvec instead of
            # the dense init_f
            if getattr(fn, "kernel_free", False) and \
                    callable(getattr(source, "matvec", None)):
                alpha0 = fn(None, y, C, prev, **params)
                return alpha0, source.matvec(alpha0 * y) - y
            raise ValueError(f"lane {spec.id!r}: transform "
                             f"{spec.transform!r} needs a dense kernel "
                             f"source (source {key!r} has no K)")
        alpha0 = fn(K, y, C, prev, **params)
        return alpha0, init_f(K, y, alpha0)

    seed.transform = spec.transform       # named by the repro.pool.seed span
    return seed


def _check_dense(plan: Plan, lane_id, key, what: str,
                 transform: str | None = None) -> None:
    """Seed transforms and evaluations need a dense K — unless the
    source supports the K-less alternative: kernel-free transforms run
    off a streaming ``matvec``, evaluations off a ``rows_at`` row slab.
    For an already-materialized (pinned) source that is checkable AT
    ENTRY — an incompatible source must not fail only after its
    dependency solved for hours. Factory entries stay deferred for the
    capabilities a spec cannot declare; the lazy resolution re-checks."""
    entry = plan.sources[key]
    if is_factory(entry) or getattr(entry, "K", None) is not None:
        return
    if transform is not None:
        fn = seeding.TRANSFORMS[transform]
        if getattr(fn, "kernel_free", False) and \
                callable(getattr(entry, "matvec", None)):
            return
    elif callable(getattr(entry, "rows_at", None)):
        return
    raise ValueError(f"lane {lane_id!r}: {what} a dense kernel "
                     f"source (source {key!r} has no K)")


def _validate_plan(plan: Plan, specs: dict) -> None:
    """Fail fast, by name, on a malformed lane graph. A typo'd ``dep`` /
    ``after`` edge or an unknown source key used to surface only at drain
    time, as ``LanePool.run``'s "missing or cyclic dep" RuntimeError
    listing EVERY pending lane — after hours of solving on a large study.
    Here every edge target, transform name and source key is checked at
    ``run_plan`` entry, and dep/after cycles are reported as the cycle."""
    for spec in plan.lanes:
        if spec.source is not None and spec.source not in plan.sources:
            raise ValueError(f"lane {spec.id!r}: unknown source key "
                             f"{spec.source!r} (plan has "
                             f"{sorted(map(repr, plan.sources))})")
        for edge, target in (("dep", spec.dep), ("after", spec.after)):
            if target is not None and target not in specs:
                raise ValueError(
                    f"lane {spec.id!r}: {edge} edge targets undeclared "
                    f"lane {target!r}")
        if spec.dep is not None:
            if spec.transform not in seeding.TRANSFORMS:
                raise ValueError(f"lane {spec.id!r}: unknown transform "
                                 f"{spec.transform!r} (have "
                                 f"{sorted(seeding.TRANSFORMS)})")
            _check_dense(plan, spec.id, plan.source_key_of(spec),
                         f"transform {spec.transform!r} needs",
                         transform=spec.transform)
    for ev in plan.evals:
        if ev.lane not in specs:
            raise ValueError(f"EvalSpec targets undeclared lane {ev.lane!r}")
        _check_dense(plan, ev.lane, plan.source_key_of(specs[ev.lane]),
                     "evaluation needs")
    # cycle check over the admission edges (given lanes are pre-resolved
    # and cannot be part of a cycle): iterative three-color DFS
    edges = {spec.id: [t for t in (spec.dep, spec.after)
                       if t is not None and specs[t].result is None]
             for spec in plan.lanes if spec.result is None}
    state: dict = {}                       # id -> "on_path" | "done"
    for root in edges:
        if root in state:
            continue
        stack = [(root, iter(edges.get(root, ())))]
        state[root] = "on_path"
        while stack:
            node, it = stack[-1]
            for target in it:
                if state.get(target) == "on_path":
                    path = [n for n, _ in stack]
                    cycle = path[path.index(target):] + [target]
                    raise ValueError(
                        "lane graph has a dep/after cycle: "
                        + " -> ".join(repr(n) for n in cycle))
                if target not in state:
                    state[target] = "on_path"
                    stack.append((target, iter(edges.get(target, ()))))
                    break
            else:
                state[node] = "done"
                stack.pop()


def resolve_source_backend(plan: Plan) -> Plan:
    """Validate ``plan.source_backend`` and apply it: ``"pallas_rbf"``
    rewrites every dense-RBF spec to the row-streaming kind (and requires
    WSS-1). This runs at entry — both ``run_plan`` and the static
    analyzer (``repro.analysis.plan_check``) resolve through here, so a
    typo'd backend fails before any kernel could materialize."""
    if plan.source_backend not in ("dense", "pallas_rbf"):
        raise ValueError(f"unknown source_backend {plan.source_backend!r} "
                         "(have 'dense', 'pallas_rbf')")
    if plan.source_backend == "pallas_rbf":
        if plan.wss != "1":
            raise ValueError("source_backend='pallas_rbf' streams both "
                             "kernel rows through the fused step kernel "
                             "and requires WSS-1 (wss='1')")
        plan = dataclasses.replace(plan, sources={
            k: (dataclasses.replace(s, kind="pallas_rbf")
                if isinstance(s, KernelSpec) and s.kind == "rbf" else s)
            for k, s in plan.sources.items()})
    return plan


def plan_specs(plan: Plan) -> dict:
    """``{lane_id: LaneSpec}`` with the duplicate-id check — the one
    spec index ``run_plan`` and the study daemon both build."""
    specs: dict[Any, LaneSpec] = {}
    for spec in plan.lanes:
        if spec.id in specs:
            raise ValueError(f"duplicate lane id {spec.id!r}")
        specs[spec.id] = spec
    return specs


def restore_study_lanes(checkpoint: StudyCheckpoint | None):
    """Load the newest committed study record (identity verified against
    ``checkpoint.meta``): returns ``(step0, {lane_id: (alpha, f, n_iter,
    done, shrink0)})`` — empty when there is nothing to resume. Factored
    out of ``run_plan`` so the daemon resumes a killed study through the
    exact code path the in-process API uses."""
    restored: dict[Any, tuple] = {}
    step0 = 0
    if checkpoint is None:
        return step0, restored
    snap = checkpoint.manager.restore_latest_of_class(
        checkpoint.retain_class)
    if snap is None:
        return step0, restored
    step0, tree, extra = snap
    want = {"phase": checkpoint.phase, **checkpoint.meta}
    got = {key: extra.get(key) for key in want}
    if got != want:
        raise ValueError(
            f"checkpoint at step {step0} belongs to run {got}, "
            f"cannot resume it as {want}; point the manager at a "
            "fresh directory or delete the stale checkpoints")
    for i, lid in enumerate(extra["lane_ids"]):
        # the shrink ledger rides along when the snapshotting pool
        # had shrinking on (absent in legacy/shrink-off snapshots):
        # a mid-shrink lane re-enters its exact compact bucket
        shrink0 = None
        if "active" in tree:
            shrink0 = (
                jnp.asarray(tree["active"][i])
                if bool(tree["shrunk"][i]) else None,
                bool(tree["no_shrink"][i]),
                int(tree["unshrinks"][i]))
        restored[_freeze(lid)] = (
            jnp.asarray(tree["alpha"][i]), jnp.asarray(tree["f"][i]),
            int(tree["n_iter"][i]), bool(tree["done"][i]), shrink0)
    return step0, restored


def enroll_plan_lanes(pool: LanePool, plan: Plan, specs: dict,
                      restored: dict, *, tenant=None) -> set:
    """Register every plan lane with ``pool`` — given results directly,
    restored lanes from their snapshot state, dependent lanes with their
    lazy seed closure. Returns the ids that entered pre-solved. The plan
    must already be validated and its sources present in the pool (the
    daemon admits sources separately, under dedup)."""
    pre_done: set = set()
    for spec in plan.lanes:
        key = plan.source_key_of(spec) if spec.result is None else None
        if spec.result is not None:
            pool.add_result(spec.id, spec.result, tenant=tenant)
            pre_done.add(spec.id)
        elif spec.id in restored:
            alpha, f, n_it, done, shrink0 = restored[spec.id]
            if done:
                # a retired lane: re-finalize its snapshot state (optimality
                # is a pure function of alpha/f, so converged/b_up/b_low
                # come back identical to the pre-crash result)
                state = EngineState(alpha, f, jnp.asarray(n_it, jnp.int64),
                                    jnp.ones((), bool))
                pool.add_result(spec.id, finalize(
                    state, plan.y_of(key), spec.train_mask, spec.C,
                    plan.tol), tenant=tenant)
                pre_done.add(spec.id)
            else:
                # mid-flight at the crash: it was already admitted, so its
                # plan-declared edges are history — resume the state as-is
                pool.add(spec.id, spec.train_mask, spec.C, alpha, f,
                         source=key, n_iter0=n_it, max_iter=spec.max_iter,
                         shrink0=shrink0, tenant=tenant)
        elif spec.dep is not None:
            pool.add(spec.id, spec.train_mask, spec.C, source=key,
                     dep=spec.dep,
                     seed_fn=_make_seed_fn(plan, spec, pool.resolve_source),
                     max_iter=spec.max_iter, after=spec.after, tenant=tenant)
        else:
            pool.add(spec.id, spec.train_mask, spec.C, spec.alpha0, spec.f0,
                     source=key, n_iter0=spec.n_iter0,
                     max_iter=spec.max_iter, after=spec.after, tenant=tenant)
    return pre_done


def run_plan_evals(pool: LanePool, plan: Plan, specs: dict,
                   results: dict) -> dict:
    """The plan's held-out evaluations: one jitted program per
    (source, test-size) group. Same-source groups run back-to-back,
    resident sources first, so a budgeted cache re-materializes each
    remaining source at most once (the residency snapshot is taken
    before any eval materializes)."""
    evals: dict[Any, tuple[int, int]] = {}
    groups: dict[tuple, list[EvalSpec]] = {}
    for ev in plan.evals:
        spec = specs[ev.lane]
        t_sz = int(np.asarray(ev.test_idx).shape[0])
        groups.setdefault((plan.source_key_of(spec), t_sz), []).append(ev)
    order0 = {}
    for key, _ in groups:
        order0.setdefault(key, len(order0))
    key_rank = {key: (not pool.cache.resident(key), order0[key])
                for key in order0}
    for (key, t_sz), evs in sorted(groups.items(),
                                   key=lambda kv: key_rank[kv[0][0]]):
        source, y = pool.resolve_source(key), plan.y_of(key)
        K = getattr(source, "K", None)
        if K is None and not callable(getattr(source, "rows_at", None)):
            raise ValueError(f"EvalSpec on lane {evs[0].lane!r}: evaluation "
                             f"needs a dense kernel source (source {key!r} "
                             "has no K)")
        res = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[results[ev.lane] for ev in evs])
        test_idx = jnp.asarray(np.stack([np.asarray(ev.test_idx)
                                         for ev in evs]))
        masks = jnp.stack([specs[ev.lane].train_mask for ev in evs])
        Cs = jnp.asarray([specs[ev.lane].C for ev in evs], jnp.float64)
        if K is None:
            # K-less source: one O(b*t*n) row slab per group instead of K
            K_rows = source.rows_at(test_idx.reshape(-1)).reshape(
                test_idx.shape[0], t_sz, -1)
            correct = jax.device_get(
                _eval_lanes_rows_jit(K_rows, y, test_idx, masks, Cs, res))
        else:
            cap_sv = 0
            if plan.sv_eval:
                # shared compact-gather bucketing: one cap per group (the
                # widest lane's SV count, rounded up) keeps this at one
                # compiled program per (source, t_sz, cap) instead of one
                # per lane; a cap that wouldn't shrink the contraction
                # falls back to the full path
                n_rows = int(np.shape(y)[0])
                cap_sv = shrink_mod.bucket_cap(
                    int(np.max(jax.device_get(
                        jnp.sum(res.alpha > 0, axis=1)))), 128)
                if cap_sv >= n_rows:
                    cap_sv = 0
            if cap_sv:
                correct = jax.device_get(_eval_lanes_sv_jit(
                    K, y, test_idx, masks, Cs, res, cap_sv))
            else:
                correct = jax.device_get(
                    _eval_lanes_jit(K, y, test_idx, masks, Cs, res))
        for ev, c in zip(evs, correct):
            evals[ev.lane] = (int(c), t_sz)
    return evals


def run_plan(plan: Plan, *, checkpoint: StudyCheckpoint | None = None,
             on_result=None, on_lane_chunk=None,
             analysis: str = "advisory", tenant=None) -> StudyResult:
    """Execute a ``Plan`` on one multi-source ``LanePool``.

    ``on_result(lane_id, result)`` streams each lane's ``SMOResult`` the
    moment it retires (long studies consume results without waiting for
    the pool to drain); ``on_lane_chunk(lane_id, state)`` observes every
    live lane between its chunks (the per-lane checkpoint hook legacy
    drivers use for their own record formats).

    With ``checkpoint``, the newest committed study record is restored
    first (identity verified against ``checkpoint.meta``): lanes found
    ``done`` re-enter as results, live lanes resume their exact iterate
    sequence, and pending lanes re-derive their seeds from the restored
    results — bit-identical to the uninterrupted run, under ANY schedule
    shape on either side of the crash.

    ``analysis`` wires the static plan analyzer
    (``repro.analysis.plan_check``): ``"advisory"`` (default) attaches
    the pre-execution report to ``StudyResult.analysis``; ``"strict"``
    raises on error-severity findings (budget-infeasible sources,
    checkpoint key collisions) BEFORE anything dispatches — the same
    gate a plan-admitting daemon calls; ``"off"`` skips it.
    """
    if analysis not in ("advisory", "strict", "off"):
        raise ValueError(f"unknown analysis mode {analysis!r} "
                         "(have 'advisory', 'strict', 'off')")
    with obs.span(obs.PLAN) as plan_span:
        with obs.span("repro.plan.prepare"):
            plan = resolve_source_backend(plan)
            specs = plan_specs(plan)
            _validate_plan(plan, specs)

        plan_analysis = None
        if analysis != "off":
            # deferred import: plan_check imports this module for the
            # validation surface and STUDY_BASE
            from repro.analysis import plan_check
            with obs.span("repro.plan.analyze"):
                if analysis == "strict":
                    plan_analysis = plan_check.check_plan(
                        plan, checkpoint=checkpoint)
                else:
                    plan_analysis = plan_check.analyze_plan(
                        plan, checkpoint=checkpoint)

        with obs.span("repro.plan.prepare"):
            step0, restored = restore_study_lanes(checkpoint)

        on_snapshot = None
        if checkpoint is not None:
            counter = {"c": max(step0, checkpoint.base_step)}

            def on_snapshot(pool):
                counter["c"] += 1
                lane_ids, tree = pool.snapshot_lanes()
                checkpoint.manager.save(
                    counter["c"], tree,
                    extra_meta={"phase": checkpoint.phase,
                                "lane_ids": lane_ids, **checkpoint.meta},
                    blocking=False, retain_class=checkpoint.retain_class)

        with obs.span("repro.pool.build"):
            pool = LanePool(
                plan.sources, plan.y, tol=plan.tol, wss=plan.wss,
                chunk_iters=plan.chunk_iters, lane_quantum=plan.lane_quantum,
                max_width=plan.max_width, max_resident=plan.max_resident,
                cache_bytes=plan.cache_bytes, on_snapshot=on_snapshot,
                snapshot_every=checkpoint.every if checkpoint else 1,
                on_result=on_result, on_lane_chunk=on_lane_chunk,
                shrink_every=plan.shrink_every,
                shrink_quantum=plan.shrink_quantum,
                shrink_caps=plan.shrink_caps,
                shrink_on_seed=plan.shrink_on_seed)
            pre_done = enroll_plan_lanes(pool, plan, specs, restored,
                                         tenant=tenant)

        kt0 = pool.cache.kernel_time
        with obs.span("repro.pool.run") as run_span:
            results = pool.run()
            with obs.span("repro.pool.wait"):
                jax.block_until_ready([results[s.id].alpha
                                       for s in plan.lanes])
        # kernel materializations during the run are attributed to the cache's
        # kernel_time (source_stats), not to seed or solve time
        wall = run_span.seconds - (pool.cache.kernel_time - kt0)
        if checkpoint is not None:
            checkpoint.manager.wait()

        stats = {}
        for spec in plan.lanes:
            res = results[spec.id]
            seed_s, solve_s = pool.lane_times(spec.id)
            stats[spec.id] = LaneStat(
                n_iter=int(res.n_iter), converged=bool(res.converged),
                seed_s=seed_s, solve_s=solve_s, restored=spec.id in pre_done)

        with obs.span("repro.plan.evals"):
            evals = run_plan_evals(pool, plan, specs, results)
        occupancy, source_stats = pool.occupancy, pool.cache.stats
        # the pool and its cache reference each other (eviction and trace
        # callbacks), so they outlive this call until Python's cycle collector
        # runs; dropping the sources now frees a dense K (4.2 GB at adult's
        # size) when the caller lets go of it, before the next entry-point call
        # builds its own
        with obs.span("repro.plan.release"):
            for key in list(pool.sources):
                pool.remove_source(key)
        plan_span.attrs["lanes"] = tuple(
            (lane_id, st.n_iter) for lane_id, st in stats.items()
            if not st.restored)
        return StudyResult(results=results, stats=stats, evals=evals,
                           occupancy=occupancy, seed_time=pool.seed_time,
                           solve_time=wall - pool.seed_time,
                           restored=frozenset(pre_done),
                           source_stats=source_stats,
                           analysis=plan_analysis, tenant=tenant)
