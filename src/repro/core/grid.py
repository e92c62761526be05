"""(C, gamma) hyper-parameter grid search over alpha-seeded k-fold CV.

The paper warm-starts fold h+1 from fold h. A hyper-parameter grid has two
more warm-start axes, and one big reuse axis, which this driver exploits as
ONE Study plan (``repro.core.study``):

* **kernel reuse** — the RBF kernel matrix depends on gamma only, so every
  C cell (and every fold) of a gamma row shares one kernel; each gamma is
  one *kernel source* of the plan, declared as a compute-on-demand
  ``KernelSpec`` factory and materialized through the pool's LRU cache
  under the ``max_resident``/``cache_bytes`` budget (DESIGN.md
  §Kernel-source cache) — grid memory scales with the budget, not
  ``len(gammas)``;
* **C-adjacent seeding** (``seed_across_C=True``) — fold 0 of cell
  (C_m, gamma) warm-starts from fold 0 of (C_{m-1}, gamma) via the
  ``"scale_C"`` transform (bounded-SV alphas scale ~linearly with C);
* **cross-gamma pooling** — every (gamma, cell, fold) solve is one lane
  of a single multi-source ``LanePool``: lanes carry their gamma's source
  key, packing buckets by (source, width), and admission is shared across
  sources. A straggler cell does not bound its gamma row's wall-clock —
  cells from OTHER gammas fill the schedule while it converges. A lane's
  iterate sequence depends only on its own (source, mask, C, state), so
  per-lane results are bit-identical to a one-gamma grid's.

The fold chain inside a cell stays sequential — that is the paper's
algorithm — but the grid turns its breadth axes into scheduler lanes:
lane (gi, ci, h) depends on (gi, ci, h-1) through the method's ``"fold"``
transform, so cells advance through their fold chains independently.

Per-lane evaluation is declared as plan ``EvalSpec``s: one jitted vmap per
(gamma, test-size) group computes every lane's held-out correct-count on
device, and a single transfer brings the counts back.

With a checkpoint manager, the whole grid checkpoints as one study
(plan-keyed ``"study"`` records, lane ids stable under resume): a killed
grid resumes every cell's exact iterate sequence.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.cv import _fold_masks, _transition_idx
from repro.core.study import Plan, StudyCheckpoint, run_plan
from repro.data.svm_suite import SVMDataset, kfold_chunks
from repro.svm import KernelSpec
from repro.svm.precision import STATE_DTYPE, kernel_input


@dataclasses.dataclass
class GridCell:
    C: float
    gamma: float
    iterations: int
    acc_correct: int
    acc_total: int
    converged: bool

    @property
    def accuracy(self) -> float:
        return self.acc_correct / max(self.acc_total, 1)


@dataclasses.dataclass
class GridReport:
    dataset: str
    method: str
    k: int
    n: int
    kernel_time: float
    seed_time: float
    solve_time: float
    cells: list[GridCell]
    #: LanePool width stats, with ``per_source`` live widths (one entry
    #: per gamma)
    occupancy: dict | None = None
    #: kernel-source cache account (materializations, evictions, peak
    #: resident sources/bytes) — the grid's memory-ceiling signal
    resident: dict | None = None

    @property
    def total_iterations(self) -> int:
        return int(sum(c.iterations for c in self.cells))

    def best(self) -> GridCell:
        return max(self.cells, key=lambda c: c.accuracy)

    def rows(self) -> list[dict]:
        return [{"dataset": self.dataset, "method": self.method,
                 "C": c.C, "gamma": c.gamma, "k": self.k,
                 "iterations": c.iterations,
                 "accuracy": round(c.accuracy, 4),
                 "converged": c.converged} for c in self.cells]


def _row_lanes(plan: Plan, gi: int, Cs, masks, transitions, method: str,
               seed_across_C: bool, max_iter: int, zeros, y, chunks) -> None:
    """Declare one gamma row's lane sub-graph (cells x folds) plus its
    evaluations on ``plan``; lane ids are (gamma index, C index, fold)."""
    k = masks.shape[0]
    for ci, C in enumerate(Cs):
        if method != "cold" and seed_across_C and ci > 0:
            plan.lane((gi, ci, 0), source=gi, train_mask=masks[0], C=C,
                      dep=(gi, ci - 1, 0), transform="scale_C",
                      params=dict(C_old=Cs[ci - 1], train_mask=masks[0]),
                      max_iter=max_iter)
        else:
            plan.lane((gi, ci, 0), source=gi, train_mask=masks[0], C=C,
                      alpha0=zeros, f0=-y, max_iter=max_iter)
        for h in range(1, k):
            if method == "cold":
                plan.lane((gi, ci, h), source=gi, train_mask=masks[h], C=C,
                          alpha0=zeros, f0=-y, max_iter=max_iter)
            else:
                S_idx, R_idx, T_idx = transitions[h]
                plan.lane((gi, ci, h), source=gi, train_mask=masks[h], C=C,
                          dep=(gi, ci, h - 1), transform="fold",
                          params=dict(method=method, S_idx=S_idx,
                                      R_idx=R_idx, T_idx=T_idx),
                          max_iter=max_iter)
        for h in range(k):
            plan.evaluate((gi, ci, h), chunks[h])


def _check_grid_args(source_backend: str, method: str) -> None:
    """The grid's own entry contract — checked before any plan is built
    or any kernel spec could resolve, so a typo fails at call time."""
    if source_backend not in ("dense", "pallas_rbf"):
        raise ValueError(f"unknown source_backend {source_backend!r} "
                         "(have 'dense', 'pallas_rbf')")
    if source_backend == "pallas_rbf" and method != "cold":
        raise ValueError("source_backend='pallas_rbf' requires "
                         "method='cold': fold-transition seeders "
                         "slab-index a dense kernel matrix")


def grid_plans(ds: SVMDataset, Cs, gammas, k: int = 10,
               method: str = "sir", tol: float = 1e-3,
               max_iter: int = 5_000_000, seed: int = 0,
               seed_across_C: bool = False, chunk_iters: int = 4096,
               kernel_backend: str = "jnp", lane_quantum: int = 4,
               max_width: int | None = None,
               max_resident: int = 0, cache_bytes: int = 0,
               source_backend: str = "dense", shrink_every: int | str = 0,
               shrink_quantum: int = 128, shrink_caps=None,
               shrink_on_seed: bool = True) -> list:
    """The exact multi-source ``Plan`` ``run_grid`` executes for these
    arguments, as a one-element list — built but not run. This is the
    static-analysis entry point: feed it to
    ``repro.analysis.plan_check.analyze_plan`` to enumerate compile
    shapes or budget feasibility without solving anything."""
    _check_grid_args(source_backend, method)
    Cs = sorted(float(c) for c in Cs)
    gammas = [float(g) for g in gammas]
    y_all = jnp.asarray(ds.y, STATE_DTYPE)
    X = kernel_input(ds.X)
    chunks = kfold_chunks(ds.n, k, seed=seed)
    n = chunks.size
    y = y_all[:n]
    masks = jnp.asarray(_fold_masks(chunks))
    transitions = {} if method == "cold" else \
        {h: _transition_idx(chunks, h - 1, h) for h in range(1, k)}
    # one DECLARED kernel per gamma — nothing is computed here. The spec
    # slices X to the k-fold truncation BEFORE the kernel call; core/cv.py
    # builds its kernel the same way, which keeps grid cells bit-identical
    # to run_cv (the two slice orders differ in final bits at some shapes)
    sources = {gi: KernelSpec(X=X, gamma=gamma, kind="rbf",
                              backend=kernel_backend, n=n)
               for gi, gamma in enumerate(gammas)}
    # cold-start alphas in the state dtype, matching run_cv's
    zeros = jnp.zeros(n, STATE_DTYPE)

    plan = Plan(sources=sources, y=y, tol=tol,
                wss="1" if source_backend == "pallas_rbf" else "2",
                chunk_iters=chunk_iters, lane_quantum=lane_quantum,
                max_width=max_width, max_resident=max_resident,
                cache_bytes=cache_bytes, source_backend=source_backend,
                shrink_every=shrink_every, shrink_quantum=shrink_quantum,
                shrink_caps=shrink_caps, shrink_on_seed=shrink_on_seed)
    for gi in sources:
        _row_lanes(plan, gi, Cs, masks, transitions, method,
                   seed_across_C, max_iter, zeros, y, chunks)
    return [plan]


def run_grid(ds: SVMDataset, Cs, gammas, k: int = 10, method: str = "sir",
             tol: float = 1e-3, max_iter: int = 5_000_000, seed: int = 0,
             seed_across_C: bool = False, chunk_iters: int = 4096,
             kernel_backend: str = "jnp", lane_quantum: int = 4,
             max_width: int | None = None,
             max_resident: int = 0, cache_bytes: int = 0,
             source_backend: str = "dense",
             checkpoint_manager=None,
             checkpoint_every: int = 1, shrink_every: int | str = 0,
             shrink_quantum: int = 128, shrink_caps=None,
             shrink_on_seed: bool = True) -> GridReport:
    """Cross-validate every (C, gamma) cell; returns per-cell accuracy and
    iteration counts (``GridReport.best()`` picks the winner).

    ``method`` is the fold-chain seeder inside each cell ("cold" disables
    chaining; every lane is then independent). ``seed_across_C``
    additionally chains fold 0 along ascending C within a gamma row —
    trades fold-0 concurrency for warm starts, which wins when C values
    are dense (adjacent cells share most of their support vectors).

    The whole grid runs as ONE multi-source lane pool — no per-row
    barrier, one study checkpoint. Per-cell results match ``run_cv`` on
    the same hyper-parameters (same seeders, same engine, bit-identical
    solves).

    Kernels are declared as factories (one ``KernelSpec`` per gamma) and
    materialize on demand through the pool's source cache.
    ``max_resident`` / ``cache_bytes`` (0 = unbounded) bound how many
    kernel matrices stay resident at once: under a budget, the scheduler
    drains each resident gamma's lanes before paying for the next kernel,
    evicting by schedule distance — memory scales with the budget instead
    of ``len(gammas) * n^2 * 8`` bytes, and per-cell results stay
    bit-identical under every budget (re-materialization is a pure
    function of (X, gamma)). ``kernel_time`` counts every materialization,
    including re-materializations after eviction or a mid-study resume;
    ``GridReport.resident`` carries the cache account.

    ``source_backend="pallas_rbf"`` resolves every gamma's spec to a
    row-streaming ``PallasRBF`` source instead of a dense matrix: no lane
    ever touches an n² kernel (peak resident bytes track X, not n²), WSS-1
    selection is forced, and evaluations run off row slabs. Requires
    ``method="cold"`` — the fold-transition seeders slab-index a dense K.

    ``shrink_every`` (iterations per heuristic evaluation, or ``"auto"``
    for the cost-model verdict) turns on bucketed active-set shrinking in
    every cell's solve (DESIGN.md §Shrinking): bound-locked variables are
    compacted out and the chunk programs run at bucketed capacities. The
    full-set optimality contract is preserved — per-cell accuracies and
    SV sets match the unshrunk grid; 0 (default) keeps every iterate
    bit-identical to today.
    """
    _check_grid_args(source_backend, method)
    Cs = sorted(float(c) for c in Cs)
    gammas = [float(g) for g in gammas]
    m = len(Cs)
    chunks = kfold_chunks(ds.n, k, seed=seed)
    n = chunks.size

    # one builder for the declared plan — grid_plans is also the static
    # analyzer's entry point, so what plan_check enumerates is exactly
    # what executes here
    (plan,) = grid_plans(
        ds, Cs, gammas, k=k, method=method, tol=tol, max_iter=max_iter,
        seed=seed, seed_across_C=seed_across_C, chunk_iters=chunk_iters,
        kernel_backend=kernel_backend, lane_quantum=lane_quantum,
        max_width=max_width, max_resident=max_resident,
        cache_bytes=cache_bytes, source_backend=source_backend,
        shrink_every=shrink_every, shrink_quantum=shrink_quantum,
        shrink_caps=shrink_caps, shrink_on_seed=shrink_on_seed)

    checkpoint = None
    if checkpoint_manager is not None:
        checkpoint = StudyCheckpoint(
            manager=checkpoint_manager, every=checkpoint_every,
            meta={"bench": "grid", "dataset": ds.name, "method": method,
                  "k": k, "seed": seed, "tol": tol, "max_iter": max_iter,
                  "Cs": Cs, "gammas": gammas,
                  "seed_across_C": seed_across_C,
                  "shrink_every": shrink_every})
    sres = run_plan(plan, checkpoint=checkpoint)

    src = sres.source_stats
    # kernel_time is attributed per MATERIALIZATION: each gamma's first
    # use, plus any re-materialization after eviction or a cold-cache
    # resume — the honest cost of the compute-on-demand schedule
    kernel_time = src.get("kernel_time", 0.0)
    resident = {key: src.get(key, 0)
                for key in ("materializations", "evictions", "peak_resident",
                            "peak_resident_bytes")}

    t_sz = chunks.shape[1]
    cells: list[GridCell] = []
    for gi, gamma in enumerate(gammas):
        for ci in range(m):
            lids = [(gi, ci, h) for h in range(k)]
            cells.append(GridCell(
                C=Cs[ci], gamma=gamma,
                iterations=int(sum(sres.stats[lid].n_iter for lid in lids)),
                acc_correct=int(sum(sres.evals[lid][0] for lid in lids)),
                acc_total=int(t_sz * k),
                converged=all(sres.stats[lid].converged for lid in lids)))

    return GridReport(dataset=ds.name, method=method, k=k, n=n,
                      kernel_time=kernel_time, seed_time=sres.seed_time,
                      solve_time=sres.solve_time, cells=cells,
                      occupancy=sres.occupancy, resident=resident)
