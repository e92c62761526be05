"""k-fold cross-validation drivers — thin plan builders over the Study API.

Reproduces the paper's experimental protocol: fold 0 starts cold; fold h>0
warm-starts from the most recent completed fold via the chosen seeder. Each
driver DECLARES that structure as a ``repro.core.study.Plan`` — lanes with
seed dependencies carrying named transforms — and ``run_plan`` executes it
on the lane pool; the drivers keep their historical signatures, record
formats and (bit-identical) outputs.

``run_cv`` is also the fault-tolerance unit, at two granularities:

* fold-level (always on with a checkpoint manager): each completed fold is
  checkpointed (fold index + alpha + f) from the pool's per-lane
  retirement callback, so a restarted job re-seeds from the last completed
  fold — the paper's own mechanism doubles as the recovery path. On
  restore, EVERY retained done record is loaded: the resumed report covers
  the pre-crash folds (``FoldStat.restored``) so its totals match an
  uninterrupted run, or ``CVReport.partial`` flags the gap when retention
  GC dropped some;
* chunk-level (opt-in via ``chunk_iters``): the pool's per-lane chunk hook
  snapshots (alpha, f, n_iter) every ``checkpoint_every`` chunks *inside*
  a fold, so recovery no longer loses an in-flight fold — the restarted
  solve resumes the exact iterate sequence (bit-identical fixed point).

Straggler policy: ``strict`` (paper semantics — always seed from fold h-1)
or ``best_available`` (seed from the nearest *completed* fold; lets the
scheduler keep going when a fold is slow/lost; still bit-compatible results
because seeding never changes the fixed point).

``run_cv_batched`` executes independent (cold) folds concurrently through
the pool's repacked schedule; ``run_loo`` chains (or fans out) the
leave-one-out rounds through the same plan machinery — both get repacked
dispatch and mid-study checkpoint/resume from the shared entry point.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.core import seeding
from repro.core.study import Plan, StudyCheckpoint, run_plan
from repro.data.svm_suite import SVMDataset, kfold_chunks
from repro.svm import (DenseKernel, PallasRBF, bias_from_solution,
                       kernel_matrix, predict)
from repro.svm.precision import STATE_DTYPE, kdot, kernel_input

# step numbering inside a checkpoint directory: fold h's mid-fold chunk
# snapshots live at h*_FOLD_STRIDE + 1 + chunk, its completion record at
# (h+1)*_FOLD_STRIDE — monotone in (fold, chunk), so ``latest_step`` always
# points at the furthest progress.
_FOLD_STRIDE = 1_000_000
# run_cv_batched's mid-batch snapshots live at _BATCH_BASE + chunk: far
# above any run_cv step (k*_FOLD_STRIDE), so the two record kinds can share
# a directory without step collisions (save() replaces an existing step
# dir, so a collision would silently clobber the other run's checkpoint).
# Study records (retain_class "study") start at study.STUDY_BASE, above
# both — see DESIGN.md §Study API for the full key scheme.
_BATCH_BASE = _FOLD_STRIDE ** 2


@dataclasses.dataclass
class FoldStat:
    fold: int
    seed_from: int          # which fold's solution seeded this one (-1 = cold)
    n_iter: int
    init_time: float        # seeding + f-recompute (the paper's "init.")
    solve_time: float       # SMO time (the paper's "the rest": train part)
    acc_correct: int
    acc_total: int
    objective: float
    converged: bool
    restored: bool = False  # rebuilt from a checkpoint (times then read 0.0)


@dataclasses.dataclass
class CVReport:
    dataset: str
    method: str
    k: int
    n: int
    kernel_time: float
    folds: list[FoldStat]
    #: lane-pool width stats (mean/peak live width, program count; with
    #: shrinking, the shrink-chunk count and mean active fraction) from the
    #: run's pool
    occupancy: dict | None = None

    @property
    def total_iterations(self) -> int:
        return int(sum(f.n_iter for f in self.folds))

    @property
    def total_init_time(self) -> float:
        return float(sum(f.init_time for f in self.folds))

    @property
    def total_solve_time(self) -> float:
        return float(sum(f.solve_time for f in self.folds))

    @property
    def accuracy(self) -> float:
        c = sum(f.acc_correct for f in self.folds)
        t = sum(f.acc_total for f in self.folds)
        return c / max(t, 1)

    @property
    def partial(self) -> bool:
        """True when the report does not cover every fold — a resumed run
        whose earlier done-records were retention-GC'd. Totals/accuracy then
        aggregate fewer than k folds and are NOT comparable to a full run."""
        return sorted(f.fold for f in self.folds) != list(range(self.k))

    def row(self) -> dict:
        return {"dataset": self.dataset, "method": self.method, "k": self.k,
                "iterations": self.total_iterations,
                "init_s": round(self.total_init_time, 4),
                "solve_s": round(self.total_solve_time, 4),
                "total_s": round(self.total_init_time + self.total_solve_time
                                 + self.kernel_time, 4),
                "accuracy": round(self.accuracy, 4)}


def _transition_idx(chunks: np.ndarray, g: int, h: int):
    """Index sets for seeding fold h from fold g's solution.

    Previous train set = all \\ chunk[g]; new train set = all \\ chunk[h]:
    T (added) = chunk[g], R (removed) = chunk[h], S = the rest.
    """
    k = chunks.shape[0]
    S = np.concatenate([chunks[j] for j in range(k) if j not in (g, h)])
    return jnp.asarray(S), jnp.asarray(chunks[h]), jnp.asarray(chunks[g])


def _eval_fold(K, y, chunks, h, res, C) -> tuple[int, int, float]:
    """(acc_correct, acc_total, objective) of fold h's held-out chunk —
    the one evaluation path shared by the live CV loop, the batched driver
    and the checkpoint-restore rebuild, so they cannot drift apart.

    One product ``K @ (alpha * y)`` gives every row's decision value and
    the dual objective's quadratic term (``dual_objective``'s own
    expression); the held-out rows are kept. A gather of K's test rows
    would be a second (n / k, n) buffer: 1.44 GB beside mnist's 14.4 GB
    K, more than one chip holds."""
    test_idx = jnp.asarray(chunks[h])
    train_mask = jnp.ones(chunks.size, bool).at[test_idx].set(False)
    b = bias_from_solution(res, y, train_mask, C)
    v = res.alpha * y
    Kv = kdot(K, v)
    pred = jnp.where(Kv[test_idx] + b >= 0, 1, -1)
    obj = jnp.sum(res.alpha) - 0.5 * (v @ Kv)
    return (int(jnp.sum(pred == y[test_idx])), int(test_idx.shape[0]),
            float(obj))


def _eval_fold_rows(source, y, chunks, h, res, C) -> tuple[int, int, float]:
    """``_eval_fold`` for row-streaming sources: the test-chunk kernel rows
    come from ``rows_at`` and the dual objective's quadratic term from the
    streaming ``matvec`` — no (n, n) matrix is ever resident."""
    test_idx = jnp.asarray(chunks[h])
    train_mask = jnp.ones(chunks.size, bool).at[test_idx].set(False)
    b = bias_from_solution(res, y, train_mask, C)
    pred = predict(source.rows_at(test_idx), y, res.alpha, b)
    v = res.alpha * y
    obj = jnp.sum(res.alpha) - 0.5 * jnp.dot(v, source.matvec(v))
    return (int(jnp.sum(pred == y[test_idx])), int(test_idx.shape[0]),
            float(obj))


def _fold_masks(chunks: np.ndarray) -> np.ndarray:
    """(k, n) boolean train masks; row h is True off fold h's test chunk."""
    k, n = chunks.shape[0], chunks.size
    masks = np.ones((k, n), bool)
    for h in range(k):
        masks[h, chunks[h]] = False
    return masks


def run_cv(ds: SVMDataset, k: int = 10, method: str = "sir",
           tol: float = 1e-3, max_iter: int = 5_000_000, seed: int = 0,
           checkpoint_manager=None, straggler_policy: str = "strict",
           unavailable_folds: frozenset[int] = frozenset(),
           kernel_backend: str = "jnp", chunk_iters: int | None = None,
           checkpoint_every: int = 1, shrink_every: int | str = 0,
           shrink_quantum: int = 128, shrink_caps=None,
           shrink_on_seed: bool = True) -> CVReport:
    """Run alpha-seeded k-fold CV. ``unavailable_folds`` simulates stragglers/
    failures: those folds' results are not used as seeds (best_available
    policy then falls back to the nearest earlier completed fold).

    ``chunk_iters`` switches the solver to chunked dispatch; with a
    checkpoint manager attached, every ``checkpoint_every``-th chunk is
    snapshotted so a crash mid-fold resumes inside the fold instead of
    replaying it from its seed.

    The fold chain is one Study plan: restored folds enter as given
    results, live fold h is a lane whose seed dependency carries the
    ``"fold"`` transform (and an ``after`` ordering edge on fold h-1, so
    the paper's sequential protocol — and the mid-fold checkpoint cadence
    that assumes one in-flight fold — is preserved even for independent
    cold folds; the concurrent schedules live in ``run_cv_batched`` and
    ``run_grid``).

    ``shrink_every`` enables active-set shrinking inside each fold's solve
    (DESIGN.md §Shrinking); 0 (default) keeps every iterate bit-identical
    to today. Incompatible with mid-fold chunk checkpointing: run_cv's
    legacy mid records carry only (alpha, f, n_iter), not the shrink
    ledger, so a resume could not re-enter the compact subproblem —
    study-keyed drivers (``run_cv_batched``, ``run_grid``) checkpoint the
    ledger and support both together."""
    seeding.SEEDERS[method]   # validate the method name up front
    if shrink_every and checkpoint_manager is not None \
            and chunk_iters is not None:
        raise ValueError(
            "run_cv mid-fold checkpoints do not record the shrink ledger; "
            "use shrink_every=0 here, drop chunk_iters, or switch to a "
            "study-keyed driver (run_cv_batched / run_grid)")
    X = kernel_input(ds.X)
    y = jnp.asarray(ds.y, STATE_DTYPE)

    chunks = kfold_chunks(ds.n, k, seed=seed)
    n = chunks.size  # padded n (multiple of k)
    # slice X to the k-fold truncation BEFORE the kernel call — computing
    # the full (N, N) matrix and slicing after wastes O(N^2 - n^2) work,
    # and run_grid's KernelSpec sources build their kernels this way (the
    # two slice orders differ in final bits at some shapes, and grid cells
    # must stay bit-identical to run_cv)
    t0 = time.perf_counter()
    K = kernel_matrix(X[:n], X[:n], kind="rbf", gamma=ds.gamma,
                      backend=kernel_backend)
    K.block_until_ready()
    kernel_time = time.perf_counter() - t0
    del X   # only K is read from here on
    y = y[:n]
    masks = jnp.asarray(_fold_masks(chunks))

    results: dict[int, object] = {}
    restored_meta: dict[int, dict] = {}
    folds: list[FoldStat] = []
    start_fold = 0
    resume = None   # (alpha, f, n_iter, seed_from) of an in-flight fold

    if checkpoint_manager is not None:
        # run_cv's records all live below _BATCH_BASE; batch/study records
        # (keyed by lane id, resumable only through run_plan) are excluded
        # from BOTH the loop and the "latest" computation — a shared
        # directory must not make run_cv treat its own newest mid snapshot
        # as stale just because a batch record outranks it numerically.
        cv_steps = [s for s in checkpoint_manager.all_steps()
                    if s < _BATCH_BASE]
        latest = cv_steps[-1] if cv_steps else None
        # restore EVERY retained done record, not just the latest: the
        # returned report must account for pre-crash folds (else its
        # total_iterations/accuracy silently disagree with an uninterrupted
        # run), and the strict straggler policy needs fold h-1 in
        # ``results`` to seed fold h. Done records live at
        # (fold+1)*_FOLD_STRIDE unconditionally — chunked and unchunked runs
        # share the numbering, so either kind can resume the other. Mid
        # snapshots (step % _FOLD_STRIDE != 0) are stale unless latest.
        for s in cv_steps:
            if s % _FOLD_STRIDE != 0 and s != latest:
                continue
            step, tree, extra = checkpoint_manager.restore(step=s)
            # a checkpoint is only resumable into the SAME run: a different
            # partition (k/dataset/seed) misaligns the fold masks, and
            # resuming a mid-fold snapshot under a different
            # method/partition would silently converge to a wrong but
            # "converged" fixed point. A done record tolerates a method
            # change (seeding never moves the fixed point); a mid snapshot
            # IS the method's trajectory, so it doesn't.
            want = {"k": k, "dataset": ds.name, "seed": seed}
            if extra.get("phase") == "mid":
                want["method"] = method
            got = {key: extra.get(key) for key in want}
            if got != want:
                raise ValueError(
                    f"checkpoint at step {step} belongs to run {got}, cannot "
                    f"resume it as {want}; point the manager at a fresh "
                    "directory or delete the stale checkpoints")
            if extra.get("phase") == "mid":   # only possible for the latest
                start_fold = extra["fold"]
                resume = (jnp.asarray(tree["alpha"]), jnp.asarray(tree["f"]),
                          int(tree["n_iter"]), extra["seed_from"])
            else:
                results[extra["fold"]] = _result_from_tree(tree)
                restored_meta[extra["fold"]] = extra
                start_fold = max(start_fold, extra["fold"] + 1)

    # rebuild FoldStats for the restored folds so the report covers them
    # (per-fold timings are not checkpointed and read 0.0; ``restored``
    # marks them) — but ONLY for records written under the SAME method:
    # a done record from another method is a valid seed (the fixed point is
    # method-independent) yet its n_iter is that method's trajectory, and
    # republishing it under this report's label would fabricate a
    # per-method iteration count (the paper's headline metric). Skipped
    # folds leave a gap that ``report.partial`` flags.
    for h in sorted(results):
        if restored_meta[h].get("method") != method:
            continue
        res = results[h]
        correct, total, obj = _eval_fold(K, y, chunks, h, res, ds.C)
        folds.append(FoldStat(
            fold=h, seed_from=restored_meta[h].get("seed_from", -1),
            n_iter=int(res.n_iter), init_time=0.0, solve_time=0.0,
            acc_correct=correct, acc_total=total, objective=obj,
            converged=bool(res.converged), restored=True))

    # ---- declare the fold chain as a plan ----
    plan = Plan(sources={"cv": DenseKernel(K)}, y=y, tol=tol,
                chunk_iters=chunk_iters if chunk_iters is not None
                else max_iter,
                shrink_every=shrink_every, shrink_quantum=shrink_quantum,
                shrink_caps=shrink_caps, shrink_on_seed=shrink_on_seed)
    for g in sorted(results):
        plan.lane(g, result=results[g])

    # the seed-fold choice (straggler policy) is deterministic: live folds
    # execute in order (the ``after`` chain), so fold h sees exactly the
    # restored folds plus every earlier live fold as completed
    seed_froms: dict[int, int] = {}
    base_counts: dict[int, int] = {}
    done_folds = sorted(results)
    prev_lane = None
    zeros = jnp.zeros(n, STATE_DTYPE)
    for h in range(start_fold, k):
        avail = [g for g in done_folds if g not in unavailable_folds]
        if resume is not None and h == start_fold:
            seed_from = resume[3]
        elif h == 0 or method == "cold" or not avail:
            seed_from = -1
        elif straggler_policy == "strict":
            seed_from = h - 1 if (h - 1) in avail else -1
        else:  # best_available: nearest completed fold
            seed_from = min(avail, key=lambda g: abs(h - g))
        seed_froms[h] = seed_from
        base_counts[h] = 0

        common = dict(train_mask=masks[h], C=ds.C, max_iter=max_iter,
                      after=prev_lane)
        if resume is not None and h == start_fold:
            alpha0, f0, n_iter0, _ = resume
            base_counts[h] = (n_iter0 // chunk_iters
                              if chunk_iters is not None else 0)
            plan.lane(h, alpha0=alpha0, f0=f0, n_iter0=n_iter0, **common)
        elif seed_from < 0:
            plan.lane(h, alpha0=zeros, f0=-y, **common)
        else:
            S_idx, R_idx, T_idx = _transition_idx(chunks, seed_from, h)
            plan.lane(h, dep=seed_from, transform="fold",
                      params=dict(method=method, S_idx=S_idx, R_idx=R_idx,
                                  T_idx=T_idx), **common)
        done_folds.append(h)
        prev_lane = h

    # ---- checkpoint hooks: run_cv keeps its own record formats ----
    on_lane_chunk = None
    if checkpoint_manager is not None and chunk_iters is not None:
        # seed the chunk counter from the restored n_iter so step numbers
        # reflect ABSOLUTE fold progress: a resumed run's snapshots must
        # outnumber the pre-crash ones, or latest_step()/retention-GC
        # would keep resurrecting the stale pre-crash snapshot forever
        counters = dict(base_counts)

        def on_lane_chunk(h, state):
            counters[h] += 1
            if counters[h] % checkpoint_every:
                return
            step = h * _FOLD_STRIDE + min(counters[h], _FOLD_STRIDE - 2) + 1
            # mid snapshots GC separately from done records: they are
            # frequent and superseded by the next one, and must never
            # evict the done records the resume path depends on
            checkpoint_manager.save(
                step, {"alpha": state.alpha, "f": state.f,
                       "n_iter": state.n_iter},
                extra_meta={"phase": "mid", "fold": h,
                            "seed_from": seed_froms[h], "method": method,
                            "k": k, "dataset": ds.name, "seed": seed},
                blocking=False, retain_class="mid")

    on_result = None
    if checkpoint_manager is not None:
        def on_result(h, res):
            # strided numbering UNCONDITIONALLY: unchunked runs used to save
            # fold h at step h while every reader assumed (h+1)*_FOLD_STRIDE,
            # so a later resume with chunk_iters set pointed at nonexistent
            # steps and silently degraded strict seeding to cold
            checkpoint_manager.save(
                (h + 1) * _FOLD_STRIDE,
                {"alpha": res.alpha, "f": res.f, "n_iter": res.n_iter,
                 "converged": res.converged, "b_up": res.b_up,
                 "b_low": res.b_low},
                extra_meta={"phase": "done", "fold": h,
                            "seed_from": seed_froms[h], "method": method,
                            "k": k, "dataset": ds.name, "seed": seed},
                blocking=False, retain_class="done")

    sres = run_plan(plan, on_result=on_result, on_lane_chunk=on_lane_chunk)

    for h in range(start_fold, k):
        res = sres.results[h]
        stat = sres.stats[h]
        correct, total, obj = _eval_fold(K, y, chunks, h, res, ds.C)
        folds.append(FoldStat(
            fold=h, seed_from=seed_froms[h], n_iter=stat.n_iter,
            init_time=stat.seed_s, solve_time=stat.solve_s,
            acc_correct=correct, acc_total=total,
            objective=obj, converged=stat.converged))

    if checkpoint_manager is not None:
        checkpoint_manager.wait()
    return CVReport(dataset=ds.name, method=method, k=k, n=n,
                    kernel_time=kernel_time, folds=folds,
                    occupancy=sres.occupancy)


def run_cv_batched(ds: SVMDataset, k: int = 10, tol: float = 1e-3,
                   max_iter: int = 5_000_000, seed: int = 0,
                   kernel_backend: str = "jnp", chunk_iters: int = 4096,
                   lane_quantum: int = 4, max_width: int | None = None,
                   source_backend: str = "dense", checkpoint_manager=None,
                   checkpoint_every: int = 1, shrink_every: int | str = 0,
                   shrink_quantum: int = 128, shrink_caps=None,
                   shrink_on_seed: bool = True) -> CVReport:
    """Cold k-fold CV with all folds solved concurrently: independent
    solves are a batch, not a loop.

    The folds are a k-lane plan (method "cold_batched_repacked") executed
    by ``run_plan`` on the lane pool: converged folds retire between
    chunks, the live batch is compacted (bucketed widths) and the dispatch
    width is capped by the backend cost model (``max_width``; on CPU the
    default is a width-1 round-robin through the sequential program), so
    device work tracks ``sum_h n_iter_h`` (DESIGN.md §Lane scheduler /
    §Study API).

    ``source_backend="pallas_rbf"`` (method "cold_pallas") swaps the dense
    precomputed matrix for the row-streaming ``PallasRBF`` source: no
    (n, n) kernel is ever built (``kernel_time`` then covers only the
    O(n·d) row-norm precompute), the folds solve under WSS-1 with the
    fused kernel-row + f-update Pallas step, and held-out evaluation
    streams test-chunk rows via ``rows_at`` / the dual objective via
    ``matvec``. Alphas match the dense WSS-1 solve bit-for-bit in
    interpret mode (DESIGN.md §Pallas sources); they differ from the
    default WSS-2 methods' iterate sequence, as any WSS choice does.

    The per-fold fixed points equal ``run_cv(method="cold")``'s
    (bit-identical alphas — the engine body is shared); only the schedule
    differs. Seeded chains stay sequential by nature — their concurrency
    axis is the hyper-parameter grid (see ``repro.core.grid``).

    With a checkpoint manager, every ``checkpoint_every``-th chunk
    snapshots ALL lanes' (alpha, f, n_iter, done) keyed by **original fold
    id** — not packed position — as one ``phase: "batch_mid"`` record
    (retain_class "batch"), so a crashed mid-batch run resumes each fold's
    exact iterate sequence regardless of how lanes were packed at the
    crash."""
    if source_backend not in ("dense", "pallas_rbf"):
        raise ValueError(f"unknown source_backend {source_backend!r}")
    X = kernel_input(ds.X)
    y = jnp.asarray(ds.y, STATE_DTYPE)

    chunks = kfold_chunks(ds.n, k, seed=seed)
    n = chunks.size
    # slice before the kernel call (see run_cv): no wasted (N, N) compute,
    # bit-aligned with run_grid's KernelSpec sources
    t0 = time.perf_counter()
    if source_backend == "pallas_rbf":
        K = None
        source = PallasRBF(X[:n], ds.gamma)
        source.sq_norms.block_until_ready()
    else:
        K = kernel_matrix(X[:n], X[:n], kind="rbf", gamma=ds.gamma,
                          backend=kernel_backend)
        K.block_until_ready()
        source = DenseKernel(K)
    kernel_time = time.perf_counter() - t0
    y = y[:n]
    masks = jnp.asarray(_fold_masks(chunks))

    method = ("cold_pallas" if source_backend == "pallas_rbf"
              else "cold_batched_repacked")
    plan = Plan(sources={"cv": source}, y=y, tol=tol,
                wss="1" if source_backend == "pallas_rbf" else "2",
                chunk_iters=chunk_iters, lane_quantum=lane_quantum,
                max_width=max_width,
                shrink_every=shrink_every, shrink_quantum=shrink_quantum,
                shrink_caps=shrink_caps, shrink_on_seed=shrink_on_seed)
    zeros = jnp.zeros(n, STATE_DTYPE)
    for h in range(k):
        plan.lane(h, train_mask=masks[h], C=ds.C, alpha0=zeros, f0=-y,
                  max_iter=max_iter)

    checkpoint = None
    if checkpoint_manager is not None:
        # tol and max_iter are part of the run identity: retired lanes
        # carry fixed points at the snapshot's tolerance/budget, so
        # resuming under different solver parameters would mix convergence
        # criteria across lanes
        checkpoint = StudyCheckpoint(
            manager=checkpoint_manager, every=checkpoint_every,
            retain_class="batch", phase="batch_mid", base_step=_BATCH_BASE,
            meta={"k": k, "dataset": ds.name, "seed": seed, "tol": tol,
                  "max_iter": max_iter, "method": method})

    t0 = time.perf_counter()
    sres = run_plan(plan, checkpoint=checkpoint)
    solve_time = time.perf_counter() - t0

    done_at_start = sres.restored
    live = max(k - len(done_at_start), 1)
    folds = []
    for h in range(k):
        res = sres.results[h]
        correct, total, obj = (
            _eval_fold(K, y, chunks, h, res, ds.C) if K is not None
            else _eval_fold_rows(source, y, chunks, h, res, ds.C))
        folds.append(FoldStat(
            fold=h, seed_from=-1, n_iter=int(res.n_iter),
            init_time=0.0,
            solve_time=0.0 if h in done_at_start else solve_time / live,
            acc_correct=correct, acc_total=total, objective=obj,
            converged=bool(res.converged), restored=h in done_at_start))
    return CVReport(dataset=ds.name, method=method, k=k,
                    n=n, kernel_time=kernel_time, folds=folds,
                    occupancy=sres.occupancy)


def _result_from_tree(tree):
    from repro.svm.smo import SMOResult
    return SMOResult(alpha=jnp.asarray(tree["alpha"]), f=jnp.asarray(tree["f"]),
                     n_iter=jnp.asarray(tree["n_iter"]),
                     converged=jnp.asarray(tree["converged"]),
                     b_up=jnp.asarray(tree["b_up"]),
                     b_low=jnp.asarray(tree["b_low"]))


def run_loo(ds: SVMDataset, method: str = "sir", rounds: int | None = None,
            tol: float = 1e-3, max_iter: int = 2_000_000, seed: int = 0,
            chunk_iters: int = 4096, max_width: int | None = None,
            checkpoint_manager=None, checkpoint_every: int = 1) -> dict:
    """Leave-one-out CV (paper suppl. Fig. 2). AVG/TOP seed every round from
    the full-data SVM; ATO/MIR/SIR chain round h from round h-1 (T = the
    instance returned, R = the instance removed); cold starts from zero.

    The protocol is one plan: the full-data solve is a lane, chain rounds
    are dependency edges carrying the ``"fold"`` transform, and AVG/TOP
    rounds all depend on the full lane only — so those fan out through the
    pool's repacked dispatch instead of the old sequential-only loop, and
    a checkpoint manager gives mid-study resume (plan-keyed ``"study"``
    records) for free."""
    if method not in ("cold", "avg", "top", "ato", "mir", "sir"):
        raise ValueError(f"unknown LOO method {method!r}")
    X = kernel_input(ds.X)
    y = jnp.asarray(ds.y, STATE_DTYPE)
    n = ds.n
    rounds = n if rounds is None else min(rounds, n)

    t_start = time.perf_counter()
    K = kernel_matrix(X, X, kind="rbf", gamma=ds.gamma)

    plan = Plan(sources={"loo": DenseKernel(K)}, y=y, tol=tol,
                chunk_iters=chunk_iters, max_width=max_width)
    zeros = jnp.zeros(n, STATE_DTYPE)
    # full-data SVM (shared by AVG/TOP; also round -1 for the chain methods)
    plan.lane("full", train_mask=jnp.ones(n, bool), C=ds.C, alpha0=zeros,
              f0=-y, max_iter=max_iter)
    for t in range(rounds):
        mask = jnp.ones(n, bool).at[t].set(False)
        common = dict(train_mask=mask, C=ds.C, max_iter=max_iter)
        if method == "cold":
            plan.lane(t, alpha0=zeros, f0=-y, **common)
        elif method in ("avg", "top"):
            plan.lane(t, dep="full", transform=f"loo_{method}",
                      params={"t": t}, **common)
        elif t == 0:
            # first round: remove t from the full SVM (AVG-style entry)
            plan.lane(0, dep="full", transform="loo_avg", params={"t": 0},
                      **common)
        else:
            S = np.delete(np.arange(n), [t - 1, t])
            plan.lane(t, dep=t - 1, transform="fold",
                      params=dict(method=method, S_idx=jnp.asarray(S),
                                  R_idx=jnp.asarray([t]),
                                  T_idx=jnp.asarray([t - 1])), **common)
        plan.evaluate(t, np.asarray([t]))

    checkpoint = None
    if checkpoint_manager is not None:
        checkpoint = StudyCheckpoint(
            manager=checkpoint_manager, every=checkpoint_every,
            meta={"bench": "loo", "dataset": ds.name, "method": method,
                  "rounds": rounds, "seed": seed, "tol": tol,
                  "max_iter": max_iter})

    sres = run_plan(plan, checkpoint=checkpoint)
    total_iters = sum(sres.stats[t].n_iter for t in range(rounds))
    correct = sum(sres.evals[t][0] for t in range(rounds))
    elapsed = time.perf_counter() - t_start
    return {"dataset": ds.name, "method": method, "rounds": rounds,
            "base_iterations": sres.stats["full"].n_iter,
            "iterations": total_iters,
            "elapsed_s": round(elapsed, 4),
            "accuracy": round(correct / rounds, 4)}
