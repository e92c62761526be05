"""Paper Table 1: k=10 cross-validation efficiency — cold (the LibSVM
baseline) vs ATO / MIR / SIR. Columns mirror the paper: init time, solve
("the rest") time, total SMO iterations, accuracy.

Datasets are the synthetic suite at CPU-budget cardinality (DESIGN.md
§Synthetic datasets); each (dataset, method) runs twice and reports the warm
run so jit compile time doesn't pollute the init-time comparison (the
paper's C++ has no JIT).

Beyond the paper's columns:

* ``cold_batched`` — the same k independent cold folds as ONE fixed-width
  batch through ``engine.solve_batched`` (identical per-fold fixed points;
  only the schedule differs). On few-core CPU hosts this was measured
  SLOWER than sequential (the live batch never shrinks — DESIGN.md
  §Batched folds);
* ``cold_batched_repacked`` — the same folds through the LaneScheduler
  (DESIGN.md §Lane scheduler): converged lanes retire between chunks, the
  live batch is repacked to bucketed widths, and the last straggler runs
  the sequential single-lane program. Its row carries an ``occupancy``
  dict (mean/peak live width) that ``benchmarks.run`` aggregates into the
  BENCH_table1.json ``scheduler`` block — the repack win, and any
  regression of it, is a one-line artifact diff against ``cold_batched``;
* ``ato_ref`` — the eager host-side ATO loop that the jitted ramp
  replaced, kept as the jit baseline;
* ``ato_shrink`` — ATO-seeded CV with active-set shrinking on (DESIGN.md
  §Shrinking), carrying the unshrunk baseline and the seeding handoff
  ablation (see ``_shrink_row``);
* ``ato_bucketed`` — the batched ATO ramp across a 3-lane C row for every
  fold transition, with per-lane m_cap buckets (``init_s``) vs the
  historical widest-lane pad (``init_s_padded``); the bucketed ramp must
  be no slower on every dataset;
* ``grid_pooled`` / ``grid_rows`` — a 3x3 (C, gamma) grid through
  ``run_grid`` as ONE cross-gamma lane pool vs the per-gamma-row scheduler
  baseline (identical per-cell results; only the schedule differs). The
  pooled row carries the pool occupancy incl. per-source (per-gamma) live
  widths, so the straggler-row win — and any regression of it — stays
  visible in the BENCH_table1.json artifact diff. Acceptance: pooled is no
  slower in aggregate;
* ``grid_pooled_lru`` — the same cross-gamma pool under a 2-resident-kernel
  LRU budget (``max_resident=GRID_LRU_BUDGET``, DESIGN.md §Kernel-source
  cache): bit-identical cells, a ``peak_resident`` block (resident
  kernels/bytes, materialization count, kernel seconds) tracking the
  memory ceiling, and wall-clock required within ~10% of ``grid_pooled``;
* ``cold_pallas`` / ``grid_pooled_pallas`` — the matrix-free rows
  (DESIGN.md §Pallas sources): cold folds / a cold budgeted grid over
  row-streaming ``PallasRBF`` sources, never materializing an n² kernel.
  On the CPU the interpret-mode kernels make their wall-clock an
  emulation artifact, so they time one rep on a reduced grid
  (``PALLAS_GRID``) and their ``peak_resident.bytes`` (X bytes, not n²)
  is the load-bearing CPU-side number; their device time is the chip
  benchmark's (``bench/``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.bench_lib import emit
from repro.core import seeding
from repro.core.cv import _fold_masks, _transition_idx, run_cv, run_cv_batched
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import (bias_from_solution, init_f, kernel_matrix, predict,
                       smo_solve_batched)
from repro.svm.precision import kernel_input

SIZES = {"adult": 1000, "heart": 270, "madelon": 1200, "mnist": 1000,
         "webdata": 1000}
METHODS = ("cold", "cold_batched", "cold_batched_repacked", "cold_pallas",
           "ato", "ato_ref", "mir", "sir")
#: C multipliers of the ato_bucketed row — a wide spread (a grid row's
#: realistic range) so lanes land in different free-set cap buckets on
#: every suite dataset (the case bucketing exists for); the middle lane is
#: the paper's C, keeping its accuracy comparable to the ato row
ATO_ROW_C = (0.01, 1.0, 100.0)
#: the grid_pooled/grid_rows comparison grid: multipliers of the paper's
#: (C, gamma), k=5 — 9 cells x 5 folds = 45 lanes per run, enough to give
#: the cross-gamma pool straggler rows to dissolve while keeping the
#: benchmark wall-clock sane
GRID_C = (0.25, 1.0, 4.0)
GRID_GAMMA = (0.5, 1.0, 2.0)
GRID_K = 5
#: the grid_pooled_lru residency budget: 2 of the 3 gamma kernels resident
#: at once — peak kernel bytes must read ~2/3 of the unbounded pool while
#: per-cell results stay bit-identical
GRID_LRU_BUDGET = 2
#: the ato_shrink row's heuristic cadence: at suite cardinality (n ~ 1000,
#: a few hundred iterations per seeded fold) a 512-iteration cadence gives
#: every fold at least one shrink opportunity without thrashing re-gathers
SHRINK_EVERY_BENCH = 512
#: the grid_pooled_pallas sizing: cold WSS-1 folds through interpret-mode
#: pallas cost 5-50x a compiled dense iteration on CPU, so the matrix-free
#: row runs a 2x2 grid corner — enough cells to exercise multi-source
#: residency accounting without dominating the bench wall-clock
PALLAS_GRID = 2


def _grid_rows(name: str, reps: int) -> list[dict]:
    """Time the same (C, gamma) grid under the cross-gamma pool (unbounded
    residency), the cross-gamma pool under a 2-kernel LRU budget
    (``grid_pooled_lru``), and the per-gamma-row baseline. Per-cell results
    are bit-identical across all three (asserted in tests/test_study.py and
    tests/test_sources.py); the rows track the schedules' wall-clock,
    occupancy shape and — for the LRU row — the ``peak_resident`` block
    (resident kernels/bytes and materialization count): peak bytes must
    read ~len(gammas)/GRID_LRU_BUDGET x below the unbounded pool, and
    wall-clock must stay within ~10% of ``grid_pooled``."""
    from repro.core.grid import run_grid
    ds = make_dataset(name, n_override=SIZES[name])
    Cs = [m * ds.C for m in GRID_C]
    gammas = [m * ds.gamma for m in GRID_GAMMA]
    rows = []
    for method_name, kw in (
            ("grid_pooled", dict(pool="cross_gamma")),
            ("grid_pooled_lru", dict(pool="cross_gamma",
                                     max_resident=GRID_LRU_BUDGET)),
            ("grid_rows", dict(pool="per_gamma")),
            ("grid_pooled_pallas", dict(
                pool="cross_gamma", method="cold",
                source_backend="pallas_rbf", max_resident=GRID_LRU_BUDGET,
                Cs=Cs[:PALLAS_GRID], gammas=gammas[:PALLAS_GRID]))):
        def runner(kw=kw):
            return run_grid(ds, **{"Cs": Cs, "gammas": gammas, "k": GRID_K,
                                   "method": "sir", **kw})
        runner()                                 # warm the jit caches
        # interpret-mode pallas rows time a single rep (see module doc)
        r_eff = 1 if method_name == "grid_pooled_pallas" else reps
        rep = min((runner() for _ in range(r_eff)),
                  key=lambda r: r.solve_time)
        row = {"dataset": name, "method": method_name, "k": GRID_K,
               "iterations": rep.total_iterations,
               "init_s": round(rep.seed_time, 4),
               "solve_s": round(rep.solve_time, 4),
               "total_s": round(rep.seed_time + rep.solve_time
                                + rep.kernel_time, 4),
               "accuracy": round(rep.best().accuracy, 4),
               "us_per_iteration": round(
                   1e6 * rep.solve_time / max(rep.total_iterations, 1), 2)}
        if rep.occupancy is not None:
            row["occupancy"] = rep.occupancy
        # the memory-ceiling signal belongs to the budgeted rows only — the
        # unbudgeted pools' residency stats are trivial (all resident)
        if (method_name in ("grid_pooled_lru", "grid_pooled_pallas")
                and rep.resident is not None):
            row["peak_resident"] = {
                "sources": rep.resident["peak_resident"],
                "bytes": rep.resident["peak_resident_bytes"],
                "materializations": rep.resident["materializations"],
                "kernel_s": round(rep.kernel_time, 4)}
        rows.append(row)
    return rows


def _shrink_row(name: str, k: int, reps: int) -> dict:
    """ATO-seeded k-fold CV with active-set shrinking on vs off (DESIGN.md
    §Shrinking): same seeder, same engine, same schedule — the only change
    is the pool compacting bound-locked rows out of each solve at bucketed
    capacities. The row reports the shrink run's timings plus the unshrunk
    baseline (``solve_s_noshrink`` / ``shrink_speedup``) and the
    seeding->shrinking handoff ablation (``solve_s_no_handoff``:
    ``shrink_on_seed=False``, so seeded lanes wait ``shrink_every``
    iterations to rediscover their bound-locked rows instead of starting
    shrunk). Fold accuracies are asserted identical to the unshrunk run —
    shrinking preserves the full-set optimality contract."""
    ds = make_dataset(name, n_override=SIZES[name])

    def runner(**kw):
        return run_cv(ds, k=k, method="ato", **kw)

    on_kw = dict(shrink_every=SHRINK_EVERY_BENCH)
    runner()                                        # warm the jit caches
    off = min((runner() for _ in range(reps)),
              key=lambda r: r.total_solve_time)
    runner(**on_kw)                                 # warm the cap programs
    on = min((runner(**on_kw) for _ in range(reps)),
             key=lambda r: r.total_solve_time)
    accs = lambda r: sorted((f.fold, f.acc_correct) for f in r.folds)
    assert accs(on) == accs(off), \
        f"shrinking changed fold accuracies on {name}"
    handoff_kw = dict(on_kw, shrink_on_seed=False)
    runner(**handoff_kw)
    no_handoff = min((runner(**handoff_kw) for _ in range(reps)),
                     key=lambda r: r.total_solve_time)

    row = on.row()
    row.update({
        "method": "ato_shrink",
        "us_per_iteration": round(
            1e6 * on.total_solve_time / max(on.total_iterations, 1), 2),
        "solve_s_noshrink": round(off.total_solve_time, 4),
        "shrink_speedup": round(
            off.total_solve_time / max(on.total_solve_time, 1e-9), 3),
        "solve_s_no_handoff": round(no_handoff.total_solve_time, 4)})
    if on.occupancy is not None:
        row["occupancy"] = on.occupancy
    return row


def _ato_bucketed_row(name: str, k: int, reps: int) -> dict:
    """Time the batched ATO ramp (one 3-lane C row, every fold transition)
    with per-lane buckets vs the widest-lane pad. The solve chain advances
    on the bucketed seeds; ramp timings are warm min-of-reps."""
    ds = make_dataset(name, n_override=SIZES[name])
    X = kernel_input(ds.X)            # run_cv's precision policy
    y = jnp.asarray(ds.y, jnp.float64)
    chunks = kfold_chunks(ds.n, k, seed=0)
    n = chunks.size
    # slice before the kernel call (same fix as core/cv.py: the full
    # (N, N) kernel wastes O(N^2 - n^2) work for the truncated folds)
    K = kernel_matrix(X[:n], X[:n], kind="rbf", gamma=ds.gamma)
    y = y[:n]
    masks = jnp.asarray(_fold_masks(chunks))
    Cs = jnp.asarray([m * ds.C for m in ATO_ROW_C], jnp.float64)
    m = Cs.shape[0]

    # warm the batched-solver program (each dataset's n forces a fresh
    # trace) so solve_s matches the other rows' warm-run convention —
    # max_iter=1 compiles the same program (it_cap is traced, not static)
    jax.block_until_ready(smo_solve_batched(
        K, y, jnp.tile(masks[0][None], (m, 1)), Cs,
        jnp.zeros((m, n), K.dtype), jnp.tile(-y, (m, 1)), max_iter=1))
    t0 = time.perf_counter()
    prev = smo_solve_batched(K, y, jnp.tile(masks[0][None], (m, 1)), Cs,
                             jnp.zeros((m, n), K.dtype), jnp.tile(-y, (m, 1)))
    jax.block_until_ready(prev)
    solve_s = time.perf_counter() - t0
    iters = int(jnp.sum(prev.n_iter))
    correct = total = 0
    ramp_bucketed = ramp_padded = 0.0

    def eval_paper_lane(res, h):
        # accuracy of the paper-C lane (index 1), comparable to the ato row
        lane = jax.tree.map(lambda a: a[1], res)
        test_idx = jnp.asarray(chunks[h])
        b = bias_from_solution(lane, y, masks[h], float(Cs[1]))
        pred = predict(K[test_idx], y, lane.alpha, b)
        return int(jnp.sum(pred == y[test_idx])), int(test_idx.shape[0])

    c0, t0_ = eval_paper_lane(prev, 0)
    correct += c0
    total += t0_
    for h in range(1, k):
        S, R, T = _transition_idx(chunks, h - 1, h)
        timed = {}
        for key, flag in (("bucketed", True), ("padded", False)):
            def ramp(flag=flag):
                out = seeding.ato_seed_batch(K, y, Cs, prev, S, R, T,
                                             bucket_by_lane=flag)
                jax.block_until_ready(out)
                return out
            ramp()                                   # warm the jit caches
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                out = ramp()
                best = min(best, time.perf_counter() - t0)
            timed[key] = best
            if flag:
                alpha0s = out
        ramp_bucketed += timed["bucketed"]
        ramp_padded += timed["padded"]
        f0s = jnp.stack([init_f(K, y, alpha0s[ci]) for ci in range(m)])
        t0 = time.perf_counter()
        prev = smo_solve_batched(K, y, jnp.tile(masks[h][None], (m, 1)), Cs,
                                 alpha0s, f0s)
        jax.block_until_ready(prev)
        solve_s += time.perf_counter() - t0
        iters += int(jnp.sum(prev.n_iter))
        ch, th = eval_paper_lane(prev, h)
        correct += ch
        total += th
    return {"dataset": name, "method": "ato_bucketed", "k": k,
            "iterations": iters, "init_s": round(ramp_bucketed, 4),
            "solve_s": round(solve_s, 4),
            "total_s": round(ramp_bucketed + solve_s, 4),
            "accuracy": round(correct / max(total, 1), 4),
            "us_per_iteration": round(1e6 * solve_s / max(iters, 1), 2),
            "init_s_padded": round(ramp_padded, 4)}


def run(k: int = 10, quick: bool = False, reps: int = 3):
    rows = []
    names = ("heart", "adult") if quick else tuple(SIZES)
    reps = 2 if quick else reps
    for name in names:
        ds = make_dataset(name, n_override=SIZES[name])
        for method in METHODS:
            if method == "cold_batched":
                runner = lambda: run_cv_batched(ds, k=k, schedule="batched")
            elif method == "cold_batched_repacked":
                runner = lambda: run_cv_batched(ds, k=k, schedule="repacked")
            elif method == "cold_pallas":
                runner = lambda: run_cv_batched(
                    ds, k=k, source_backend="pallas_rbf")
            else:
                runner = lambda m=method: run_cv(ds, k=k, method=m)
            runner()                                # warm the jit caches
            # min-of-reps: solver timings on shared CPUs are noisy (and the
            # near-degenerate suites hit denormal-heavy kernels); the min is
            # the standard low-variance estimator for the true cost — except
            # the interpret-mode pallas row, which times one rep (module doc)
            r_eff = 1 if method == "cold_pallas" else reps
            rep = min((runner() for _ in range(r_eff)),
                      key=lambda r: r.total_solve_time)
            row = rep.row()
            row["us_per_iteration"] = round(
                1e6 * (rep.total_solve_time)
                / max(rep.total_iterations, 1), 2)
            if rep.occupancy is not None:
                row["occupancy"] = rep.occupancy
            rows.append(row)
        rows.append(_shrink_row(name, k, reps))
        rows.append(_ato_bucketed_row(name, k, reps))
        rows.extend(_grid_rows(name, reps))
    emit(f"table1_k{k}", rows)
    return rows


if __name__ == "__main__":
    run()
