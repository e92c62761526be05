"""Hyper-parameter grid driver: cell parity with run_cv, kernel reuse,
C-adjacent seeding, and cold-row batching."""
import dataclasses

import pytest

from repro.core.cv import run_cv
from repro.core.grid import run_grid
from repro.data.svm_suite import make_dataset

CS = [1.0, 8.0]
GAMMAS = [0.1, 0.3]


@pytest.fixture(scope="module")
def ds():
    return make_dataset("heart", n_override=120)


def test_grid_covers_all_cells(ds):
    rep = run_grid(ds, Cs=CS, gammas=GAMMAS, k=4, method="sir")
    assert len(rep.cells) == len(CS) * len(GAMMAS)
    assert {(c.C, c.gamma) for c in rep.cells} == \
        {(C, g) for C in CS for g in GAMMAS}
    assert all(c.converged for c in rep.cells)
    best = rep.best()
    assert best.accuracy == max(c.accuracy for c in rep.cells)


@pytest.mark.parametrize("method", ["sir", "cold", "mir", "ato"])
def test_grid_cell_matches_run_cv(ds, method):
    """Each grid cell must reproduce the standalone CV run on that cell's
    hyper-parameters exactly (same engine, same seeds, same schedule)."""
    rep = run_grid(ds, Cs=CS, gammas=GAMMAS, k=4, method=method)
    cell = [c for c in rep.cells if c.C == 8.0 and c.gamma == 0.3][0]
    ds_cell = dataclasses.replace(ds, C=8.0, gamma=0.3)
    cv = run_cv(ds_cell, k=4, method=method)
    assert cell.accuracy == pytest.approx(cv.accuracy, abs=1e-12)
    assert cell.iterations == cv.total_iterations


def test_seed_across_C_same_accuracy(ds):
    """C-chained fold 0 changes the starting point, not the fixed point."""
    plain = run_grid(ds, Cs=[0.5, 2.0, 8.0], gammas=[0.2], k=4, method="sir")
    chained = run_grid(ds, Cs=[0.5, 2.0, 8.0], gammas=[0.2], k=4,
                       method="sir", seed_across_C=True)
    for p, c in zip(plain.cells, chained.cells):
        assert (p.C, p.gamma) == (c.C, c.gamma)
        assert c.accuracy == pytest.approx(p.accuracy, abs=0.05)
        assert c.converged


def test_grid_ato_batched_row(ds):
    """method="ato": each cell's fold transitions run the jittable ATO ramp
    (seeding.ato_seed) as scheduler admission transforms, so cells advance
    independently. Cells must match the standalone ATO CV run on accuracy
    and converge; iteration counts are comparable (same per-lane m_cap as
    run_cv, so usually identical, but not contractually bit-equal)."""
    rep = run_grid(ds, Cs=CS, gammas=[0.3], k=4, method="ato")
    assert len(rep.cells) == len(CS)
    assert all(c.converged for c in rep.cells)
    for C in CS:
        cell = [c for c in rep.cells if c.C == C][0]
        cv = run_cv(dataclasses.replace(ds, C=C, gamma=0.3), k=4,
                    method="ato")
        assert cell.accuracy == pytest.approx(cv.accuracy, abs=0.05)
        assert cell.iterations <= 2 * cv.total_iterations + 500
    # ATO transitions compose with the C-chained fold 0 (seed_across_C)
    rep2 = run_grid(ds, Cs=CS, gammas=[0.3], k=4, method="ato",
                    seed_across_C=True)
    assert all(c.converged for c in rep2.cells)


def test_grid_reports_times(ds):
    rep = run_grid(ds, Cs=CS, gammas=GAMMAS, k=3, method="sir")
    assert rep.kernel_time > 0 and rep.solve_time > 0
    rows = rep.rows()
    assert len(rows) == 4 and all("accuracy" in r for r in rows)
