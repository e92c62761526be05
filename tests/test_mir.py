"""MIR's blockwise assembly and the dense held-out evaluation.

Over an f32 K, ``mir_seed`` builds its least-squares system's normal
equations from K in blocks (``_mir_assemble``) instead of gathering the
(|S + R|, |T|) slabs; the start must be the slab form's to float32
rounding, seeded folds must reach cold's KKT point, and ``run_cv`` must
agree with the benchmark's plain float64 reference. ``_eval_fold``
predicts through one ``K @ (alpha * y)`` instead of gathering K's test
rows, and must count as the gather did."""
import math
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import cv, seeding
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import (bias_from_solution, dual_objective, kernel_matrix,
                       predict, smo_solve)
from repro.svm.precision import kdot, kernel_input

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import reference  # noqa: E402
import run as bench_run  # noqa: E402

EPS32 = float(np.finfo(np.float32).eps)


def _transition(name, n, k, dtype=jnp.float32):
    """A solved fold 0 and the 0 -> 1 transition sets, over the policy's
    f32 K (or an f64 one)."""
    ds = make_dataset(name, n_override=n)
    chunks = kfold_chunks(n, k, seed=0)
    nn = chunks.size
    X = kernel_input(ds.X)[:nn].astype(dtype)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    y = jnp.asarray(ds.y[:nn], jnp.float64)
    mask0 = jnp.ones(nn, bool).at[jnp.asarray(chunks[0])].set(False)
    res0 = smo_solve(K, y, mask0, ds.C, jnp.zeros(nn), -y)
    return ds, K, y, chunks, res0, cv._transition_idx(chunks, 0, 1)


def _slab(K, y, C, prev, S, R, T):
    """The slab form: ``A = K[X, T]`` and the right-hand side gathered
    whole, the equality row appended, as ``mir_seed`` built them before."""
    X_idx = jnp.concatenate([S, R])
    df, beta_R = seeding._mir_rhs(y, C, prev, S, R)
    rhs = df[X_idx] + kdot(K[jnp.ix_(X_idx, R)], beta_R)
    A = jnp.concatenate([K[jnp.ix_(X_idx, T)],
                         jnp.ones((1, T.shape[0]), K.dtype)], 0)
    return A, jnp.concatenate([rhs, jnp.sum(beta_R)[None]], 0)


@pytest.mark.parametrize("name,n,k", [("adult", 600, 6), ("mnist", 600, 10)])
@pytest.mark.parametrize("block", [64, 97, 257])
def test_blockwise_start_equals_the_slab_start(name, n, k, block):
    """|S + R| is 500 or 540, so no block size here divides it, and 97 and
    257 leave a last block whose start is clamped back into K."""
    ds, K, y, chunks, res0, (S, R, T) = _transition(name, n, k)
    A, rhs = _slab(K, y, ds.C, res0, S, R, T)
    G_slab = jnp.dot(A.T, A, precision=jax.lax.Precision.HIGHEST)
    A64, r64 = np.asarray(A, np.float64), np.asarray(rhs)
    exact, scale = A64.T @ r64, np.abs(A64).T @ np.abs(r64)

    G, Atb = seeding._mir_assemble(K, y, ds.C, res0, S, R, T, block=block)
    assert G.shape == G_slab.shape and Atb.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_slab), rtol=0,
                               atol=4 * EPS32 * float(jnp.max(G_slab)))
    # both right-hand sides are the exact sums to float32 rounding; the
    # blockwise one, summed across blocks in f64, lies closer
    assert np.all(np.abs(np.asarray(Atb) - exact) <= EPS32 * scale)
    slab_Atb = np.asarray(kdot(A.T, rhs))
    assert np.all(np.abs(slab_Atb - exact) <= 4 * EPS32 * scale)

    # the start is the slab's system solved from those sums: the same f32
    # normal equations. (The solve amplifies a last-place change of the
    # f32 right-hand side by G's condition, so the slab's own start moves
    # as far from this one as the rounding of its sums allows.)
    start = seeding._mir_solve(G, Atb, y, ds.C, res0.alpha, S, R, T)
    want = seeding._mir_solve(G_slab, jnp.asarray(exact), y, ds.C,
                              res0.alpha, S, R, T)
    np.testing.assert_allclose(np.asarray(start), np.asarray(want),
                               rtol=0, atol=1e-6 * ds.C)
    slab = seeding._mir_solve(G_slab, jnp.asarray(slab_Atb), y, ds.C,
                              res0.alpha, S, R, T)
    beta = np.asarray((y * start)[T])
    for a0 in (start, slab):
        assert abs(float(jnp.sum((y * a0)[jnp.concatenate([S, T])]))) < 1e-9
        assert float(jnp.max(jnp.abs(a0[R]))) == 0.0
        assert bool(jnp.all((a0 >= 0) & (a0 <= ds.C)))
    # the f32 solve's sensitivity bounds how far the slab's start lies
    lam = seeding.MIR_RIDGE * np.trace(np.asarray(G, np.float64)) / G.shape[0]
    inv = 1.0 / np.linalg.eigvalsh(np.asarray(G, np.float64)
                                   + lam * np.eye(G.shape[0]))[0]
    moved = inv * np.linalg.norm(slab_Atb.astype(np.float32)
                                 - exact.astype(np.float32))
    assert np.max(np.abs(np.asarray((y * slab)[T]) - beta)) <= 4 * moved + 1e-9


def test_wrapper_runs_the_two_programs_and_records_the_span():
    ds, K, y, chunks, res0, (S, R, T) = _transition("adult", 600, 6)
    n = y.shape[0]
    obs.reset()
    got = seeding.mir_seed(K, y, ds.C, res0, S, R, T)
    spans = [s for s in obs.records() if s.name == "repro.seed.assemble"]
    assert len(spans) == 1
    block = min(seeding.MIR_BLOCK, n)
    assert spans[0].attrs == {"blocks": math.ceil(n / block),
                              "rows": int(S.shape[0] + R.shape[0])}
    G, Atb = seeding._mir_assemble(K, y, ds.C, res0, S, R, T, block=block)
    want = seeding._mir_solve(G, Atb, y, ds.C, res0.alpha, S, R, T)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_f64_reference_path_keeps_lstsq_and_no_assembly():
    ds, K, y, chunks, res0, (S, R, T) = _transition("adult", 300, 6,
                                                    jnp.float64)
    obs.reset()
    a0 = seeding.mir_seed(K, y, ds.C, res0, S, R, T)
    assert not [s for s in obs.records() if s.name == "repro.seed.assemble"]
    A, rhs = _slab(K, y, ds.C, res0, S, R, T)
    beta_T = jnp.linalg.lstsq(A, rhs)[0]
    want = seeding._mir_start(beta_T, y, ds.C, res0.alpha, S, R, T)
    np.testing.assert_allclose(np.asarray(a0), np.asarray(want), atol=1e-12)


def _run_cv(monkeypatch, ds, k, method):
    """``run_cv``'s report and the fold results its plan solved."""
    solved, run_plan = [], cv.run_plan

    def keep(*a, **kw):
        solved.append(run_plan(*a, **kw))
        return solved[-1]

    monkeypatch.setattr(cv, "run_plan", keep)
    rep = cv.run_cv(ds, k=k, method=method)
    monkeypatch.undo()
    return rep, solved[0].results


@pytest.mark.parametrize("name", ["adult", "mnist"])
def test_mir_folds_reach_the_cold_kkt_point(name, monkeypatch):
    """Seeding moves the start, not the fixed point: every MIR fold ends at
    cold's dual objective (to the solver's tolerance), and a held-out
    prediction differs from cold's only where a decision value lies within
    that tolerance of 0 (the chance-level mnist stand-in has such rows)."""
    ds = make_dataset(name, n_override=600)
    cold, cold_res = _run_cv(monkeypatch, ds, 6, "cold")
    mir, mir_res = _run_cv(monkeypatch, ds, 6, "mir")
    chunks = kfold_chunks(600, 6, seed=0)
    X = kernel_input(ds.X)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    y = jnp.asarray(ds.y, jnp.float64)
    assert mir.total_iterations < cold.total_iterations
    for c, m in zip(cold.folds, mir.folds):
        assert m.converged and c.converged
        assert m.objective == pytest.approx(c.objective, rel=1e-5)
        test = jnp.asarray(chunks[m.fold])
        mask = jnp.ones(600, bool).at[test].set(False)
        dec = [kdot(K[test], r.alpha * y)
               + bias_from_solution(r, y, mask, ds.C)
               for r in (cold_res[c.fold], mir_res[m.fold])]
        differs = (dec[0] >= 0) != (dec[1] >= 0)
        near = (jnp.abs(dec[0]) < 2e-3) | (jnp.abs(dec[1]) < 2e-3)
        assert bool(jnp.all(~differs | near))
        if name == "adult":
            assert m.acc_correct == c.acc_correct


def test_run_cv_mir_agrees_with_the_reference(monkeypatch):
    """``run_cv(method="mir")`` on the mnist stand-in: every fold's alpha
    meets the float64 reference's KKT conditions at tol, its f and dual
    objective are the reference's, and the reference, predicting the
    held-out rows from that alpha, gets as many right as run_cv counted."""
    ds = make_dataset("mnist", n_override=600)
    rep, results = _run_cv(monkeypatch, ds, 10, "mir")
    chunks = kfold_chunks(ds.n, 10, seed=0)
    n = chunks.size
    y = np.asarray(ds.y[:n], np.float64)
    X32 = np.asarray(ds.X[:n], np.float32)
    for f in rep.folds:
        res = results[f.fold]
        train = np.ones(n, bool)
        train[chunks[f.fold]] = False
        test = chunks[f.fold]
        # pred = the true labels: the count that does not match is the
        # reference's own held-out errors
        got = reference.check(X32, y, ds.C, ds.gamma, 1e-3, [dict(
            alpha=np.asarray(res.alpha), f=np.asarray(res.f), train=train,
            test=test, pred=y[test], objective=f.objective)])
        assert got["kkt_excess"] <= 1e-4, (f.fold, got)
        assert got["f_err"] <= 1e-4 and got["obj_rel"] <= 1e-5, got
        assert got["pred_mismatch"] == f.acc_total - f.acc_correct, got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_eval_fold_counts_as_the_gathered_rows_did(dtype):
    ds = make_dataset("adult", n_override=300)
    chunks = kfold_chunks(300, 5, seed=0)
    X = kernel_input(ds.X).astype(dtype)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    y = jnp.asarray(ds.y, jnp.float64)
    for h in range(5):
        test = jnp.asarray(chunks[h])
        mask = jnp.ones(300, bool).at[test].set(False)
        res = smo_solve(K, y, mask, ds.C, jnp.zeros(300), -y)
        b = bias_from_solution(res, y, mask, ds.C)
        pred = predict(K[test], y, res.alpha, b)
        want = (int(jnp.sum(pred == y[test])), int(test.shape[0]),
                float(dual_objective(K, y, res.alpha)))
        assert cv._eval_fold(K, y, chunks, h, res, ds.C) == want


def test_mir_assemble_s_reads_the_window_folds():
    """The reader sums the window folds' ``repro.seed.assemble`` spans;
    a cell whose folds run no MIR assembly reads nothing."""
    reader = bench_run.load_module(ROOT / "bench" / "metrics"
                                   / "mir_assemble_s.py")
    got = {}
    for cell in ("adult.mir", "adult.cold"):
        c = bench_run.resolve(cell)
        out = bench_run.run_cell(c, 2**31 + 16, 0.3, False, rows=310,
                                 t_start=0.0)
        r = types.SimpleNamespace(folds=out["info"]["folds"], trace=None,
                                  cfg=c.cfg, traffic=c.traffic)
        got[cell] = reader.read(r)
    assert got["adult.cold"] is None
    assert 0.0 < got["adult.mir"] < 10.0
