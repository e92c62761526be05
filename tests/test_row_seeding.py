"""Seeded k-fold CV without a dense K: SIR's K[R, T] block from X
(``OnDemandRBF.block``), f0 from the streaming ``matvec``, and
``run_cv(source_backend="pallas_rbf")`` through ``run_plan`` and the lane
pool, held against the dense kernel, the dense seeder and the plain
reference of the benchmark (``bench/reference.py``), on the Forest
stand-in in interpret mode."""
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import seeding
from repro.core.cv import _fold_masks, _transition_idx, run_cv
from repro.core.study import Plan, run_plan
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import (DenseKernel, PallasRBF, bias_from_solution,
                       kernel_matrix, predict, smo_solve)
from repro.svm.precision import STATE_DTYPE, kernel_input

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import reference  # noqa: E402
import run  # noqa: E402

ROWS = (310, 2000)
LIMITS = run.load_json(ROOT / "bench" / "limits" / "forest.sir_pallas.json")


@functools.lru_cache(maxsize=None)
def _forest(n: int, k: int = 10):
    ds = make_dataset("forest", n_override=n)
    chunks = kfold_chunks(ds.n, k, seed=0)
    m = chunks.size
    X = kernel_input(ds.X)[:m]
    y = jnp.asarray(ds.y[:m], STATE_DTYPE)
    return ds, X, y, chunks


def _first_fold(ds, K, y, chunks):
    """Fold 0 solved over the dense K, and the 0 -> 1 transition sets."""
    mask0 = jnp.asarray(_fold_masks(chunks)[0])
    res0 = smo_solve(K, y, mask0, ds.C, jnp.zeros(y.shape[0], STATE_DTYPE),
                     -y)
    return res0, _transition_idx(chunks, 0, 1)


@pytest.mark.parametrize("n", ROWS)
def test_block_is_the_kernel_block(n):
    """``block(R, T)`` is ``rows_at(R)[:, T]`` and the dense K's block
    within float32 rounding."""
    ds, X, y, chunks = _forest(n)
    src = PallasRBF(X, ds.gamma)
    S, R, T = _transition_idx(chunks, 0, 1)
    got = np.asarray(src.block(R, T))
    assert got.shape == (R.shape[0], T.shape[0]) and got.dtype == np.float32
    slab = np.asarray(src.rows_at(R))[:, np.asarray(T)]
    K = kernel_matrix(X, X, kind="rbf", gamma=ds.gamma)
    dense = np.asarray(K)[np.ix_(np.asarray(R), np.asarray(T))]
    eps = np.finfo(np.float32).eps
    assert np.max(np.abs(got - slab)) <= 4 * eps
    assert np.max(np.abs(got - dense)) <= 4 * eps
    assert 0.05 < got.mean() < 0.95          # an informative block


@jax.tree_util.register_pytree_node_class
class _BlockOf:
    """A source with no K whose ``block`` is a dense K's slice."""

    def __init__(self, K):
        self._K = K

    def block(self, rows, cols):
        return self._K[rows][:, cols]

    def tree_flatten(self):
        return (self._K,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@pytest.mark.parametrize("n", ROWS)
def test_sir_from_a_block_is_the_dense_seeder(n):
    """Handed the dense K's own block, the row-streaming ``sir_seed``
    gives the dense seeder's alpha0 to the last bit, and times the block
    in a ``repro.seed.block`` span."""
    ds, X, y, chunks = _forest(n)
    K = kernel_matrix(X, X, kind="rbf", gamma=ds.gamma)
    res0, (S, R, T) = _first_fold(ds, K, y, chunks)
    dense = seeding.sir_seed(K, y, ds.C, res0, S, R, T)
    obs.reset()
    rows = seeding.sir_seed(_BlockOf(K), y, ds.C, res0, S, R, T)
    assert np.array_equal(np.asarray(dense), np.asarray(rows))
    spans = [s for s in obs.records() if s.name == "repro.seed.block"]
    assert [(s.attrs["rows"], s.attrs["cols"]) for s in spans] == \
        [(R.shape[0], T.shape[0])]
    # and from X: the same start within the block's float32 rounding
    from_x = seeding.sir_seed(PallasRBF(X, ds.gamma), y, ds.C, res0, S, R, T)
    assert np.max(np.abs(np.asarray(from_x) - np.asarray(dense))) <= \
        1e-9 * ds.C


def _dense_wss1_sir(ds, X, y, chunks):
    """The SIR fold chain as ``run_cv`` declares it, over the dense K under
    WSS-1: the solve the matrix-free chain stands in for."""
    K = kernel_matrix(X, X, kind="rbf", gamma=ds.gamma)
    masks = jnp.asarray(_fold_masks(chunks))
    plan = Plan(sources={"cv": DenseKernel(K)}, y=y, tol=1e-3, wss="1",
                chunk_iters=4096)
    k = chunks.shape[0]
    plan.lane(0, train_mask=masks[0], C=ds.C, alpha0=jnp.zeros_like(y),
              f0=-y)
    for h in range(1, k):
        S, R, T = _transition_idx(chunks, h - 1, h)
        plan.lane(h, train_mask=masks[h], C=ds.C, dep=h - 1, transform="fold",
                  params=dict(method="sir", S_idx=S, R_idx=R, T_idx=T),
                  after=h - 1)
    res = run_plan(plan).results
    v = [res[h].alpha * y for h in range(k)]
    return [float(jnp.sum(res[h].alpha) - 0.5 * v[h] @ (K.astype(STATE_DTYPE)
                                                         @ v[h]))
            for h in range(k)]


@functools.lru_cache(maxsize=None)
def _pallas_cv(n: int):
    """``run_cv``'s matrix-free SIR chain, with every fold's answer kept
    from the pool's retirement callback."""
    ds, X, y, chunks = _forest(n)
    kept = {}
    real = run_plan

    def keeping(plan, **kw):
        out = real(plan, **kw)
        kept.update(out.results)
        return out

    import repro.core.cv as cv
    obs.reset()
    cv.run_plan = keeping
    try:
        report = run_cv(ds, k=10, method="sir", source_backend="pallas_rbf")
    finally:
        cv.run_plan = real
    return report, kept, obs.records()


@pytest.mark.parametrize("n", ROWS)
def test_matrix_free_sir_cv_passes_the_reference(n):
    """Every fold of ``run_cv(method="sir", source_backend="pallas_rbf")``
    passes the benchmark's plain reference under the cell's limits, its
    objectives are the dense WSS-1 SIR chain's within 1e-6, and each
    seeded fold read its block and its f0 through the streaming spans."""
    ds, X, y, chunks = _forest(n)
    report, results, spans = _pallas_cv(n)
    assert [f.fold for f in report.folds] == list(range(10))
    assert [f.seed_from for f in report.folds] == list(range(-1, 9))
    assert all(f.converged and f.n_iter > 0 for f in report.folds)
    assert all(f.init_time > 0 for f in report.folds[1:])
    masks = _fold_masks(chunks)
    src = PallasRBF(X, ds.gamma)
    answers = []
    for f in report.folds:
        res, test = results[f.fold], chunks[f.fold]
        b = bias_from_solution(res, y, jnp.asarray(masks[f.fold]), ds.C)
        pred = predict(src.rows_at(jnp.asarray(test)), y, res.alpha, b)
        answers.append(dict(alpha=np.asarray(res.alpha), f=np.asarray(res.f),
                            pred=np.asarray(pred), objective=f.objective,
                            train=masks[f.fold], test=test))
    numbers = reference.check(np.asarray(X), np.asarray(y), ds.C, ds.gamma,
                              1e-3, answers)
    assert run.judge(numbers, LIMITS)[1], numbers
    dense = _dense_wss1_sir(ds, X, y, chunks)
    got = [f.objective for f in report.folds]
    assert np.allclose(got, dense, rtol=1e-6, atol=0), (got, dense)
    blocks = [s for s in spans if s.name == "repro.seed.block"]
    matvecs = [s for s in spans if s.name == "repro.seed.matvec"]
    assert len(blocks) == len(matvecs) == 9
    assert all(s.attrs["rows"] == n for s in matvecs)


@pytest.mark.parametrize("method", ["mir", "ato"])
def test_slab_seeders_refused_at_entry_on_a_row_source(method):
    """MIR and ATO read whole slabs of K: on ``PallasRBF`` they fail at
    ``run_plan`` entry, naming the method, before fold 0 solves."""
    ds, X, y, chunks = _forest(310)
    with pytest.raises(ValueError, match=rf"lane 1: transform 'fold' with "
                       rf"method '{method}' needs a dense kernel source "
                       r"\(source 'cv' has no K\)"):
        run_cv(ds, k=10, method=method, source_backend="pallas_rbf",
               max_iter=1)


def test_seed_stream_s_reads_the_window_folds():
    """The reader sums the window folds' ``repro.seed.block`` and
    ``repro.seed.matvec`` spans; dense SIR opens neither, so webdata.sir
    reads nothing."""
    import types
    reader = run.load_module(ROOT / "bench" / "metrics" / "seed_stream_s.py")
    got = {}
    for cell in ("forest.sir_pallas", "webdata.sir"):
        c = run.resolve(cell)
        out = run.run_cell(c, 2**31 + 21, 0.3, False, rows=310, t_start=0.0)
        r = types.SimpleNamespace(folds=out["info"]["folds"], trace=None,
                                  cfg=c.cfg, traffic=c.traffic)
        got[cell] = reader.read(r)
    assert got["webdata.sir"] is None
    assert 0.0 < got["forest.sir_pallas"] < 10.0
