"""The correctness check's control and its evaluation fault, at a size a
test run holds (2,000 rows; the readings at the cells' own sizes, on the
chip, are in PERF.md), judged by ``run.judge``, the comparison that
decides a run's ``correct``: the plain reference solving in the program's
place at the configuration's precision is correct under each cell's
limits; solving over the kernel one step of precision below (matmul at
``high``) reads ten times the sound solve or more on a compared number;
and the sound solve's predictions taken for the next fold's rows are not
correct. The control's errors grow with the rows, so at this size
they stay under the limits set at the cells' sizes. The solve that
computes its kernel rows from X, for a K that one chip cannot hold, is
the dense solve to the last bit."""
import functools
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import control  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

ROWS = 2000
CELLS = [w["name"] for w in run.load_json(ROOT / "BENCHMARK.json")["workloads"]]


@functools.lru_cache(maxsize=None)
def _readings(config: str, seed: int) -> dict:
    cfg = run.load_json(ROOT / "bench" / "configs" / f"{config}.json")
    cfg = dict(cfg, published_rows=ROWS, rows=ROWS)
    return control.readings(cfg, seed, max_iter=100_000)


@pytest.mark.parametrize("seed", [5, 2**31 + 6])
@pytest.mark.parametrize("cell", CELLS)
def test_control_separates_from_sound_reference(cell, seed):
    c = run.resolve(cell)
    got = _readings(c.cell["config"], seed)
    sound, ctl = got["sound"], got["control"]
    assert run.judge(sound, c.limits)[1], sound
    assert any(ctl[k] > 0 and ctl[k] >= 10 * sound[k] for k in c.limits), got
    assert not run.judge(got["wrong_fold"], c.limits)[1], got["wrong_fold"]


@pytest.mark.parametrize("rows", [310, 2000])
@pytest.mark.parametrize("fn", ["rbf", "rbf_high"])
def test_rows_solve_is_the_dense_solve(fn, rows):
    """``smo_rows`` takes the same iterations to the same alpha as ``smo``
    over the dense K of the same kernel, and ``evaluate_rows`` predicts
    the held-out rows as ``evaluate`` does."""
    cfg = run.load_json(ROOT / "bench" / "configs" / "adult.json")
    X, y, chunks = data.cell_inputs(dict(cfg, published_rows=rows), 2**31 + 3)
    n = chunks.size
    X32, y = jnp.asarray(X[:n], jnp.float32), y[:n]
    train = jnp.asarray(data.train_masks(chunks)[4])
    test = jnp.asarray(chunks[4])
    kern = getattr(reference, fn)
    C, tol, gamma = cfg["C"], cfg["tol"], cfg["gamma"]
    K = reference.kernel(X32, gamma, kern)
    a, f, it = reference.smo(K, y, train, C, tol, 100_000)
    pred, obj = reference.evaluate(K, test, y, a, f, train, C)
    a2, f2, it2 = reference.smo_rows(X32, gamma, kern, y, train, C, tol,
                                     100_000)
    pred2, obj2 = reference.evaluate_rows(X32, gamma, kern, test, y, a2, f2,
                                          train, C)
    assert int(it) == int(it2) and 0 < int(it) < 100_000
    a, a2 = np.asarray(a), np.asarray(a2)
    assert np.max(np.abs(a - a2)) <= 1e-12 * np.max(np.abs(a))
    assert np.array_equal(np.asarray(pred), pred2)
    assert abs(float(obj) - float(obj2)) <= 1e-12 * abs(float(obj))


def test_control_without_a_dense_kernel_reads_as_with_one(monkeypatch):
    """Where the dense K does not fit, ``readings`` solves from X and
    reads what the dense solve reads."""
    dense = _readings("adult", 5)
    monkeypatch.setattr(control, "fits_dense", lambda n, limit: False)
    cfg = run.load_json(ROOT / "bench" / "configs" / "adult.json")
    rows = control.readings(dict(cfg, published_rows=ROWS, rows=ROWS), 5,
                            max_iter=100_000)
    assert (dense["solve"], rows["solve"]) == ("dense", "rows")
    for name in ("sound", "control", "wrong_fold"):
        for key in ("kkt_excess", "f_err", "obj_rel", "pred_mismatch",
                    "n_iter"):
            assert rows[name][key] == dense[name][key], (name, key)


def test_dense_kernel_fits_up_to_mnist():
    """The rule that picks the solve: on a v5e, whose ``bytes_limit``
    this is, mnist's 60,000 rows take the dense K and 64,000 rows do not;
    a device with no stated limit takes it."""
    v5e = 16_909_336_064
    assert control.fits_dense(60_000, v5e) and control.fits_dense(62_919, v5e)
    assert not control.fits_dense(62_920, v5e)
    assert not control.fits_dense(64_000, v5e)
    assert control.fits_dense(10**6, None)
