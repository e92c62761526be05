"""The benchmark's own data generator and fold partition equal the
program's today, and the helpers the fold ring uses hold their shapes."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import data  # noqa: E402
from repro.data import svm_suite  # noqa: E402

SEEDS = (0, 7, 2**31 + 12345)


@pytest.mark.parametrize("name", sorted(data.SPECS))
@pytest.mark.parametrize("seed", SEEDS)
def test_make_dataset_equals_program(name, seed):
    X, y = data.make_dataset(name, seed=seed, n=301)
    ref = svm_suite.make_dataset(name, seed=seed, n_override=301)
    assert np.array_equal(X, ref.X) and np.array_equal(y, ref.y)
    assert X.dtype == np.float64 and set(np.unique(y)) <= {-1, 1}


def test_specs_equal_program():
    assert data.SPECS == svm_suite.SPECS


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("n,k", [(32561, 10), (49749, 10), (300, 7)])
def test_kfold_chunks_equal_program(n, k, seed):
    got = data.kfold_chunks(n, k, seed=seed)
    assert np.array_equal(got, svm_suite.kfold_chunks(n, k, seed=seed))
    assert got.shape == (k, n // k)
    assert np.array_equal(np.sort(got.ravel()), np.arange(k * (n // k)))


def test_transition_sets_and_masks():
    chunks = data.kfold_chunks(100, 10, seed=3)
    masks = data.train_masks(chunks)
    for h in range(10):
        g = (h - 1) % 10
        S, R, T = data.transition_idx(chunks, g, h)
        # fold g trains on S + R, fold h on S + T
        assert set(S) | set(R) == set(np.flatnonzero(masks[g]))
        assert set(S) | set(T) == set(np.flatnonzero(masks[h]))
        assert not set(S) & set(R) and not set(S) & set(T)
        assert not masks[h, chunks[h]].any() and masks[h].sum() == 90


def test_fixed_dataset_gives_every_seed_the_same_folds():
    """With ``data_seed``, seeds differ in the order of the rows alone:
    every fold holds the same rows."""
    cfg = {"dataset": "webdata", "published_rows": 205, "k": 10,
           "data_seed": 0}
    runs = [data.cell_inputs(cfg, s) for s in (1, 2, 2**31 + 7)]
    assert not np.array_equal(runs[0][0], runs[1][0])
    for X, y, chunks in runs:
        assert np.array_equal(np.sort(chunks.ravel()), np.arange(200))
        ref = runs[0]
        for h in range(10):
            rows = sorted(map(tuple, np.c_[X[chunks[h]], y[chunks[h]]]))
            want = sorted(map(tuple, np.c_[ref[0][ref[2][h]], ref[1][ref[2][h]]]))
            assert rows == want
    again = data.cell_inputs(cfg, 2)
    assert all(np.array_equal(a, b) for a, b in zip(again, runs[1]))
