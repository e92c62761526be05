"""The benchmark's own data generator and fold partition equal the
program's today, each configuration's stand-in is the data its cells ran
on before the configuration carried its generator, and the helpers the
fold ring uses hold their shapes."""
import hashlib
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import data  # noqa: E402
import run  # noqa: E402
from repro.data import svm_suite  # noqa: E402

SEEDS = (0, 7, 2**31 + 12345)
CONFIGS = {p.stem: run.load_json(p)
           for p in sorted((ROOT / "bench" / "configs").glob("*.json"))}

#: sha256 of ``cell_inputs`` at 2,000 rows, as the generator gave it when
#: its parameters were a table in ``bench/data.py`` (``SPECS``), keyed by
#: (configuration, seed)
HASHES_2000 = {
    ("adult", 0): "95f46317d434b73b919c03689e8950ad620cb831c5482aec638638216ecb4198",
    ("adult", 7): "0864728f1f47d00960338223bf8d5f20dd54be14af51bb8623b54386fdfb8d43",
    ("adult", 2**31 + 12345): "3c1fe1ae1fea26325e49958539a99aaff651dd0fa98991d01799a566359b9bd5",
    ("webdata", 0): "39e7777c521ad4d37ef99363ae3025f748776702c0b77db3aa4a8a1d728d5ed4",
    ("webdata", 7): "f41fe3a3e524dddd765f16f65bc6ff32470c586e10c2e1a02a0af91571771c20",
    ("webdata", 2**31 + 12345): "ed30f9ede1f74195d22fbba3728d1cd8708cd5c08fd7c83243046bf8403bcfb3",
    ("mnist", 0): "13f1796151e5014bc1b81d93356b3bef2621ee2343325ca4ae318356872aabd9",
    ("mnist", 7): "954d66c44d41761c65bdeaf3b9bf458bd92af00411b37859116d92a3a5ad4948",
    ("mnist", 2**31 + 12345): "af575148ff5a441bca61739e554c12db4cb2834525ad1ca40a16fead83092b86",
}


def program_config(name: str) -> dict:
    """A configuration whose stand-in is the program's entry for ``name``:
    its width and a generator block built from that entry."""
    _, d, _, _, n_inf, sep, flip, balanced = svm_suite.SPECS[name]
    return {"dataset": name, "features": d,
            "generator": {"n_informative": n_inf, "separation": sep,
                          "flip": flip, "balanced": balanced}}


@pytest.mark.parametrize("name", sorted(svm_suite.SPECS))
@pytest.mark.parametrize("seed", SEEDS)
def test_make_dataset_equals_program(name, seed):
    X, y = data.make_dataset(program_config(name), seed=seed, n=301)
    ref = svm_suite.make_dataset(name, seed=seed, n_override=301)
    assert np.array_equal(X, ref.X) and np.array_equal(y, ref.y)
    assert X.dtype == np.float64 and set(np.unique(y)) <= {-1, 1}


def test_specs_equal_program():
    """Each configuration's width, C, gamma and generator block equal the
    program's entry for its dataset, where the program has one."""
    known = [c for c in CONFIGS.values() if c["dataset"] in svm_suite.SPECS]
    assert {c["dataset"] for c in known} >= {"adult", "webdata", "mnist"}
    for cfg in known:
        _, d, C, gamma, *_ = svm_suite.SPECS[cfg["dataset"]]
        assert (cfg["features"], cfg["C"], cfg["gamma"]) == (d, C, gamma)
        want = program_config(cfg["dataset"])["generator"]
        assert cfg["generator"] == want, cfg["name"]


def _sha256(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config,seed", sorted(HASHES_2000))
def test_cell_inputs_are_the_tables(config, seed):
    """The stand-in made from the configuration's own generator block is
    byte for byte the one the table made."""
    cfg = dict(CONFIGS[config], published_rows=2000, rows=2000)
    assert _sha256(*data.cell_inputs(cfg, seed)) == HASHES_2000[config, seed]


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
    cfg = dict(CONFIGS["webdata"], published_rows=205)
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
