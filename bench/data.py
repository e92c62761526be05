"""The benchmark's own data generator and fold partition.

A copy of the stand-in generator and ``kfold_chunks`` that the program
ships (``repro.data.svm_suite``), kept here so that no change to the
program can change the data a cell runs on. Its parameters are the
configuration's own: ``dataset`` (the name its random stream is drawn
under), ``features`` and the ``generator`` block, so a new dataset is a
new configuration file and nothing else. ``tests/bench`` holds each
configuration's block equal to the program's entry for its dataset,
where the program has one.

Generator: two anisotropic Gaussian clusters over ``n_informative`` dims
at centres drawn at scale ``separation``, the other dims pure noise, then
label noise ``flip``; ``balanced`` gives an exact 50/50 label split;
features scaled to [-1, 1]. Deterministic per (dataset, seed): the seed
goes through crc32, not ``hash()``, which Python salts per process.
"""
from __future__ import annotations

import zlib

import numpy as np


def make_dataset(cfg: dict, *, seed: int, n: int):
    """``(X, y)`` of the stand-in that configuration ``cfg`` describes, at
    ``n`` rows: X (n, cfg["features"]) float64 in [-1, 1], y (n,) int64 in
    {-1, +1}."""
    gen, d = cfg["generator"], cfg["features"]
    n_inf, sep = gen["n_informative"], gen["separation"]
    rng = np.random.default_rng(zlib.crc32(f"{cfg['dataset']}:{seed}".encode()))
    if gen["balanced"]:
        y = np.repeat([1, -1], [n - n // 2, n // 2])
        y = y[rng.permutation(n)]
    else:
        y = np.where(rng.random(n) < 0.5, 1, -1)
    X = rng.normal(size=(n, d))
    if n_inf > 0:
        centers = rng.normal(size=(2, n_inf)) * sep
        scales = 0.5 + rng.random(n_inf)
        X[:, :n_inf] = X[:, :n_inf] * scales + np.where(y[:, None] > 0,
                                                        centers[0], centers[1])
    flip_mask = rng.random(n) < gen["flip"]
    y = np.where(flip_mask, -y, y)
    X = X / (np.abs(X).max(axis=0, keepdims=True) + 1e-12)
    return X.astype(np.float64), y.astype(np.int64)


def kfold_chunks(n: int, k: int, *, seed: int = 0) -> np.ndarray:
    """A shuffled permutation of ``range(k * (n // k))`` split into k equal
    chunks, shape (k, n // k); chunk h is fold h's held-out set."""
    rng = np.random.default_rng(seed)
    m = n // k
    return rng.permutation(k * m).reshape(k, m)


def cell_inputs(cfg: dict, seed: int):
    """``(X, y, chunks)`` of one run of a cell with configuration ``cfg``.
    The configuration's ``data_seed`` fixes the dataset and the fold
    partition, so every seed gets the same rows in the same folds, in a row
    order drawn from ``seed``: seeds differ in their inputs and not in the
    work they need."""
    n, k = cfg["published_rows"], cfg["k"]
    X, y = make_dataset(cfg, seed=cfg["data_seed"], n=n)
    chunks = kfold_chunks(n, k, seed=cfg["data_seed"])
    # the rows past k * (n // k), in no fold, stay where they are
    solved = chunks.size
    order = np.concatenate([np.random.default_rng((seed, 1)).permutation(solved),
                            np.arange(solved, n)])
    return X[order], y[order], np.argsort(order)[chunks]


def transition_idx(chunks: np.ndarray, g: int, h: int):
    """(S, R, T) index sets for seeding fold h from fold g's solution:
    fold g trained on all but chunk g, fold h on all but chunk h, so T
    (added) is chunk g, R (removed) is chunk h, and S is the rest."""
    k = chunks.shape[0]
    S = np.concatenate([chunks[j] for j in range(k) if j not in (g, h)])
    return S, chunks[h], chunks[g]


def train_masks(chunks: np.ndarray) -> np.ndarray:
    """(k, n) boolean train masks; row h is False on fold h's chunk."""
    k, n = chunks.shape[0], chunks.size
    masks = np.ones((k, n), bool)
    for h in range(k):
        masks[h, chunks[h]] = False
    return masks
