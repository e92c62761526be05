"""Unified SMO engine: kernel-source agreement, chunked-dispatch exactness,
batched fold execution, and wrapper parity (smo_solve / smo_iterations)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.svm_suite import make_dataset, kfold_chunks
from repro.svm import (DenseKernel, FusedRBF, LanePool, OnDemandRBF,
                       PallasRBF, init_f, kernel_matrix, smo_solve)
from repro.svm.distributed import smo_iterations
from repro.svm.engine import EngineState, smo_chunk, solve


def _setup(name="heart", n=150):
    ds = make_dataset(name, n_override=n)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    return ds, X, K, y


def _pool_batch(source, y, masks, Cs, alpha0s, f0s, *, wss="2",
                max_iter=10_000_000, n_iter0s=None):
    """Solve one lane per row of ``masks`` on a LanePool at the batch's
    full width: ``max_width=0`` and ``lane_quantum`` = the lane count, so
    the packed width is the batch's own until lanes retire. Returns the
    lanes' SMOResults stacked along a leading lane axis."""
    b = masks.shape[0]
    Cs = np.broadcast_to(np.asarray(Cs, np.float64), (b,))
    n_iter0s = np.broadcast_to(
        np.asarray(0 if n_iter0s is None else n_iter0s), (b,))
    pool = LanePool({"src": source}, y, wss=wss, max_width=0,
                    lane_quantum=b, chunk_iters=4096)
    for h in range(b):
        pool.add(h, masks[h], float(Cs[h]), alpha0s[h], f0s[h], source="src",
                 n_iter0=int(n_iter0s[h]), max_iter=max_iter)
    res = pool.run()
    return jax.tree.map(lambda *xs: jnp.stack(xs), *(res[h] for h in range(b)))


# ------------------------------------------------- kernel-source parity ---

def test_sources_agree_on_rows():
    """Every provider must hand the engine the same kernel row."""
    ds, X, K, y = _setup()
    dense = DenseKernel(K)
    gather = OnDemandRBF(X, ds.gamma)
    onehot = OnDemandRBF(X, ds.gamma, impl="onehot")
    fused = FusedRBF(X, ds.gamma)
    for i, j in [(0, 7), (31, 149), (80, 80)]:
        rows = [np.asarray(dense.row(i)), np.asarray(gather.row(i)),
                np.asarray(onehot.row(i)), np.asarray(fused.rows2(i, j)[0])]
        for r in rows[1:]:
            np.testing.assert_allclose(r, rows[0], atol=1e-12)
        np.testing.assert_allclose(np.asarray(fused.rows2(i, j)[1]),
                                   np.asarray(dense.row(j)), atol=1e-12)


def test_ondemand_gather_vs_onehot_bitwise():
    """The two scalar-read/update idioms must replay the exact same fp ops."""
    ds, X, K, y = _setup(n=120)
    n = y.shape[0]
    sq = jnp.sum(X * X, axis=1)
    mask = jnp.ones(n, bool).at[:20].set(False)
    outs = {}
    for impl in ("gather", "onehot"):
        outs[impl] = smo_iterations(X, y, mask, jnp.zeros(n), -y, sq, ds.C,
                                    gamma=ds.gamma, n_iters=200, impl=impl)
    for a, b in zip(outs["gather"], outs["onehot"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_wss1_converges_same_fixed_point():
    """WSS-1/fused takes more iterations but must reach the same dual."""
    ds, X, K, y = _setup(n=120)
    n = y.shape[0]
    sq = jnp.sum(X * X, axis=1)
    mask = jnp.ones(n, bool)
    a, f, it, gap = smo_iterations(X, y, mask, jnp.zeros(n), -y, sq, ds.C,
                                   gamma=ds.gamma, n_iters=200_000,
                                   impl="onehot_fused")
    assert float(gap) <= 1e-3
    ref = smo_solve(kernel_matrix(X, X, gamma=ds.gamma), y, mask, ds.C,
                    jnp.zeros(n), -y)
    from repro.svm import dual_objective
    K_full = kernel_matrix(X, X, gamma=ds.gamma)
    assert float(dual_objective(K_full, y, a)) == pytest.approx(
        float(dual_objective(K_full, y, ref.alpha)), rel=1e-3)


def test_fused_requires_wss1():
    ds, X, K, y = _setup(n=64)
    src = FusedRBF(X, ds.gamma)
    state = EngineState(jnp.zeros(64), -y, jnp.zeros((), jnp.int32),
                        jnp.zeros((), bool))
    with pytest.raises(ValueError, match="WSS-1"):
        smo_chunk(src, y, jnp.ones(64, bool), ds.C, state, n_iters=10,
                  wss="2")


# ------------------------------------------------------ chunked dispatch ---

@pytest.mark.parametrize("chunk_iters", [64, 500])
def test_chunked_equals_monolithic_bitwise(chunk_iters):
    ds, X, K, y = _setup()
    n = y.shape[0]
    mask = jnp.ones(n, bool).at[:25].set(False)
    mono = smo_solve(K, y, mask, ds.C, jnp.zeros(n), -y)
    chun = smo_solve(K, y, mask, ds.C, jnp.zeros(n), -y,
                     chunk_iters=chunk_iters)
    for a, b in zip(mono, chun):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_snapshot_resumes_to_same_fixed_point():
    """Restart from any mid-solve snapshot (the checkpoint unit) and land on
    the identical iterate sequence — alpha, f AND the n_iter account."""
    ds, X, K, y = _setup()
    n = y.shape[0]
    mask = jnp.ones(n, bool)
    snaps = []
    full = smo_solve(K, y, mask, ds.C, jnp.zeros(n), -y, chunk_iters=100,
                     on_chunk=snaps.append)
    assert len(snaps) >= 2, "test needs a solve spanning several chunks"
    state = snaps[1]
    resumed = smo_solve(K, y, mask, ds.C, state.alpha, state.f,
                        chunk_iters=100, n_iter0=int(state.n_iter))
    np.testing.assert_array_equal(np.asarray(full.alpha),
                                  np.asarray(resumed.alpha))
    np.testing.assert_array_equal(np.asarray(full.f), np.asarray(resumed.f))
    assert int(full.n_iter) == int(resumed.n_iter)


def test_smo_iterations_is_resumable_chunk():
    """Two 150-iteration dispatches == one 300-iteration dispatch: the chunk
    is the scheduler's retry unit, with (alpha, f) as the only state."""
    ds, X, K, y = _setup(n=120)
    n = y.shape[0]
    sq = jnp.sum(X * X, axis=1)
    mask = jnp.ones(n, bool)
    a1, f1, it1, _ = smo_iterations(X, y, mask, jnp.zeros(n), -y, sq, ds.C,
                                    gamma=ds.gamma, n_iters=150)
    a2, f2, it2, _ = smo_iterations(X, y, mask, a1, f1, sq, ds.C,
                                    gamma=ds.gamma, n_iters=150)
    a3, f3, it3, _ = smo_iterations(X, y, mask, jnp.zeros(n), -y, sq, ds.C,
                                    gamma=ds.gamma, n_iters=300)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(a3))
    np.testing.assert_array_equal(np.asarray(f2), np.asarray(f3))
    assert int(it1) + int(it2) == int(it3)


def test_converged_input_passes_through():
    ds, X, K, y = _setup(n=100)
    n = y.shape[0]
    mask = jnp.ones(n, bool)
    res = smo_solve(K, y, mask, ds.C, jnp.zeros(n), -y)
    again = smo_solve(K, y, mask, ds.C, res.alpha, res.f, chunk_iters=32)
    assert int(again.n_iter) == 0
    np.testing.assert_array_equal(np.asarray(res.alpha),
                                  np.asarray(again.alpha))
    # the sharded wrapper likewise reports 0 iterations for a converged state
    sq = jnp.sum(X * X, axis=1)
    a, f, it, gap = smo_iterations(X, y, mask, res.alpha, res.f, sq, ds.C,
                                   gamma=ds.gamma, n_iters=50)
    assert int(it) == 0 and float(gap) <= 1e-3


def test_max_iter_cap_respected_across_chunks():
    ds, X, K, y = _setup()
    n = y.shape[0]
    mask = jnp.ones(n, bool)
    capped = smo_solve(K, y, mask, ds.C, jnp.zeros(n), -y, max_iter=130,
                       chunk_iters=50)
    mono = smo_solve(K, y, mask, ds.C, jnp.zeros(n), -y, max_iter=130)
    assert int(capped.n_iter) == 130 == int(mono.n_iter)
    assert not bool(capped.converged)
    np.testing.assert_array_equal(np.asarray(capped.alpha),
                                  np.asarray(mono.alpha))


# ------------------------------------------------- batched fold execution ---

def test_batched_folds_match_sequential_bitwise():
    ds, X, K, y = _setup("adult", n=400)
    k = 5
    chunks = kfold_chunks(400, k, seed=0)
    n = chunks.size
    K2, y2 = K[:n][:, :n], y[:n]
    masks = np.ones((k, n), bool)
    for h in range(k):
        masks[h, chunks[h]] = False
    masks = jnp.asarray(masks)
    bat = _pool_batch(DenseKernel(K2), y2, masks, ds.C, jnp.zeros((k, n)),
                      jnp.tile(-y2, (k, 1)))
    for h in range(k):
        seq = smo_solve(K2, y2, masks[h], ds.C, jnp.zeros(n), -y2)
        np.testing.assert_array_equal(np.asarray(seq.alpha),
                                      np.asarray(bat.alpha[h]))
        np.testing.assert_array_equal(np.asarray(seq.f),
                                      np.asarray(bat.f[h]))
        assert int(seq.n_iter) == int(bat.n_iter[h])
        assert bool(bat.converged[h])


def test_batched_per_lane_C():
    """Per-lane C values (the hyper-parameter grid axis) solve correctly."""
    ds, X, K, y = _setup(n=120)
    n = y.shape[0]
    mask = jnp.ones(n, bool).at[:20].set(False)
    Cs = jnp.asarray([0.5, 4.0, 32.0])
    bat = _pool_batch(DenseKernel(K), y, jnp.tile(mask[None], (3, 1)), Cs,
                      jnp.zeros((3, n)), jnp.tile(-y, (3, 1)))
    for lane, C in enumerate([0.5, 4.0, 32.0]):
        seq = smo_solve(K, y, mask, C, jnp.zeros(n), -y)
        np.testing.assert_array_equal(np.asarray(seq.alpha),
                                      np.asarray(bat.alpha[lane]))
        assert float(jnp.max(bat.alpha[lane])) <= C + 1e-12


def test_batched_warm_seeds():
    """Warm-started lanes (alpha-seeded folds) drop iterations in batch mode
    exactly as they do sequentially."""
    from repro.core import seeding
    from repro.core.cv import _transition_idx
    ds, X, K, y = _setup("adult", n=400)
    k = 5
    chunks = kfold_chunks(400, k, seed=0)
    n = chunks.size
    K2, y2 = K[:n][:, :n], y[:n]
    m0 = jnp.ones(n, bool).at[jnp.asarray(chunks[0])].set(False)
    m1 = jnp.ones(n, bool).at[jnp.asarray(chunks[1])].set(False)
    r0 = smo_solve(K2, y2, m0, ds.C, jnp.zeros(n), -y2)
    S, R, T = _transition_idx(chunks, 0, 1)
    a1 = seeding.sir_seed(K2, y2, ds.C, r0, S, R, T)
    f1 = init_f(K2, y2, a1)
    masks = jnp.stack([m1, m1])
    alpha0s = jnp.stack([jnp.zeros(n), a1])
    f0s = jnp.stack([-y2, f1])
    bat = _pool_batch(DenseKernel(K2), y2, masks, ds.C, alpha0s, f0s)
    assert int(bat.n_iter[1]) < int(bat.n_iter[0])
    cold = smo_solve(K2, y2, m1, ds.C, jnp.zeros(n), -y2)
    warm = smo_solve(K2, y2, m1, ds.C, a1, f1)
    assert int(bat.n_iter[0]) == int(cold.n_iter)
    assert int(bat.n_iter[1]) == int(warm.n_iter)


# ------------------------------------------- pallas row-streaming source ---

#: five-dataset acceptance sweep; (n_override, max_iter) keeps the parity
#: check fast — heart runs to full convergence, the rest are capped replays
#: of the identical iterate prefix
_SUITE = [("adult", 200, 2000), ("heart", 150, 5_000_000),
          ("madelon", 120, 2000), ("mnist", 150, 2000),
          ("webdata", 200, 2000)]


@pytest.mark.parametrize("name,n,max_iter", _SUITE)
def test_pallas_source_matches_fused_bitwise(name, n, max_iter):
    """PallasRBF (interpret mode) must replay FusedRBF's exact fp ops:
    alpha, f and the iteration count are bit-identical on every suite
    dataset — the streaming source changes memory traffic, not math."""
    ds = make_dataset(name, n_override=n)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    m = y.shape[0]
    mask = jnp.ones(m, bool).at[: m // 5].set(False)
    args = (y, mask, ds.C, jnp.zeros(m), -y)
    fr = solve(FusedRBF(X, ds.gamma), *args, wss="1", max_iter=max_iter)
    pr = solve(PallasRBF(X, ds.gamma), *args, wss="1", max_iter=max_iter)
    np.testing.assert_array_equal(np.asarray(fr.alpha), np.asarray(pr.alpha))
    np.testing.assert_array_equal(np.asarray(fr.f), np.asarray(pr.f))
    assert int(fr.n_iter) == int(pr.n_iter)
    assert bool(fr.converged) == bool(pr.converged)


def test_pallas_source_batched_bitwise():
    """The parity holds under vmap (the pool's batched dispatch path) at
    width 3, the widest at which the two programs stay bitwise."""
    ds = make_dataset("heart", n_override=120)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    n = y.shape[0]
    masks = jnp.stack([jnp.ones(n, bool).at[:20].set(False),
                       jnp.ones(n, bool).at[20:40].set(False),
                       jnp.ones(n, bool)])
    Cs = jnp.asarray([ds.C, 4.0 * ds.C, ds.C])
    a0 = jnp.zeros((3, n))
    f0 = jnp.tile(-y, (3, 1))
    fb = _pool_batch(FusedRBF(X, ds.gamma), y, masks, Cs, a0, f0, wss="1")
    pb = _pool_batch(PallasRBF(X, ds.gamma), y, masks, Cs, a0, f0, wss="1")
    for a, b in zip(fb, pb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("max_width", [1, 2])
def test_pallas_source_under_pool_bitwise(max_width):
    """Same fixed points through the lane pool's repacked dispatch, at the
    production (measured, width-1) cap and the bucket-exact batched width.
    One pool per source: parity is per-schedule (solo chunk_jit and the
    vmapped program are not mutually bitwise), and as long as both sources
    see the same dispatch trajectory their iterates stay bit-identical
    chunk by chunk. Wider batches drift at the last ulp — see
    test_pallas_wide_batch_tolerance and DESIGN.md §Pallas sources."""
    from repro.svm.scheduler import LanePool
    ds = make_dataset("heart", n_override=120)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    n = y.shape[0]
    masks = [jnp.ones(n, bool).at[h * 20:(h + 1) * 20].set(False)
             for h in range(3)]

    def run(source):
        pool = LanePool({"src": source}, y, wss="1", max_width=max_width,
                        chunk_iters=512)
        for h in range(3):
            pool.add(h, masks[h], ds.C, jnp.zeros(n), -y, source="src")
        return pool.run()

    fres = run(FusedRBF(X, ds.gamma))
    pres = run(PallasRBF(X, ds.gamma))
    for h in range(3):
        fr, pr = fres[h], pres[h]
        np.testing.assert_array_equal(np.asarray(fr.alpha),
                                      np.asarray(pr.alpha))
        np.testing.assert_array_equal(np.asarray(fr.f), np.asarray(pr.f))
        assert int(fr.n_iter) == int(pr.n_iter)


def test_pallas_wide_batch_tolerance():
    """At batch widths >= 4 XLA picks different batched-dot reduction
    strategies for the two programs, so cross-source parity relaxes from
    bitwise to last-ulp agreement (~1e-13 on f64 alphas). The measured CPU
    cost model never dispatches those widths; this pins the failure mode
    so a future regression shows up as a tolerance break, not a mystery."""
    ds = make_dataset("heart", n_override=120)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    n = y.shape[0]
    masks = jnp.stack([jnp.ones(n, bool).at[h * 15:(h + 1) * 15].set(False)
                       for h in range(5)])
    a0 = jnp.zeros((5, n))
    f0 = jnp.tile(-y, (5, 1))
    fb = _pool_batch(FusedRBF(X, ds.gamma), y, masks, ds.C, a0, f0, wss="1")
    pb = _pool_batch(PallasRBF(X, ds.gamma), y, masks, ds.C, a0, f0, wss="1")
    assert bool(jnp.all(fb.converged)) and bool(jnp.all(pb.converged))
    np.testing.assert_allclose(np.asarray(fb.alpha), np.asarray(pb.alpha),
                               atol=1e-10)


@pytest.mark.parametrize("case", ["i<j", "i>j", "i=j", "vmapped",
                                  "compact"])
def test_pallas_pair_reads_rows_by_index(case):
    """The WSS pair's feature rows are X[[i, j]], bit for bit, with traced
    indices as in the SMO loop: solo, under vmap (the pool's batched
    dispatch) and on a source from ``compact`` (the shrinking scheduler's
    active set, whose pads, index n, clamp to the last row)."""
    ds = make_dataset("heart", n_override=150)
    X = jnp.asarray(ds.X, jnp.float32)
    src = PallasRBF(X, ds.gamma)
    Xn = np.asarray(X)
    pair = jax.jit(lambda s, i, j: s._pair(i, j))
    if case == "vmapped":
        i, j = np.array([0, 149, 31, 80]), np.array([7, 2, 31, 149])
        got = jax.jit(jax.vmap(src._pair))(jnp.asarray(i), jnp.asarray(j))
        want = Xn[np.stack([i, j], 1)]
    elif case == "compact":
        idx = np.array([3, 17, 40, 99, 148, 150, 150])
        i, j = 4, 1
        got = pair(src.compact(idx), jnp.asarray(i), jnp.asarray(j))
        want = Xn[np.minimum(idx, 149)][[i, j]]
    else:
        i, j = {"i<j": (5, 120), "i>j": (149, 0), "i=j": (63, 63)}[case]
        got = pair(src, jnp.asarray(i), jnp.asarray(j))
        want = Xn[[i, j]]
    assert got.dtype == X.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pallas_compact_solves_as_fresh_source():
    """A compacted source (the shrinking scheduler's active set, pads
    clamped to the last row) holds the feature-major X of its own rows,
    so a blocked launch over it solves exactly as a source built over
    ``X[idx]`` does."""
    ds = make_dataset("heart", n_override=150)
    X = jnp.asarray(ds.X, jnp.float32)
    y = jnp.asarray(ds.y, jnp.float64)
    idx = jnp.asarray(np.r_[0:150:2, 150, 150])      # 75 rows and 2 pads
    blocks = dict(bm=128, bk=8)                      # blocked: reads Xt
    comp = PallasRBF(X, ds.gamma, **blocks).compact(idx)
    fresh = PallasRBF(X[idx], ds.gamma, **blocks)
    np.testing.assert_array_equal(np.asarray(comp.Xt), np.asarray(fresh.Xt))
    yc = y[idx]
    mask = jnp.arange(idx.shape[0]) < 75
    args = (yc, mask, ds.C, jnp.zeros(idx.shape[0]), -yc)
    rc = solve(comp, *args, wss="1", max_iter=300)
    rf = solve(fresh, *args, wss="1", max_iter=300)
    np.testing.assert_array_equal(np.asarray(rc.alpha), np.asarray(rf.alpha))
    np.testing.assert_array_equal(np.asarray(rc.f), np.asarray(rf.f))
    assert int(rc.n_iter) == int(rf.n_iter) > 0


def test_pallas_nbytes_is_data_not_matrix():
    """The cache budget must account X's bytes, in the two layouts the
    source holds, not n² kernel bytes."""
    from repro.kernels.smo_step import feature_major_shape
    from repro.svm.sources import KernelSpec
    ds = make_dataset("heart", n_override=150)
    X = jnp.asarray(ds.X)
    src = PallasRBF(X, ds.gamma)
    assert src.Xt.shape == feature_major_shape(*X.shape, X.dtype.itemsize)
    assert src.nbytes == X.nbytes + src.Xt.nbytes
    spec = KernelSpec(X, gamma=ds.gamma, kind="pallas_rbf", n=100)
    D, N = feature_major_shape(100, X.shape[1], X.dtype.itemsize)
    assert spec.nbytes == (100 * X.shape[1] + D * N) * X.dtype.itemsize
    assert spec.fused and spec.streams_rows
    mat = spec.materialize()
    assert isinstance(mat, PallasRBF) and mat.nbytes == spec.nbytes


def test_dense_f32_kernel_solves_in_f64():
    """The precision policy at the engine: an f32 K is read upcast, so the
    solve is bit-identical to one over the same values held in f64 — the
    state (alpha, f) is float64 either way, like LIBSVM's Qfloat K with
    double gradients."""
    ds, X, K, y = _setup(n=120)
    n = y.shape[0]
    mask = jnp.ones(n, bool).at[:20].set(False)
    K32 = K.astype(jnp.float32)
    lo = solve(DenseKernel(K32), y, mask, ds.C, jnp.zeros(n), -y)
    hi = solve(DenseKernel(K32.astype(jnp.float64)), y, mask, ds.C,
               jnp.zeros(n), -y)
    assert lo.alpha.dtype == lo.f.dtype == jnp.float64
    np.testing.assert_array_equal(np.asarray(lo.alpha), np.asarray(hi.alpha))
    np.testing.assert_array_equal(np.asarray(lo.f), np.asarray(hi.f))
    assert int(lo.n_iter) == int(hi.n_iter) and bool(lo.converged)


def test_run_cv_batched_pallas_backend():
    from repro.core.cv import run_cv, run_cv_batched
    ds = make_dataset("heart", n_override=120)
    rep = run_cv_batched(ds, k=4, source_backend="pallas_rbf")
    assert rep.method == "cold_pallas"
    assert all(f.converged for f in rep.folds)
    # same fixed points as the dense drivers up to tolerance: held-out
    # accuracy is identical, objectives agree to solver tolerance
    cold = run_cv(ds, k=4, method="cold")
    assert rep.accuracy == pytest.approx(cold.accuracy, abs=1e-12)
    for fp, fd in zip(rep.folds, cold.folds):
        assert fp.objective == pytest.approx(fd.objective, rel=1e-5)
    with pytest.raises(ValueError, match="source_backend"):
        run_cv_batched(ds, k=4, source_backend="pallas")


def test_grid_pallas_resident_is_n2_independent():
    """A budgeted grid over pallas sources: peak resident kernel bytes are
    X's bytes in the source's two layouts per gamma — independent of n² —
    and accuracy matches the dense cold grid."""
    from repro.core.grid import run_grid
    from repro.kernels.smo_step import feature_major_shape
    ds = make_dataset("heart", n_override=120)
    kw = dict(Cs=(0.5, 2.0), gammas=(0.5, 1.0), k=3, method="cold",
              max_resident=1)
    pal = run_grid(ds, source_backend="pallas_rbf", **kw)
    dense = run_grid(ds, **kw)
    n = pal.n
    itemsize = 4   # kernel operands are float32 (svm/precision.py)
    # X row-major and the kernel's padded feature-major copy of it
    D, N = feature_major_shape(n, ds.X.shape[1], itemsize)
    x_bytes = (n * ds.X.shape[1] + D * N) * itemsize
    assert pal.resident["peak_resident_bytes"] <= x_bytes
    assert pal.resident["peak_resident_bytes"] < n * n * itemsize
    assert dense.resident["peak_resident_bytes"] >= n * n * itemsize
    for cp, cd in zip(pal.cells, dense.cells):
        assert (cp.C, cp.gamma) == (cd.C, cd.gamma)
        assert cp.accuracy == pytest.approx(cd.accuracy, abs=1e-12)
    with pytest.raises(ValueError, match="cold"):
        run_grid(ds, Cs=(0.5,), gammas=(0.5,), k=3, method="sir",
                 source_backend="pallas_rbf")


# ------------------------------------------------------- NaN guards -------

def test_arg_reduces_nan_guard():
    """Regression: a NaN used to make ``v == min(v)`` all-False, so the
    reduce returned v.shape[0] — out of range — and jax's clamped gather
    silently aliased it to the last row."""
    from repro.svm.engine import _argmin, _argmax
    v = jnp.asarray([3.0, jnp.nan, 1.0, jnp.nan])
    assert int(_argmin(v)) == int(jnp.argmin(v)) == 1
    assert int(_argmax(v)) == int(jnp.argmax(v)) == 1
    clean = jnp.asarray([3.0, 1.0, 1.0, 7.0])
    assert int(_argmin(clean)) == int(jnp.argmin(clean)) == 1
    assert int(_argmax(clean)) == int(jnp.argmax(clean)) == 3
    # degenerate inputs stay in range
    assert int(_argmin(jnp.asarray([jnp.nan, jnp.nan]))) == 0
    assert int(_argmax(jnp.asarray([jnp.nan, jnp.nan]))) == 0
    assert int(_argmin(jnp.full(3, jnp.inf))) == 0
    assert int(_argmax(jnp.full(3, -jnp.inf))) == 0


def test_solver_halts_on_nan_state():
    """A NaN in f on an active row must stop the solve immediately with
    converged=False instead of spinning on a bogus pair until max_iter."""
    ds, X, K, y = _setup(n=64)
    n = y.shape[0]
    f0 = (-y).at[3].set(jnp.nan)
    res = smo_solve(K, y, jnp.ones(n, bool), ds.C, jnp.zeros(n), f0,
                    max_iter=50_000)
    assert not bool(res.converged)
    assert int(res.n_iter) == 0   # halted before any update was applied


@pytest.mark.parametrize("max_width", [1, 2, 4])
def test_run_cv_batched_matches_cold_cv(max_width):
    """The k-lane plan replays ``run_cv(method="cold")`` fold for fold at
    every dispatch width, up to all k folds in one batched program."""
    from repro.core.cv import run_cv, run_cv_batched
    ds = make_dataset("heart", n_override=120)
    cold = run_cv(ds, k=4, method="cold")
    bat = run_cv_batched(ds, k=4, max_width=max_width)
    assert bat.method == "cold_batched_repacked"
    assert bat.accuracy == pytest.approx(cold.accuracy, abs=1e-12)
    assert [f.n_iter for f in bat.folds] == [f.n_iter for f in cold.folds]
    assert all(f.converged for f in bat.folds)
    assert bat.occupancy["chunks"] >= 1
    assert bat.occupancy["peak_width"] == max_width


def test_solve_batched_n_iter0s_resume_bitwise():
    """A capped batch of pool lanes resumed with per-lane ``n_iter0``
    replays the uninterrupted iterate sequence — alpha, f AND the n_iter
    account — mirroring the single-lane ``solve(..., n_iter0=...)``
    path."""
    ds, X, K, y = _setup(n=120)
    n = y.shape[0]
    masks = jnp.stack([jnp.ones(n, bool).at[:20].set(False),
                       jnp.ones(n, bool).at[20:40].set(False)])
    Cs = jnp.asarray([ds.C, 4.0 * ds.C])
    a0 = jnp.zeros((2, n))
    f0 = jnp.tile(-y, (2, 1))
    src = DenseKernel(K)
    full = _pool_batch(src, y, masks, Cs, a0, f0)
    part = _pool_batch(src, y, masks, Cs, a0, f0, max_iter=150)
    np.testing.assert_array_equal(np.asarray(part.n_iter), [150, 150])
    resumed = _pool_batch(src, y, masks, Cs, part.alpha, part.f,
                          n_iter0s=part.n_iter)
    for a, b in zip(full, resumed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the cap counts TOTAL updates incl. the preload: resuming a 150-iter
    # state under max_iter=150 must apply zero further updates
    recapped = _pool_batch(src, y, masks, Cs, part.alpha, part.f,
                           n_iter0s=part.n_iter, max_iter=150)
    np.testing.assert_array_equal(np.asarray(recapped.alpha),
                                  np.asarray(part.alpha))
    np.testing.assert_array_equal(np.asarray(recapped.n_iter), [150, 150])
