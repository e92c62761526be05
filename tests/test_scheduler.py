"""LanePool: per-lane bit-parity with sequential solves under forced
repack boundaries, mixed convergence orders, dependency admission, and
resume-from-mid-batch checkpoints (by original lane id)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cv import _fold_masks, _transition_idx
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import (DenseKernel, LanePool, init_f, kernel_matrix,
                       smo_solve)
from repro.svm.precision import kernel_input
from repro.svm.scheduler import bucket_width

SUITE = ("adult", "heart", "madelon", "mnist", "webdata")


def _setup(name, n=140, k=4):
    """Kernel and folds as the CV entry points build them: f32 kernel values
    (``kernel_input``), f64 labels and state."""
    ds = make_dataset(name, n_override=n)
    X = kernel_input(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    chunks = kfold_chunks(n, k, seed=0)
    nn = chunks.size
    return ds, K[:nn][:, :nn], y[:nn], chunks, jnp.asarray(_fold_masks(chunks))


def test_bucket_width_policy():
    assert [bucket_width(w, 4) for w in (1, 2, 3, 4, 5, 9, 16)] == \
        [1, 2, 4, 4, 8, 12, 16]
    assert bucket_width(3, 1) == 3          # quantum 1 = exact widths
    assert bucket_width(7, 8) == 8


@pytest.mark.parametrize("max_width", [0, 1, 3])
@pytest.mark.parametrize("name", SUITE)
def test_scheduler_parity_bitwise_all_suite(name, max_width):
    """Cold folds through the scheduler with tiny chunks (many forced
    repack boundaries) must be bit-identical to sequential solves on every
    suite dataset, for every schedule shape: unbounded vmapped packing
    (max_width=0, straggler tail degrading to the single-lane program),
    pure width-1 round-robin (the CPU cost-model default), and a capped
    width that parks/rotates lanes (max_width=3 over 4 lanes)."""
    ds, K, y, chunks, masks = _setup(name)
    n = y.shape[0]
    pool = LanePool({"K": DenseKernel(K)}, y, chunk_iters=64,
                    lane_quantum=2, max_width=max_width)
    for h in range(4):
        pool.add(h, masks[h], ds.C, jnp.zeros(n, K.dtype), -y)
    results = pool.run()
    for h in range(4):
        seq = smo_solve(K, y, masks[h], ds.C, jnp.zeros(n), -y)
        np.testing.assert_array_equal(np.asarray(seq.alpha),
                                      np.asarray(results[h].alpha))
        np.testing.assert_array_equal(np.asarray(seq.f),
                                      np.asarray(results[h].f))
        assert int(seq.n_iter) == int(results[h].n_iter)
        assert bool(results[h].converged) == bool(seq.converged)
    occ = pool.occupancy
    assert occ["chunks"] > 1
    if max_width == 0:
        assert occ["peak_width"] >= 4
        # repacking must actually shrink the batch as lanes retire
        assert occ["mean_live_width"] < 4
    else:
        # dispatched width caps at max_width rounded up to its pad bucket
        assert occ["peak_width"] <= bucket_width(max(max_width, 1), 2)


def test_scheduler_mixed_convergence_orders():
    """Heterogeneous lanes (spread C values, one warm-seeded lane) retire
    in scrambled order across many repack boundaries; every lane must still
    replay its sequential iterate sequence bit-exactly."""
    ds, K, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    Cs = [0.1 * ds.C, ds.C, 10.0 * ds.C, 100.0 * ds.C, ds.C]
    warm = smo_solve(K, y, masks[0], ds.C, jnp.zeros(n), -y)
    inits = [(jnp.zeros(n, K.dtype), -y)] * 4 + [(warm.alpha, warm.f)]
    lane_masks = [masks[h % 4] for h in range(4)] + [masks[0]]
    pool = LanePool({"K": DenseKernel(K)}, y, chunk_iters=32,
                    lane_quantum=2, max_width=0)
    for i, (C, (a0, f0), mask) in enumerate(zip(Cs, inits, lane_masks)):
        pool.add(i, mask, C, a0, f0)
    results = pool.run()
    orders = set()
    for i, (C, (a0, f0), mask) in enumerate(zip(Cs, inits, lane_masks)):
        seq = smo_solve(K, y, mask, C, a0, f0)
        np.testing.assert_array_equal(np.asarray(seq.alpha),
                                      np.asarray(results[i].alpha))
        assert int(seq.n_iter) == int(results[i].n_iter)
        orders.add(int(seq.n_iter))
    assert len(orders) >= 3, "test wants genuinely mixed convergence times"
    # the warm-seeded lane converges immediately and retires on chunk 1
    assert int(results[4].n_iter) == 0


def test_scheduler_admission_matches_cv_chain():
    """A fold chain expressed as lane dependencies (seed transform at
    admission) reproduces run_cv's per-fold trajectories bit-exactly."""
    from repro.core import seeding
    from repro.core.cv import run_cv
    ds = make_dataset("heart", n_override=140)
    rep = run_cv(ds, k=4, method="sir")
    _, K, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    pool = LanePool({"K": DenseKernel(K)}, y, chunk_iters=64, lane_quantum=2)
    pool.add(0, masks[0], ds.C, jnp.zeros(n, K.dtype), -y)
    for h in range(1, 4):
        S, R, T = _transition_idx(chunks, h - 1, h)

        def seed_fn(prev, C=ds.C, S=S, R=R, T=T):
            a0 = seeding.sir_seed(K, y, C, prev, S, R, T)
            return a0, init_f(K, y, a0)
        pool.add(h, masks[h], ds.C, dep=h - 1, seed_fn=seed_fn)
    results = pool.run()
    assert [int(results[h].n_iter) for h in range(4)] == \
        [f.n_iter for f in rep.folds]
    assert pool.seed_time > 0.0


def test_scheduler_snapshot_resume_bitwise():
    """Rebuild a pool from any mid-batch snapshot — retired lanes via
    add_result, live lanes via their (alpha, f, n_iter) keyed by original
    lane id — and finish with bit-identical results."""
    from repro.svm.engine import EngineState, finalize
    ds, K, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    snaps = []
    pool = LanePool({"K": DenseKernel(K)}, y, chunk_iters=64,
                    lane_quantum=2, max_width=0,
                    on_snapshot=lambda s: snaps.append(s.snapshot_lanes()))
    for h in range(4):
        pool.add(h, masks[h], ds.C, jnp.zeros(n, K.dtype), -y)
    full = pool.run()
    assert len(snaps) >= 3, "solve should span several chunks"
    mid = len(snaps) // 2
    ids, tree = snaps[mid]
    assert ids == [0, 1, 2, 3]
    # resume under a DIFFERENT schedule shape (width-1 round-robin): the
    # snapshot is keyed by lane id, so packing at crash time is irrelevant
    resumed = LanePool({"K": DenseKernel(K)}, y, chunk_iters=64,
                       lane_quantum=2, max_width=1)
    for i, h in enumerate(ids):
        if bool(tree["done"][i]):
            state = EngineState(tree["alpha"][i], tree["f"][i],
                                tree["n_iter"][i], jnp.ones((), bool))
            resumed.add_result(h, finalize(state, y, masks[h], ds.C, 1e-3))
        else:
            resumed.add(h, masks[h], ds.C, tree["alpha"][i], tree["f"][i],
                        n_iter0=int(tree["n_iter"][i]))
    res2 = resumed.run()
    for h in range(4):
        np.testing.assert_array_equal(np.asarray(full[h].alpha),
                                      np.asarray(res2[h].alpha))
        np.testing.assert_array_equal(np.asarray(full[h].f),
                                      np.asarray(res2[h].f))
        assert int(full[h].n_iter) == int(res2[h].n_iter)


def test_run_cv_batched_mid_batch_checkpoint_resume(tmp_path):
    """End-to-end: crash a repacked batched CV mid-flight; the restarted
    run restores every lane by fold id and lands on the identical report."""
    from repro.checkpoint import CheckpointManager
    from repro.core.cv import run_cv_batched
    ds = make_dataset("heart", n_override=120)
    full = run_cv_batched(ds, k=4, chunk_iters=64)

    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    run_cv_batched(ds, k=4, chunk_iters=64, checkpoint_manager=mgr)
    steps = mgr.steps_of_class("batch")
    assert len(steps) >= 3
    import shutil
    for s in steps[3:]:                      # 'crash' after the 3rd chunk
        shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    resumed = run_cv_batched(ds, k=4, chunk_iters=64,
                             checkpoint_manager=mgr2)
    assert [f.n_iter for f in resumed.folds] == \
        [f.n_iter for f in full.folds]
    assert resumed.accuracy == full.accuracy
    assert [f.converged for f in resumed.folds] == \
        [f.converged for f in full.folds]


def test_run_cv_batched_checkpoint_rejects_other_run(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.core.cv import run_cv_batched
    ds = make_dataset("heart", n_override=120)
    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    run_cv_batched(ds, k=4, chunk_iters=64, checkpoint_manager=mgr)
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    with pytest.raises(ValueError, match="cannot resume"):
        run_cv_batched(ds, k=5, chunk_iters=64, checkpoint_manager=mgr2)
    # a different tol is a different run: retired lanes carry fixed points
    # at the snapshot's tolerance, so mixing criteria must be rejected too
    mgr3 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    with pytest.raises(ValueError, match="cannot resume"):
        run_cv_batched(ds, k=4, chunk_iters=64, tol=1e-6,
                       checkpoint_manager=mgr3)


def test_scheduler_single_lane_degrades_to_sequential():
    """One lane never pays the batched program: every chunk dispatches the
    single-lane (width 1) path, bit-identical to engine.solve."""
    ds, K, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    pool = LanePool({"K": DenseKernel(K)}, y, chunk_iters=64)
    pool.add("only", masks[0], ds.C, jnp.zeros(n, K.dtype), -y)
    results = pool.run()
    assert pool.occupancy["peak_width"] == 1
    assert pool.occupancy["programs"] == 1
    seq = smo_solve(K, y, masks[0], ds.C, jnp.zeros(n), -y)
    np.testing.assert_array_equal(np.asarray(seq.alpha),
                                  np.asarray(results["only"].alpha))
    assert int(seq.n_iter) == int(results["only"].n_iter)


def test_scheduler_deadlock_detection():
    ds, K, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    pool = LanePool({"K": DenseKernel(K)}, y, chunk_iters=64)
    pool.add(0, masks[0], ds.C, jnp.zeros(n, K.dtype), -y)
    pool.add(1, masks[1], ds.C, dep="missing",
              seed_fn=lambda prev: (prev.alpha, prev.f))
    with pytest.raises(RuntimeError, match="never retire"):
        pool.run()


def test_scheduler_rejects_bad_lane_specs():
    ds, K, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    pool = LanePool({"K": DenseKernel(K)}, y)
    pool.add(0, masks[0], ds.C, jnp.zeros(n, K.dtype), -y)
    with pytest.raises(ValueError, match="duplicate"):
        pool.add(0, masks[0], ds.C, jnp.zeros(n, K.dtype), -y)
    with pytest.raises(ValueError, match="exactly one"):
        pool.add(1, masks[1], ds.C)
    with pytest.raises(ValueError, match="together"):
        pool.add(1, masks[1], ds.C, jnp.zeros(n, K.dtype))
    with pytest.raises(ValueError, match="seed_fn"):
        pool.add(2, masks[2], ds.C, dep=0)


def test_engine_state_lane_helpers():
    """stack/lane/gather/scatter round-trip: the packed-batch vocabulary."""
    from repro.svm.engine import EngineState
    states = [EngineState(jnp.full(3, float(i)), jnp.full(3, -float(i)),
                          jnp.asarray(i, jnp.int64), jnp.asarray(i % 2 == 0))
              for i in range(4)]
    packed = EngineState.stack(states)
    assert packed.alpha.shape == (4, 3)
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(packed.lane(i).alpha),
                                      np.asarray(states[i].alpha))
    sub = packed.gather(jnp.asarray([3, 1]))
    np.testing.assert_array_equal(np.asarray(sub.n_iter), [3, 1])
    back = packed.scatter(jnp.asarray([3, 1]), sub)
    for a, b in zip(back, packed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = packed.scatter(jnp.asarray([0]), packed.gather(jnp.asarray([2])))
    np.testing.assert_array_equal(np.asarray(moved.alpha[0]),
                                  np.asarray(packed.alpha[2]))


def test_run_cv_and_batched_share_checkpoint_directory(tmp_path):
    """Batch snapshots live above _BATCH_BASE: neither run kind clobbers or
    mis-restores the other's records in a shared directory."""
    from repro.checkpoint import CheckpointManager
    from repro.core.cv import _BATCH_BASE, run_cv, run_cv_batched
    ds = make_dataset("heart", n_override=120)
    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    run_cv(ds, k=4, method="sir", checkpoint_manager=mgr, chunk_iters=64)
    cv_steps = set(mgr.all_steps())
    assert all(s < _BATCH_BASE for s in cv_steps)
    run_cv_batched(ds, k=4, chunk_iters=64, checkpoint_manager=mgr)
    # every run_cv record survived the batch run's saves
    assert cv_steps <= set(mgr.all_steps())
    assert all(s >= _BATCH_BASE for s in mgr.steps_of_class("batch"))
    # both kinds resume cleanly from the shared directory
    full_cv = run_cv(ds, k=4, method="sir")
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    resumed = run_cv(ds, k=4, method="sir", checkpoint_manager=mgr2,
                     chunk_iters=64)
    assert resumed.total_iterations == full_cv.total_iterations
    full_bat = run_cv_batched(ds, k=4, chunk_iters=64)
    mgr3 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    rebat = run_cv_batched(ds, k=4, chunk_iters=64, checkpoint_manager=mgr3)
    assert [f.n_iter for f in rebat.folds] == \
        [f.n_iter for f in full_bat.folds]