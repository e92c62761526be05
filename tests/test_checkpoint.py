"""Checkpointing: roundtrip, crash consistency, retention, async, CV resume,
and elastic (mesh-changing) restore in a multi-device subprocess."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.int32),
                       "c": jnp.asarray(3.5)}}


def test_roundtrip(tmp_path):
    path = str(tmp_path / "ck")
    save_pytree(path, _tree(), {"step": 7})
    restored, extra = load_pytree(path, target=_tree())
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(_tree()), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoint_ignored(tmp_path):
    """A writer killed mid-save must never corrupt the latest checkpoint."""
    mgr = CheckpointManager(str(tmp_path), max_to_keep=5)
    mgr.save(1, _tree(), {"ok": True})
    # simulate a partial write: directory without COMMIT marker
    bad = os.path.join(str(tmp_path), "step_0000000002")
    os.makedirs(bad)
    with open(os.path.join(bad, "meta.json"), "w") as fh:
        json.dump({}, fh)
    assert mgr.latest_step() == 1
    step, tree, extra = mgr.restore()
    assert step == 1 and extra["ok"]


def test_retention_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    for s in range(5):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [3, 4]


def test_retention_classes_gc_independently(tmp_path):
    """max_to_keep applies PER retain_class: a stream of frequent "mid"
    snapshots must not evict the rare "done" records."""
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    for s in range(3):
        mgr.save(s, _tree(), retain_class="done")
    for s in range(10, 16):
        mgr.save(s, _tree(), retain_class="mid")
    assert mgr.all_steps() == [1, 2, 14, 15]
    # a fresh manager (post-crash) learns the classes back from meta.json
    mgr2 = CheckpointManager(str(tmp_path), max_to_keep=2)
    mgr2.save(16, _tree(), retain_class="mid")
    assert mgr2.all_steps() == [1, 2, 15, 16]


def test_cv_mid_snapshots_do_not_evict_done_records(tmp_path):
    """Default retention + chunked dispatch: fold 4's many chunk snapshots
    used to GC away every earlier fold's done record, making resumed
    reports permanently partial in exactly the configuration where
    checkpointing matters most."""
    from repro.core.cv import run_cv, _FOLD_STRIDE
    from repro.data.svm_suite import make_dataset
    ds = make_dataset("heart", n_override=100)
    mgr = CheckpointManager(str(tmp_path / "cv"))   # default max_to_keep=3
    run_cv(ds, k=5, method="sir", checkpoint_manager=mgr, chunk_iters=50)
    done = [s for s in mgr.all_steps() if s % _FOLD_STRIDE == 0]
    assert len(done) == 3   # the newest 3 done records survived the mids


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, _tree(), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 3


def test_cv_resume_matches_uninterrupted(tmp_path):
    """Kill the CV driver after fold 2; the restarted run must return the
    SAME report as an uninterrupted run — every retained done record is
    restored (not just the latest), so totals/accuracy agree and only folds
    3-4 are recomputed (the alpha chain doubles as the restart seed)."""
    from repro.core.cv import run_cv
    from repro.data.svm_suite import make_dataset
    ds = make_dataset("heart", n_override=100)
    full = run_cv(ds, k=5, method="sir")

    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=100)
    run_cv(ds, k=5, method="sir", checkpoint_manager=mgr)
    # emulate a crash after fold 2 by removing the last two fold checkpoints
    for s in mgr.all_steps()[-2:]:
        import shutil
        shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=100)
    resumed = run_cv(ds, k=5, method="sir", checkpoint_manager=mgr2)
    assert [f.fold for f in resumed.folds] == [0, 1, 2, 3, 4]
    assert [f.restored for f in resumed.folds] == [True] * 3 + [False] * 2
    assert not resumed.partial
    for f_full, f_res in zip(full.folds, resumed.folds):
        assert f_full.acc_correct == f_res.acc_correct
        assert f_full.n_iter == f_res.n_iter
        assert f_full.seed_from == f_res.seed_from
        assert f_full.converged == f_res.converged
    # the report-level aggregates no longer silently disagree
    assert resumed.total_iterations == full.total_iterations
    assert resumed.accuracy == full.accuracy


def test_cv_resume_partial_report_flagged(tmp_path):
    """When retention GC dropped the early done records, the resumed report
    cannot cover every fold — it must say so instead of passing off partial
    totals as a full run."""
    from repro.core.cv import run_cv
    from repro.data.svm_suite import make_dataset
    ds = make_dataset("heart", n_override=100)
    mgr = CheckpointManager(str(tmp_path / "cv"))   # default max_to_keep=3
    run_cv(ds, k=5, method="sir", checkpoint_manager=mgr)
    import shutil
    for s in mgr.all_steps()[-2:]:
        shutil.rmtree(mgr._step_dir(s))             # only fold 2 retained
    mgr2 = CheckpointManager(str(tmp_path / "cv"))
    resumed = run_cv(ds, k=5, method="sir", checkpoint_manager=mgr2)
    assert [f.fold for f in resumed.folds] == [2, 3, 4]
    assert resumed.folds[0].restored
    assert resumed.partial
    assert not run_cv(ds, k=5, method="sir").partial


def test_cv_resume_other_method_seeds_but_stays_out_of_report(tmp_path):
    """A done record from a different method is a legitimate seed (the
    fixed point is method-independent) but its n_iter is that method's
    trajectory: it must NOT be republished as this report's per-method
    iteration count (the paper's headline metric)."""
    from repro.core.cv import run_cv
    from repro.data.svm_suite import make_dataset
    ds = make_dataset("heart", n_override=100)
    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=100)
    run_cv(ds, k=5, method="cold", checkpoint_manager=mgr)
    import shutil
    for s in mgr.all_steps()[-2:]:
        shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=100)
    resumed = run_cv(ds, k=5, method="sir", checkpoint_manager=mgr2)
    # folds 3-4 are recomputed under sir (fold 3 seeded from cold's fold-2
    # fixed point — that part is sound); cold's folds 0-2 seed the chain
    # but stay out of the sir-labelled report, which says so via partial
    assert [f.fold for f in resumed.folds] == [3, 4]
    assert not any(f.restored for f in resumed.folds)
    assert resumed.folds[0].seed_from == 2
    assert resumed.partial


def test_cv_resume_unchunked_run_with_chunking(tmp_path):
    """Regression: done records use the strided numbering unconditionally,
    so a run checkpointed WITHOUT chunk_iters resumes correctly WITH it.
    (Unchunked runs used to save fold h at step h while the restore path
    assumed (h+1)*_FOLD_STRIDE, leaving mid-snapshot provenance pointing at
    nonexistent steps and silently degrading strict seeding to cold.)"""
    from repro.core.cv import run_cv, _FOLD_STRIDE
    from repro.data.svm_suite import make_dataset
    ds = make_dataset("heart", n_override=100)
    full = run_cv(ds, k=5, method="sir", chunk_iters=50)

    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=100)
    run_cv(ds, k=5, method="sir", checkpoint_manager=mgr)   # unchunked
    assert all(s % _FOLD_STRIDE == 0 for s in mgr.all_steps())
    import shutil
    for s in mgr.all_steps()[-2:]:
        shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=100)
    resumed = run_cv(ds, k=5, method="sir", checkpoint_manager=mgr2,
                     chunk_iters=50)
    assert [f.fold for f in resumed.folds] == [0, 1, 2, 3, 4]
    # strict seeding provenance survives the chunking-mode change
    assert resumed.folds[3].seed_from == 2
    assert resumed.folds[4].seed_from == 3
    for f_full, f_res in zip(full.folds, resumed.folds):
        assert f_full.acc_correct == f_res.acc_correct
        assert f_full.n_iter == f_res.n_iter
    assert resumed.total_iterations == full.total_iterations


def test_cv_mid_fold_resume(tmp_path):
    """Chunked dispatch checkpoints INSIDE a fold: crash after a few chunks
    of fold 2 and the restarted run resumes that fold's iterate sequence
    (same n_iter account, same accuracy) instead of replaying it."""
    from repro.core.cv import run_cv, _FOLD_STRIDE
    from repro.data.svm_suite import make_dataset
    ds = make_dataset("heart", n_override=100)
    full = run_cv(ds, k=5, method="sir")

    mgr = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    chunked = run_cv(ds, k=5, method="sir", checkpoint_manager=mgr,
                     chunk_iters=50)
    # chunking must not change results at all
    for f_full, f_ch in zip(full.folds, chunked.folds):
        assert f_full.n_iter == f_ch.n_iter
        assert f_full.acc_correct == f_ch.acc_correct
    # 'crash' mid fold 2: drop everything after its second chunk snapshot
    mids = [s for s in mgr.all_steps() if s % _FOLD_STRIDE != 0
            and s // _FOLD_STRIDE == 2]
    assert len(mids) >= 2, "fold 2 should span several 50-iter chunks"
    import shutil
    for s in mgr.all_steps():
        if s > mids[1]:
            shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "cv"), max_to_keep=1000)
    resumed = run_cv(ds, k=5, method="sir", checkpoint_manager=mgr2,
                     chunk_iters=50)
    # folds 0-1 come back from their done records; fold 2 resumes mid-flight
    assert [f.fold for f in resumed.folds] == [0, 1, 2, 3, 4]
    assert [f.restored for f in resumed.folds] == [True, True] + [False] * 3
    for f_full, f_res in zip(full.folds, resumed.folds):
        assert f_full.n_iter == f_res.n_iter
        assert f_full.acc_correct == f_res.acc_correct
    # the resumed fold still records its original seed provenance
    assert resumed.folds[2].seed_from == 1
    assert resumed.accuracy == full.accuracy


ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import warnings; warnings.filterwarnings("ignore")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
    from repro.checkpoint import CheckpointManager

    d = os.environ["CKPT_DIR"]
    mgr = CheckpointManager(d)
    mesh = jax.make_mesh((MESHA, MESHB), ("data", "model"),
                         axis_types=(AxisType.Auto,)*2)
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("data", "model")))
    tree = {"w": x}
    if os.environ["MODE"] == "save":
        mgr.save(1, tree, {"mesh": [MESHA, MESHB]})
    else:
        step, restored, extra = mgr.restore(target=tree)
        assert extra["mesh"] != [MESHA, MESHB]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(jnp.arange(64.0).reshape(8, 8)))
        assert restored["w"].sharding.mesh.shape["data"] == MESHA
    print("OK")
""")


@pytest.mark.parametrize("save_mesh,restore_mesh", [((4, 2), (2, 4)),
                                                    ((8, 1), (2, 4))])
def test_elastic_restore_across_meshes(tmp_path, save_mesh, restore_mesh):
    """Save on one mesh, restore onto a different one (elastic scaling)."""
    env = dict(os.environ, CKPT_DIR=str(tmp_path / "el"),
               PYTHONPATH="src")
    for mode, mesh in (("save", save_mesh), ("restore", restore_mesh)):
        script = ELASTIC_SCRIPT.replace("MESHA", str(mesh[0])) \
                               .replace("MESHB", str(mesh[1]))
        env["MODE"] = mode
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, cwd=os.getcwd(),
                             timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout
