"""End-to-end driver for the paper's full experimental protocol on one
dataset: the SVC estimator facade, all four seeding methods, the pooled
cross-gamma grid, and a fault-tolerant restart demo — every path a thin
plan over the Study API.

    PYTHONPATH=src python examples/svm_cv_seeding.py [dataset]

Study-service mode (DESIGN.md §Study service): start a daemon in one
terminal, then point any number of clients at it — each client's study
runs bit-identically to the in-process path, sharing the daemon's pool
(and deduping identical kernels across clients):

    PYTHONPATH=src python examples/svm_cv_seeding.py --serve /tmp/study.sock
    PYTHONPATH=src python examples/svm_cv_seeding.py \\
        --connect /tmp/study.sock [dataset]
"""
import shutil
import sys
import tempfile

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.core.cv import run_cv
from repro.data.svm_suite import make_dataset
from repro.svm import SVC

enable_compile_cache()


def _serve(sock_path: str) -> None:
    """Run the study daemon until Ctrl-C (drains gracefully)."""
    from repro.service import StudyServer, StudyService
    service = StudyService(chunk_iters=512,
                           checkpoint_root=tempfile.mkdtemp())
    print(f"study daemon on {sock_path} "
          f"(tol={service.pool.tol}, wss={service.pool.wss}) — Ctrl-C drains")
    StudyServer(sock_path, service).serve_forever()


def _connect(sock_path: str, name: str) -> None:
    """Submit this example's fold-chain study to a running daemon and
    compare against the local run — same bits, shared pool."""
    import getpass

    import jax.numpy as jnp

    from repro.core.cv import _fold_masks, _transition_idx
    from repro.core.study import Plan, run_plan
    from repro.data.svm_suite import kfold_chunks
    from repro.service import StudyClient
    from repro.svm.sources import KernelSpec

    ds = make_dataset(name, n_override=600)
    chunks = kfold_chunks(ds.n, 5, seed=0)
    nn = chunks.size
    X = jnp.asarray(ds.X)[:nn]
    y = jnp.asarray(ds.y, jnp.float64)[:nn]
    masks = jnp.asarray(_fold_masks(chunks))
    plan = Plan(sources={"k": KernelSpec(X=X, gamma=ds.gamma, n=nn)}, y=y,
                chunk_iters=512)
    plan.lane(0, train_mask=masks[0], C=ds.C,
              alpha0=jnp.zeros(nn), f0=-y)
    for h in range(1, 5):
        S, R, T = _transition_idx(chunks, h - 1, h)
        plan.lane(h, train_mask=masks[h], C=ds.C, dep=h - 1,
                  transform="fold",
                  params=dict(method="sir", S_idx=S, R_idx=R, T_idx=T))
    for h in range(5):
        plan.evaluate(h, chunks[h])

    with StudyClient(sock_path, tenant=getpass.getuser()) as cli:
        print(f"connected; daemon pool contract: {cli.pool_contract}")
        served = cli.submit(f"cv-{name}", plan,
                            on_result=lambda lid, r: print(
                                f"  fold {lid}: {int(r.n_iter)} iters"))
    local = run_plan(plan)
    same = all(bool((served.results[l].alpha == local.results[l].alpha).all())
               for l in local.results)
    acc = sum(c for c, _ in served.evals.values()) / \
        sum(t for _, t in served.evals.values())
    print(f"served 5-fold CV acc={acc:.4f}; bit-identical to local "
          f"run_plan: {same}; dedup_hits={served.dedup_hits} "
          f"(submit again from another terminal to see kernel dedup)")


if "--serve" in sys.argv:
    _serve(sys.argv[sys.argv.index("--serve") + 1])
    sys.exit(0)
if "--connect" in sys.argv:
    _i = sys.argv.index("--connect")
    _rest = [a for a in sys.argv[_i + 2:] if not a.startswith("-")]
    _connect(sys.argv[_i + 1], _rest[0] if _rest else "madelon")
    sys.exit(0)

name = sys.argv[1] if len(sys.argv) > 1 else "madelon"
ds = make_dataset(name, n_override=600)

# ---- the estimator facade: fit / predict / cross_validate ----
svc = SVC(C=ds.C, gamma=ds.gamma)
svc.fit(ds.X, ds.y)
print(f"== {ds.name}: n={ds.n}, C={ds.C}, gamma={ds.gamma}, k=10 ==")
print(f"SVC fit: {svc.n_iter_} iterations, converged={svc.converged_}, "
      f"train acc={svc.score(ds.X, ds.y):.4f}")

for method in ("cold", "ato", "mir", "sir"):
    rep = svc.cross_validate(ds.X, ds.y, k=10, method=method)
    r = rep.row()
    print(f"{method:>5}: iters={r['iterations']:>7} init={r['init_s']:>8}s "
          f"solve={r['solve_s']:>8}s acc={r['accuracy']}")
    if method == "sir":
        per_fold = [(f.fold, f.seed_from, f.n_iter) for f in rep.folds]
        print("       per-fold (fold, seeded_from, iters):", per_fold)

# ---- lane-scheduled fold execution: independent cold folds submitted to
# the lane pool (repacked/bucketed/width-capped dispatch) ----
from repro.core.cv import run_cv_batched  # noqa: E402

rep_cold = run_cv(ds, k=10, method="cold")
rep_bat = run_cv_batched(ds, k=10)
print(f"\ncold sequential: {rep_cold.row()['total_s']}s; "
      f"cold lane-scheduled: {rep_bat.row()['total_s']}s "
      f"(same per-fold fixed points; occupancy {rep_bat.occupancy})")

# ---- hyper-parameter grid: ONE multi-source pool across gammas — kernel
# reuse per gamma, C-adjacent alpha seeding, no per-row barrier ----
from repro.core.grid import run_grid  # noqa: E402

grid = run_grid(ds, Cs=[ds.C / 4, ds.C, ds.C * 4],
                gammas=[ds.gamma / 2, ds.gamma],
                k=5, method="sir", seed_across_C=True)
best = grid.best()
occ = grid.occupancy or {}
print(f"grid best cell: C={best.C} gamma={best.gamma} "
      f"acc={best.accuracy:.4f} ({grid.total_iterations} total iters; "
      f"per-gamma live widths {occ.get('per_source')})")

# ---- fault tolerance: the alpha chain doubles as the restart seed ----
tmp = tempfile.mkdtemp()
try:
    mgr = CheckpointManager(tmp)
    run_cv(ds, k=10, method="sir", checkpoint_manager=mgr)
    # simulate losing the node after fold 7: drop the last 2 checkpoints
    for s in mgr.all_steps()[-2:]:
        shutil.rmtree(mgr._step_dir(s))
    resumed = run_cv(ds, k=10, method="sir",
                     checkpoint_manager=CheckpointManager(tmp))
    redone = [f.fold for f in resumed.folds if not f.restored]
    kept = [f.fold for f in resumed.folds if f.restored]
    print(f"\nrestart after failure: recomputed folds {redone} only "
          f"(folds {kept} restored from checkpoint; report "
          f"{'partial' if resumed.partial else 'complete'})")
finally:
    shutil.rmtree(tmp, ignore_errors=True)
