"""One step of each traffic kind, on the CPU at a tiny size, through the
step code: the fold ring's order and seeding, and answers that the plain
reference accepts under the cell's limits."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import data  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

ROWS = 200
SPEC = run.load_json(ROOT / "BENCHMARK.json")
#: one cell per traffic mix that a cell uses
BY_TRAFFIC = {w["traffic"]: w["name"] for w in SPEC["workloads"]}


def test_every_traffic_file_is_used_and_names_a_step():
    files = {p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")}
    assert files == set(BY_TRAFFIC)
    for t in files:
        traffic = run.load_json(ROOT / "bench" / "traffic" / f"{t}.json")
        assert (ROOT / "bench" / "steps" / f"{traffic['step']}.py").is_file()


@pytest.mark.parametrize("traffic", sorted(BY_TRAFFIC))
def test_fold_ring_steps(traffic):
    c = run.resolve(BY_TRAFFIC[traffic])
    cfg = dict(c.cfg, published_rows=ROWS, rows=ROWS)
    X, y = data.make_dataset(cfg, seed=3, n=ROWS)
    chunks = data.kfold_chunks(ROWS, cfg["k"], seed=3)
    step = c.step.STEP(cfg, c.traffic, X, y, chunks)
    folds = step.setup()
    k = cfg["k"]
    cold = c.traffic["method"] == "cold"
    # set-up solves what compiles the window's programs, up to fold 1
    assert [f.fold for f in folds] == ([1] if cold else [0, 1])
    folds += [step() for _ in range(k)]
    assert [f.fold for f in folds] == [h % k for h in range(folds[0].fold, k + 2)]
    assert folds[0].seed_from == -1
    for prev, f in zip(folds, folds[1:]):
        assert f.seed_from == (-1 if cold else prev.fold)
        assert f.converged and f.n_iter > 0 and f.solve_s > 0
        assert (f.seed_s > 0) != cold
    masks = data.train_masks(chunks)
    answers = [dict(alpha=np.asarray(f.alpha), f=np.asarray(f.f), pred=f.pred,
                    objective=f.objective, train=masks[f.fold],
                    test=chunks[f.fold]) for f in folds]
    got = reference.check(X.astype(np.float32), y, cfg["C"], cfg["gamma"],
                          cfg["tol"], answers)
    assert all(got[k_] <= lim for k_, lim in c.limits.items()), got
    step.close()
