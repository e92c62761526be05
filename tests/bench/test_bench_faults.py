"""A run of each cell, driven on the CPU at a tiny size past the harness's
look for a chip: sound, it comes out correct; with the timed path broken
underneath, it comes out not correct. And without a chip, or outside a
checkout that holds the program, the command prints no result."""
import json
import pathlib
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import run  # noqa: E402

#: a size at which no fold's training labels balance: on webdata's
#: stand-in (K = I) the bias is their mean, and a bias of 0 would make
#: every held-out prediction a tie
ROWS, SECONDS = 310, 0.3
CELLS = [w["name"] for w in run.load_json(ROOT / "BENCHMARK.json")["workloads"]]


def _altered(res, plan):
    """The fold's answer altered where it is produced: one training row's
    alpha moved by a tenth."""
    i = int(jnp.argmax(res.alpha))
    return res._replace(alpha=res.alpha.at[i].add(-0.1))


def _unchanged(res, plan):
    """A step that returns its state unchanged: the fold's lane left as it
    started, alpha = 0 and f = -y."""
    return res._replace(alpha=jnp.zeros_like(res.alpha), f=-plan.y)


FAULTS = {"answer_altered": _altered, "state_unchanged": _unchanged}


def _cell(name, monkeypatch, fault=None, half_rows=False, wrong_fold=False):
    c = run.resolve(name)
    if wrong_fold:
        base = c.step.STEP

        class WrongFold(base):
            """Each fold's held-out predictions taken for the next fold's
            rows."""
            def evaluate(self, h, res):
                return super().evaluate((h + 1) % self.k, res)

        monkeypatch.setattr(c.step, "STEP", WrongFold)
    if fault is not None:
        real = c.step.run_plan

        def broken(plan, **kw):
            out = real(plan, **kw)
            last = plan.lanes[-1].id
            out.results[last] = fault(out.results[last], plan)
            return out

        monkeypatch.setattr(c.step, "run_plan", broken)
    if half_rows:
        base = c.step.STEP

        class HalfRows(base):
            """Each fold trained on half its training rows, the other half
            left out."""
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                m = np.asarray(self.masks).copy()
                for row in m:
                    on = np.flatnonzero(row)
                    row[on[::2]] = False
                self.masks = jnp.asarray(m)

        monkeypatch.setattr(c.step, "STEP", HalfRows)
    return c


def _result(c):
    return run.run_cell(c, 2**31 + 99, SECONDS, False, rows=ROWS,
                        t_start=time.monotonic())["result"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    out = _result(_cell(cell, monkeypatch))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"fold_s", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    out = _result(_cell(cell, monkeypatch, fault=FAULTS[fault]))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_rows_left_out_is_not_correct(cell, monkeypatch):
    out = _result(_cell(cell, monkeypatch, half_rows=True))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_predictions_of_the_wrong_fold_are_not_correct(cell, monkeypatch):
    """A fault in the held-out evaluation alone: alpha, f and the
    objective stay sound, and ``pred_mismatch`` catches it."""
    out = _result(_cell(cell, monkeypatch, wrong_fold=True))
    assert not out["correct"], out["checks"]
    checks = out["checks"]
    assert all(v["value"] <= v["limit"] for k, v in checks.items()
               if k != "pred_mismatch"), checks


def _command(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "adult.cold",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=120, env=env)


def test_no_result_without_a_chip():
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _command(ROOT, env=env)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_no_result_outside_a_checkout(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())
