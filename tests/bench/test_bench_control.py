"""The correctness check's control and its evaluation fault, at a size a
test run holds (2,000 rows; the readings at the cells' own sizes, on the
chip, are in PERF.md), judged by ``run.judge``, the comparison that
decides a run's ``correct``: the plain reference solving in the program's
place at the configuration's precision is correct under each cell's
limits; solving over the kernel one step of precision below (matmul at
``high``) reads ten times the sound solve or more on a compared number;
and the sound solve's predictions taken for the next fold's rows are not
correct. The control's errors grow with the rows, so at this size
they stay under the limits set at the cells' sizes."""
import functools
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import control  # noqa: E402
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
