"""BENCHMARK.json and the files it names: everything resolves by name, and
names, units and limits keep to the benchmark's rules."""
import json
import math
import pathlib
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TEXT_KEYS = ("why", "layer", "source")


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_full_check_fits_its_time():
    """2 + 14 x 24 runs of run_seconds + 60 s, 2 x 90 s of compile per
    cell and 1200 s spare fit in 43200 s, with the full 24 cells."""
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_text():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            for key in TEXT_KEYS:
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
                    assert "\t" not in e[key]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in SPEC["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_configs_files_and_reductions():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or k == "features"
                       for k in c["reduced"])
        assert cfg["rows"] == cfg["k"] * (cfg["published_rows"] // cfg["k"])


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = run.resolve(cell)
    assert callable(c.step.STEP)
    assert c.cfg["name"] == c.cell["config"]
    assert set(c.limits) and all(v > 0 for v in c.limits.values())
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.readers[m["name"]].read)


def test_every_metric_is_reported_somewhere_with_what_it_moves():
    e2e_of = {w: {m["name"] for m in run.cell_metrics(SPEC, w, "end_to_end")}
              for w in CELLS}
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        cells = m.get("workloads", CELLS)
        assert cells and set(cells) <= set(CELLS)
        for w in cells:
            assert m["moves"] in e2e_of[w], (m["name"], w)
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["end_to_end"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_layers_named_alike():
    """Metrics of one layer name it letter for letter as PERF.md's list of
    layers does."""
    perf = (ROOT / "PERF.md").read_text()
    for m in SPEC["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def _tree(root: pathlib.Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_and_metric_need_no_edit(tmp_path):
    """A later configuration (of a dataset that no file of the benchmark
    names), cell, traffic mix and per-layer metric are new files and new
    entries: the harness finds them, and makes the new configuration's
    data, with no file that exists edited."""
    import data
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = _tree(root / "bench")
    spec = json.loads(json.dumps(SPEC))
    new = {
        "configs/covtype_mini.json": {
            "name": "covtype_mini", "dataset": "covtype_mini",
            "published_rows": 300, "rows": 300, "features": 54,
            "generator": {"n_informative": 10, "separation": 1.3,
                          "flip": 0.1, "balanced": False},
            "C": 10.0, "gamma": 0.125, "k": 10, "tol": 1e-3,
            "max_iter": 100_000, "reduced": {}, "data_seed": 0},
        "traffic/ato_probe.json": {
            "step": "fold_chain", "method": "ato", "source": "dense",
            "wss": "2", "chunk_iters": None},
        "limits/covtype_mini.ato_probe.json": {"obj_rel": 1e-5},
    }
    for rel, body in new.items():
        assert not (root / "bench" / rel).exists(), rel
        (root / "bench" / rel).write_text(json.dumps(body))
    (root / "bench" / "metrics" / "fold_count.py").write_text(
        "def read(run):\n    return float(len(run.folds))\n")
    spec["configs"].append({"name": "covtype_mini", "source": "s",
                            "file": "bench/configs/covtype_mini.json",
                            "reduced": [], "why": "w"})
    spec["workloads"].append({"name": "covtype_mini.ato_probe",
                              "config": "covtype_mini",
                              "traffic": "ato_probe", "chips": 1, "why": "w"})
    spec["per_layer"].append({"name": "fold_count", "unit": "folds",
                              "better": "higher", "source": "host_clock",
                              "layer": "plan and pool", "moves": "fold_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = run.resolve("covtype_mini.ato_probe", bench=root / "bench")
    assert c.traffic["method"] == "ato" and c.cfg["features"] == 54
    assert "fold_count" in c.readers
    assert c.readers["fold_count"].read(type("R", (), {"folds": [1, 2]})) == 2.0
    X, y, chunks = data.cell_inputs(c.cfg, 2**31 + 5)
    assert X.shape == (300, 54) and y.shape == (300,)
    assert chunks.shape == (10, 30) and np.abs(X).max() <= 1.0
    again = data.cell_inputs(c.cfg, 2**31 + 5)
    assert all(np.array_equal(a, b) for a, b in zip(again, (X, y, chunks)))
    # an old cell now reports the new metric too, since it lists no cells
    old = run.resolve("adult.cold", bench=root / "bench")
    assert "fold_count" in {m["name"] for m in old.per_layer}
    after = _tree(root / "bench")
    assert {k: after[k] for k in before} == before


def test_setup_bound_and_metric_bounds_are_finite():
    for m in SPEC["end_to_end"]:
        assert math.isfinite(m["bound"])
    assert {m["name"]: m["bound"] for m in SPEC["end_to_end"]}["setup_s"] <= 0.25
