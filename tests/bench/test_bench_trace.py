"""Trace reduction: on synthetic events, and on a small trace recorded on
a TPU v5e (``record_trace.py``: adult.cold_pallas at 300 rows, each
step cut at 100 SMO iterations, a few steps traced)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import devtrace  # noqa: E402

FIXTURE = ROOT / "bench" / "testdata" / "fold_chain_small.xplane.pb.gz"


def test_op_name():
    assert devtrace.op_name("%while.28 = (f32[8]{0}, s32[]) while((f32[8]"
                            "{0}, s32[]) %tuple.1), condition=%c") == "while.28"
    assert devtrace.op_name("%fused_smo_step.8 = f32[600,2]{1,0} "
                            "custom-call(...)") == "fused_smo_step.8"
    assert devtrace.op_name("copy-done") == "copy-done"


def test_self_times_leave_nested_ops_out_of_their_parent():
    ops = [("while.1", 0, 100), ("a", 10, 30), ("b", 40, 50),
           ("c", 45, 48), ("d", 120, 130)]
    got = dict(devtrace.self_times(ops))
    assert got == {"while.1": 70, "a": 20, "b": 7, "c": 3, "d": 10}


def test_reduce_synthetic():
    device = {"/device:TPU:0": [("while.1", 100, 200), ("f.2", 120, 150),
                                ("g.3", 300, 350), ("early", 0, 50)]}
    spans = [("bench.step", 100, 400), ("bench.plan", 100, 260),
             ("bench.eval", 260, 400)]
    r = devtrace.reduce(device, spans)
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx(150e-9)
    assert r["idle_share"] == pytest.approx(0.5)
    assert "early" not in r["op_seconds"]
    assert r["op_seconds"]["while.1"] == pytest.approx(70e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.plan"] == pytest.approx(100e-9)
    assert gaps["bench.eval"] == pytest.approx(50e-9)
    assert devtrace.ops_matching(r, "f") == (pytest.approx(30e-9), 1)
    assert devtrace.ops_matching(r, "g") == (pytest.approx(50e-9), 1)


def test_reduce_refuses_a_trace_without_device_or_step():
    with pytest.raises(ValueError):
        devtrace.reduce({}, [("bench.step", 0, 1)])
    with pytest.raises(ValueError):
        devtrace.reduce({"/device:TPU:0": [("a", 0, 1)]}, [])


@pytest.fixture(scope="module")
def chip_trace():
    device, spans = devtrace.events(FIXTURE)
    return device, spans, devtrace.reduce(device, spans)


def test_chip_trace_planes_and_spans(chip_trace):
    device, spans, _ = chip_trace
    assert list(device) == ["/device:TPU:0"]
    names = {s[0] for s in spans}
    assert {"bench.step", "bench.plan", "bench.eval"} <= names
    assert all(" " not in name for name, _, _ in device["/device:TPU:0"])


def test_chip_trace_reduced(chip_trace):
    _, spans, r = chip_trace
    t0, t1 = devtrace.step_window(spans)
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    # self times add up to the busy time: nothing counted twice
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"])
    assert 1 <= len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert {g for g, _ in r["idle_gaps"]} <= {"bench.step", "bench.plan",
                                              "bench.eval", "outside"}


def test_chip_trace_finds_the_kernel(chip_trace):
    _, spans, r = chip_trace
    secs, calls = devtrace.ops_matching(r, "fused_smo_step")
    # two steps traced, each cut at 100 SMO iterations: 101 kernel calls
    # each (one per iteration, and the engine's last check)
    steps = sum(name == "bench.step" for name, _, _ in spans)
    assert steps == 2 and calls == 101 * steps and secs > 0
