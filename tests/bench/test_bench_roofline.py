"""The roofline's work counts at adult's shape and its table of peaks."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import roofline  # noqa: E402
import run  # noqa: E402

V5E = "TPU v5 lite"
N, D = 32560, 123


def test_fused_smo_step_at_adult_shape():
    flops, nbytes = roofline.fused_smo_step(N, D)
    assert flops == 4.0 * N * D            # two kernel rows, n x d MACs each
    assert nbytes == 4.0 * (N * D + N + 2 * D + 2 * N)
    assert nbytes / 1e6 == pytest.approx(16.41, abs=0.01)
    least, bound = roofline.least_time(flops, nbytes, V5E)
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9)
    assert 19e-6 < least < 21e-6


def test_peaks_table_names_v5e_and_refuses_unknown_kinds():
    assert roofline.peaks(V5E) == {"flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def _run(trace):
    cfg = {"rows": N, "features": D, "k": 10}
    return types.SimpleNamespace(
        cfg=cfg, device_kind=V5E, trace=trace,
        folds=[{"n_iter": 44000, "wall_s": 5.0}] * 3)


def test_kernel_roofline_reader():
    c = run.resolve("adult.cold_pallas")
    reader = c.readers["fused_smo_step_roofline"]
    least, _ = roofline.least_time(*roofline.fused_smo_step(N, D), V5E)
    trace = {"op_seconds": {"fused_smo_step.3": 2 * 100 * least,
                            "while.1": 1.0},
             "op_counts": {"fused_smo_step.3": 100, "while.1": 1}}
    assert reader.read(_run(trace)) == pytest.approx(50.0)
    assert reader.read(_run({"op_seconds": {"while.1": 1.0},
                             "op_counts": {"while.1": 1}})) is None
    assert reader.read(_run(None)) is None

