"""Record the small device trace that ``test_bench_trace.py`` reduces.

    python3 tests/bench/record_trace.py OUT_DIR [SECONDS]

Run on one TPU chip from the checkout's root: it solves two folds of the
``adult.cold_pallas`` fold chain at 300 rows, each cut at 100 SMO
iterations, then profiles further steps, each in a ``bench.step`` span,
for SECONDS (default 0.05), as a traced run does, and copies the trace
file to
``OUT_DIR/fold_chain_small.xplane.pb``; commit it gzipped, as
``bench/testdata/fold_chain_small.xplane.pb.gz``.
"""
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import data  # noqa: E402
import run  # noqa: E402

ROWS, MAX_ITER = 300, 100


def main(out_dir: str, seconds: float = 0.05) -> None:
    run.enable_compile_cache()
    c = run.resolve("adult.cold_pallas")
    cfg = dict(c.cfg, published_rows=ROWS, rows=ROWS, max_iter=MAX_ITER)
    X, y = data.make_dataset(cfg, seed=7, n=ROWS)
    chunks = data.kfold_chunks(ROWS, cfg["k"], seed=7)
    step = c.step.STEP(cfg, c.traffic, X, y, chunks)
    step.setup()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        path = run.record_trace(step, tmp, seconds)
        out = pathlib.Path(out_dir) / "fold_chain_small.xplane.pb"
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(path, out)
        print(out, out.stat().st_size)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1], *map(float, sys.argv[2:3]))
