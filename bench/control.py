"""The readings that the correctness check's limits are set from.

    python3 bench/control.py --workload adult.cold --seeds 11 12 13
    python3 bench/control.py --workload webdata.sir --seeds 1 2 3 4 \
        --program-seconds 5 --control 0

With ``--program-seconds S``, each seed first gets a run of the cell
(``run.run_cell``: set-up, a window of S seconds, the check of every
fold of the window), all in this one process, so that only the first
seed compiles; its numbers are the program's readings. Then, for the
first ``--control`` seeds (all by default), the cell's data and fold
partition are made as a run makes them, and for a few folds drawn from
the seed the plain reference solves the fold from zero in the program's
place:

* ``sound``: over the reference's float32 kernel, in float64 state, as the
  configuration states it;
* ``control``: over ``reference.rbf_high``, the kernel with its matmul
  one step of precision below the configuration's (``high``, three
  bfloat16 passes, for float32 at ``highest``);
* ``wrong_fold``: the sound solve, its held-out predictions taken for the
  next fold's rows: a fault in the evaluation, which the compared count
  ``pred_mismatch`` has to catch.

Each solve runs over the dense n x n kernel (``reference.kernel``,
``reference.smo``) where that fits the chip beside the solve
(``fits_dense``), and otherwise with the pair's kernel rows computed from
X in every iteration (``reference.smo_rows``), the held-out decision
values from blocks of rows: the same solve, for a configuration whose K
one chip cannot hold.

Each answer is judged by ``reference.check`` as a run judges the
program's, and its numbers go through ``run.judge`` with the cell's limits
(``bench/limits``), the comparison that decides a run's ``correct``: one
JSON line per seed gives the numbers of each reading and its ``correct``.
The check's limit of each number lies above the program's readings and
below the smallest that the control (or, for ``pred_mismatch``, the
fault) gives.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: folds per seed, drawn from the seed
FOLDS = 2
#: device bytes the dense solve needs beside its n x n float32 kernel:
#: the block of rows ``reference.kernel`` writes (at most 2,048 rows of
#: n) with its intermediates, X, and the solve's vectors. On a v5e (XLA
#: may use 16,909,336,064 bytes) up to 62,919 rows take the dense solve,
#: mnist's 60,000 (K 14.4 GB) among them
HEADROOM = 1 << 30
#: kernel of each solve -> the readings taken from it: (name, how many
#: folds on from the solved one lie the rows it predicts)
READINGS = {"rbf": (("sound", 0), ("wrong_fold", 1)),
            "rbf_high": (("control", 0),)}


def memory_limit():
    """The bytes the device says XLA may use, or None where it states no
    limit (the CPU, in tests)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def fits_dense(n: int, limit) -> bool:
    """Whether the dense n x n float32 kernel fits a device that lets XLA
    use ``limit`` bytes, beside its solve: 4 n^2 bytes and ``HEADROOM``.
    With no limit stated, the dense solve is taken."""
    return limit is None or 4 * n * n + HEADROOM <= limit


def answers(cfg, y, chunks, folds, solve, evaluate, max_iter, shifts=(0,)):
    """What the reference solver answers for ``folds``, in the form
    ``reference.check`` takes: one list of answers for each shift in
    ``shifts``; fold h's held-out predictions are taken for the rows of
    fold h + shift. ``solve`` and ``evaluate`` are ``reference.smo`` and
    ``reference.evaluate`` (or ``smo_rows`` and ``evaluate_rows``) with
    their kernel bound."""
    import jax.numpy as jnp
    import numpy as np

    import data

    masks = data.train_masks(chunks)
    k = chunks.shape[0]
    out = [[] for _ in shifts]
    for h in folds:
        train = jnp.asarray(masks[h])
        alpha, f, it = solve(y, train, cfg["C"], cfg["tol"], max_iter)
        for got, shift in zip(out, shifts):
            rows = jnp.asarray(chunks[(h + shift) % k])
            pred, obj = evaluate(rows, y, alpha, f, train, cfg["C"])
            got.append(dict(alpha=np.asarray(alpha), f=np.asarray(f),
                            pred=np.asarray(pred), objective=float(obj),
                            train=masks[h], test=chunks[h], n_iter=int(it)))
    return out


def readings(cfg, seed: int, max_iter: int) -> dict:
    """The numbers ``reference.check`` reads for each of ``READINGS`` on
    the folds drawn from ``seed``."""
    import numpy as np

    import data
    import reference

    X, y, chunks = data.cell_inputs(cfg, seed)
    n = chunks.size
    X32, y = X[:n].astype(np.float32), y[:n]
    folds = [int(h) for h in np.random.default_rng(seed).choice(
        cfg["k"], FOLDS, replace=False)]
    dense = fits_dense(n, memory_limit())
    out = {"seed": seed, "folds": folds, "solve": "dense" if dense else "rows"}
    for fn, names in READINGS.items():
        t0 = time.monotonic()
        shifts = [shift for _, shift in names]
        kern = getattr(reference, fn)
        if dense:
            K = reference.kernel(X32, cfg["gamma"], kern)
            got = answers(cfg, y, chunks, folds,
                          functools.partial(reference.smo, K),
                          functools.partial(reference.evaluate, K),
                          max_iter, shifts)
            # free the n x n kernel now: the next one does not fit beside it
            K.delete()
            del K
        else:
            got = answers(cfg, y, chunks, folds,
                          functools.partial(reference.smo_rows, X32,
                                            cfg["gamma"], kern),
                          functools.partial(reference.evaluate_rows, X32,
                                            cfg["gamma"], kern),
                          max_iter, shifts)
        for (name, _), ans in zip(names, got):
            out[name] = reference.check(X32, y, cfg["C"], cfg["gamma"],
                                        cfg["tol"], ans)
            out[name]["n_iter"] = [a["n_iter"] for a in ans]
            out[name]["seconds"] = time.monotonic() - t0
    return out


def program_readings(c, seed: int, seconds: float) -> dict:
    """One run of cell ``c`` (no trace) and the numbers its check read."""
    import run
    out = run.run_cell(c, seed, seconds, False, t_start=time.monotonic())
    info = out["info"]
    return {"seed": seed, "program": info["numbers"],
            "correct": out["result"]["correct"],
            "n_iter": [f["n_iter"] for f in info["folds"]],
            "fold_s": out["result"]["metrics"]["fold_s"]["value"],
            "setup_s": info["setup_s"], "reference_s": info["reference_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-seconds", type=float, default=0.0)
    ap.add_argument("--control", type=int, default=None,
                    help="seeds (from the first) the reference solves")
    ap.add_argument("--max-iter", type=int, default=400_000)
    args = ap.parse_args(argv)
    import run
    sys.path.insert(0, str(run.ROOT / "src"))
    c = run.resolve(args.workload)
    import jax
    jax.config.update("jax_enable_x64", True)
    run.enable_compile_cache()
    if run.require_chips(c.cell["chips"]) is None:
        return 3
    if args.program_seconds > 0:
        for seed in args.seeds:
            print(json.dumps(program_readings(c, seed, args.program_seconds)),
                  flush=True)
    for seed in args.seeds[:args.control]:
        got = readings(c.cfg, seed, args.max_iter)
        for name, _ in sum(READINGS.values(), ()):
            got[name]["correct"] = run.judge(got[name], c.limits)[1]
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
