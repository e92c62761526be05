#!/usr/bin/env python
"""Measure the lane scheduler's dispatch-width cost model.

The pool's ``max_width`` verdict ("how many lanes may share one vmapped
chunk program before per-lane cost degrades") used to be hard-coded in
``svm/scheduler.py``. This harness measures it with the pool itself: for
each source kind (dense matrix vs row-streaming pallas) it runs a
``LanePool`` of heterogeneous lanes (spread C values, distinct fold-like
masks — so convergence is staggered, exactly the workload the scheduler
repacks for) at each forced ``max_width`` and divides wall-clock by the
total *useful* iterations ``sum_h n_iter_h``. That metric charges the
batched program for its real overheads — frozen mid-chunk lanes, padded
widths, batched gathers — not just raw vmap throughput.

Verdict per (backend, kind):

    max_width = 1     when width-1 is within SLACK (10%) of the best
                      width — the sequential program is preferred at
                      marginal differences (per-lane retirement
                      granularity, O(n) packed state, and the spread at
                      these chunk durations is near timing noise)
    best width        when a bounded width wins by more than SLACK
    0 (unbounded)     when the largest measured width is the winner

The verdict lands in ``results/cost_model.json`` (see
``svm/cost_model.py`` for the schema), which ``LanePool`` loads at
construction. CI runs ``--quick`` and asserts the file parses with a CPU
entry; on this container the full run reproduces the historical width-1
CPU verdict for both kinds.

The harness also sweeps per-cap throughput for the shrink verdict
(``shrink_every="auto"``): the width-1 chunk program is timed at several
problem sizes — the shapes a shrunk lane's compact dispatches run at —
and shrinking is worth its recompiles + re-gathers on this backend only
when per-iteration cost actually falls with operand size
(``us_per_iter_by_n``). Dispatch-bound backends (CPU interpret mode)
measure flat and get ``shrink: false``; ``--shrink-only`` re-runs just
this sweep and merges it into an existing file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.svm import cost_model
from repro.svm.engine import DenseKernel, PallasRBF
from repro.svm.kernels import kernel_matrix
from repro.svm.precision import kernel_input
from repro.svm.scheduler import LanePool

#: width-1 keeps the cap unless a batched width beats it by this factor
SLACK = 1.10

#: shrink pays off only when the smallest swept size is at least this much
#: cheaper per iteration than the full size. The margin is deliberately
#: wide: the sweep measures the NECESSARY condition (the chunk program
#: gets cheaper at compact shapes) at a quarter of the problem, but a real
#: workload's active set rarely shrinks that far and every shrink run also
#: pays costs the sweep cannot charge — per-cap recompiles, re-gather
#: chunks, boundary-bounded dispatches. Requiring a 2x per-iteration win
#: at quarter size keeps dispatch-bound backends (dense CPU measures
#: ~1.5x and then LOSES end-to-end on the ato_shrink bench row) gated
#: off while bytes-bound streams (pallas X-streaming scales ~linearly
#: with the cap) still qualify.
SHRINK_SLACK = 2.0

#: staggered-convergence lane spread (grid-like C heterogeneity)
C_SPREAD = (0.25, 0.5, 1.0, 2.0, 4.0, 1.0, 0.5, 2.0)


def _problem(n: int, d: int, gamma: float, n_lanes: int):
    rng = np.random.default_rng(0)
    X = kernel_input(rng.normal(size=(n, d)))   # the entry points' f32 policy
    y = jnp.asarray(np.where(rng.random(n) < 0.5, -1.0, 1.0))
    masks = [jnp.asarray(np.random.default_rng(10 + h).random(n) < 0.85)
             for h in range(n_lanes)]
    Cs = [C_SPREAD[h % len(C_SPREAD)] for h in range(n_lanes)]
    sources = {"dense": DenseKernel(kernel_matrix(X, X, gamma=gamma)),
               "pallas_rbf": PallasRBF(X, gamma)}
    return sources, y, masks, Cs


def measure_kind(kind: str, source, y, masks, Cs, *, widths, chunk_iters,
                 reps: int) -> dict:
    """us per useful lane-iteration at each forced ``max_width``."""
    n = y.shape[0]
    wss = "1" if getattr(source, "fused", False) else "2"

    def run(width: int):
        pool = LanePool({kind: source}, y, wss=wss, max_width=width,
                        chunk_iters=chunk_iters)
        for h, (mask, C) in enumerate(zip(masks, Cs)):
            pool.add(h, mask, C, jnp.zeros(n, source.dtype), -y,
                     source=kind)
        t0 = time.perf_counter()
        results = pool.run()
        dt = time.perf_counter() - t0
        return dt, sum(int(r.n_iter) for r in results.values())

    run(1)                                  # warm (compile both programs)
    run(max(widths))
    cost = {}
    for w in widths:
        best = np.inf
        for _ in range(reps):
            dt, iters = run(w)
            best = min(best, dt / max(iters, 1))
        cost[str(w)] = best * 1e6
        print(f"  {kind:>10s} width {w:>2d}: "
              f"{cost[str(w)]:8.2f} us/useful-lane-iter", flush=True)
    best_w = min(widths, key=lambda w: cost[str(w)])
    if cost["1"] <= SLACK * cost[str(best_w)]:
        max_width = 1
    elif best_w == max(widths):
        max_width = 0                       # more is better: unbounded
    else:
        max_width = best_w
    return {"max_width": max_width, "us_per_lane_iter": cost}


def measure_shrink(kind: str, *, ns, d, gamma, chunk_iters, reps,
                   n_lanes: int = 2) -> dict:
    """Per-cap throughput sweep: us per useful iteration of the width-1
    chunk program at each problem size in ``ns`` — the static shapes a
    shrunk lane's compact dispatches run at. Shrink verdict = operand-byte
    sensitivity: True iff the smallest size beats the full size by more
    than ``SHRINK_SLACK`` per iteration."""
    cost = {}
    for m in sorted(ns):
        sources, y, masks, Cs = _problem(m, d, gamma, n_lanes)
        source = sources[kind]
        wss = "1" if getattr(source, "fused", False) else "2"
        best = np.inf
        for rep in range(reps + 1):         # rep 0 doubles as compile warmup
            pool = LanePool({kind: source}, y, wss=wss, max_width=1,
                            chunk_iters=chunk_iters)
            for h, (mask, C) in enumerate(zip(masks, Cs)):
                pool.add(h, mask, C, jnp.zeros(m, source.dtype), -y,
                         source=kind)
            t0 = time.perf_counter()
            results = pool.run()
            dt = time.perf_counter() - t0
            iters = sum(int(r.n_iter) for r in results.values())
            if rep > 0:
                best = min(best, dt / max(iters, 1))
        cost[str(m)] = best * 1e6
        print(f"  {kind:>10s} n {m:>5d}: {cost[str(m)]:8.2f} us/iter",
              flush=True)
    full, small = cost[str(max(ns))], cost[str(min(ns))]
    return {"shrink": bool(small * SHRINK_SLACK <= full),
            "us_per_iter_by_n": cost}


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1000,
                    help="instances per synthetic lane problem")
    ap.add_argument("--d", type=int, default=40)
    ap.add_argument("--chunk-iters", type=int, default=2048,
                    help="pool dispatch granularity (production default)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--widths", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--out", default=None,
                    help="output path (default: the loader's path, "
                         "results/cost_model.json or $REPRO_COST_MODEL)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizing (small n, widths 1/2, 1 rep)")
    ap.add_argument("--shrink-only", action="store_true",
                    help="skip the width sweep; merge only the per-cap "
                         "shrink sweep into the existing file")
    args = ap.parse_args(argv)
    if args.quick:
        args.n, args.chunk_iters, args.reps = 200, 256, 1
        args.widths = [1, 2]
    if 1 not in args.widths:
        ap.error("widths must include 1 (the sequential baseline)")

    backend = jax.default_backend()
    print(f"backend={backend} n={args.n} d={args.d} "
          f"chunk_iters={args.chunk_iters} widths={args.widths}", flush=True)
    out_path = pathlib.Path(args.out) if args.out else cost_model.model_path()
    try:
        model = json.loads(out_path.read_text())
        assert isinstance(model.get("entries"), dict)
    except (OSError, ValueError, AssertionError):
        model = {"entries": {}}
    model["schema"] = 1
    entries = model["entries"].setdefault(backend, {})

    if not args.shrink_only:
        sources, y, masks, Cs = _problem(args.n, args.d, gamma=0.5,
                                         n_lanes=max(args.widths))
        for kind, src in sources.items():
            entries.setdefault(kind, {}).update(
                measure_kind(kind, src, y, masks, Cs, widths=args.widths,
                             chunk_iters=args.chunk_iters, reps=args.reps))
        model.setdefault("meta", {})[backend] = {
            "n": args.n, "d": args.d, "chunk_iters": args.chunk_iters,
            "widths": args.widths, "n_lanes": len(masks),
            "quick": bool(args.quick), "slack": SLACK,
            "platform": platform.platform(), "jax": jax.__version__,
        }

    # per-cap sweep (the shrink verdict): quarter / half / full size,
    # mirroring the capacities a shrink_quantum-bucketed lane visits
    shrink_ns = sorted({max(64, args.n // 4), max(64, args.n // 2), args.n})
    for kind in ("dense", "pallas_rbf"):
        entries.setdefault(kind, {}).update(
            measure_shrink(kind, ns=shrink_ns, d=args.d, gamma=0.5,
                           chunk_iters=args.chunk_iters, reps=args.reps))
    model.setdefault("meta", {}).setdefault(backend, {})["shrink_ns"] = \
        shrink_ns
    model["meta"][backend]["shrink_slack"] = SHRINK_SLACK

    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(model, indent=2, sort_keys=True) + "\n")
    for kind, e in entries.items():
        print(f"{backend}/{kind}: max_width={e.get('max_width')} "
              f"shrink={e.get('shrink')}")
    print(f"wrote {out_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
