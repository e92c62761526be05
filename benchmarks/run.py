"""Benchmark driver — one section per paper table/figure.
``python -m benchmarks.run [--quick]`` prints CSV per section and writes
JSON under results/bench/.

The table1 section additionally writes ``BENCH_table1.json`` at the repo
root (cold vs cold_batched vs seeded methods) so the perf trajectory is
tracked across PRs — CI runs ``--quick --only table1`` and uploads it.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _write_bench_table1(rows: list[dict], quick: bool) -> None:
    import jax
    # per-method aggregates (summed over datasets): the CI artifact diff
    # shows a seeding init-time regression — e.g. the jittable ATO losing
    # its edge over ato_ref — in one line instead of buried across rows
    per_method: dict[str, dict] = {}
    for r in rows:
        agg = per_method.setdefault(
            r["method"], {"init_s": 0.0, "solve_s": 0.0, "iterations": 0})
        agg["init_s"] += r["init_s"]
        agg["solve_s"] += r["solve_s"]
        agg["iterations"] += r["iterations"]
    for agg in per_method.values():
        agg["init_s"] = round(agg["init_s"], 4)
        agg["solve_s"] = round(agg["solve_s"], 4)
    # scheduler occupancy aggregate: mean live width (weighted by chunk
    # count) and peak width across every repacked run — a shrinking
    # mean_live_width is the repack win; mean == peak means retirement
    # never compacted the batch and the scheduler degraded to the old
    # fixed-width schedule
    occ_rows = [r["occupancy"] for r in rows if "occupancy" in r]
    # the historical mean/peak aggregate is the REPACKED-CV signal (a
    # shrinking mean_live_width is the repack win vs cold_batched); the
    # 45-lane grid rows would dominate its chunk counts and shift it for
    # schedule-unrelated reasons, so they are excluded here and tracked by
    # their own rows + the per-source block below
    cv_occ = [r["occupancy"] for r in rows
              if "occupancy" in r and not r["method"].startswith("grid")]
    scheduler = None
    if occ_rows:
        total_chunks = sum(o["chunks"] for o in cv_occ)
        scheduler = {
            "chunks": total_chunks,
            "mean_live_width": round(
                sum(o["mean_live_width"] * o["chunks"] for o in cv_occ)
                / max(total_chunks, 1), 3),
            "peak_width": max((o["peak_width"] for o in cv_occ), default=0),
        }
        # per-source (per-gamma) live widths from multi-source pools,
        # aggregated across datasets by source slot: a straggler gamma row
        # shows up as one slot's mean/peak running away from the others —
        # the cross-gamma pooling win stays visible as an artifact diff
        per_source: dict[str, dict] = {}
        for o in occ_rows:
            for key, s in (o.get("per_source") or {}).items():
                rec = per_source.setdefault(
                    key, {"chunks": 0, "live": 0.0, "peak": 0})
                rec["chunks"] += s["chunks"]
                rec["live"] += s["mean_live_width"] * s["chunks"]
                rec["peak"] = max(rec["peak"], s["peak_live_width"])
        if per_source:
            scheduler["per_source_live_width"] = {
                key: {"chunks": rec["chunks"],
                      "mean": round(rec["live"] / max(rec["chunks"], 1), 3),
                      "peak": rec["peak"]}
                for key, rec in sorted(per_source.items())}
    # kernel-source LRU aggregate (grid_pooled_lru rows): peak resident
    # kernels/bytes across datasets and total materializations — a memory
    # ceiling regression (budget not holding, or eviction thrash showing
    # up as runaway materialization counts) is a one-line artifact diff
    lru = [r["peak_resident"] for r in rows
           if r.get("method") == "grid_pooled_lru" and "peak_resident" in r]
    kernel_cache = None
    if lru:
        kernel_cache = {
            "peak_resident_sources": max(b["sources"] for b in lru),
            "peak_resident_bytes": max(b["bytes"] for b in lru),
            "materializations": sum(b["materializations"] for b in lru),
            "kernel_s": round(sum(b["kernel_s"] for b in lru), 4),
        }
    payload = {
        "bench": "table1_kfold",
        "quick": quick,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "per_method": per_method,
        "scheduler": scheduler,
        "kernel_cache": kernel_cache,
        "rows": rows,
    }
    out = os.path.join(_REPO_ROOT, "BENCH_table1.json")
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"wrote {out}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small datasets / fewer k values")
    ap.add_argument("--only", default=None,
                    help="table1|table3|fig2")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import fig2_loo, table1_kfold, table3_vary_k
    sections = {
        "table1": lambda: table1_kfold.run(quick=args.quick),
        "table3": lambda: table3_vary_k.run(quick=args.quick),
        "fig2": lambda: fig2_loo.run(quick=args.quick),
    }
    failed = []
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        print(f"\n### {name} " + "#" * 50, flush=True)
        try:
            rows = fn()
            if name == "table1" and rows:
                _write_bench_table1(rows, args.quick)
        except Exception as e:  # noqa: BLE001
            print(f"SECTION FAILED {name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed.append(name)
    if failed:
        # a green exit on failure would let CI publish the stale checked-in
        # BENCH_table1.json as this commit's perf numbers
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
