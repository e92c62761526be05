"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def emit(name: str, rows: list[dict]) -> None:
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, name + ".json"), "w") as fh:
        json.dump(rows, fh, indent=1)
    if rows:
        # column union in first-appearance order: rows are heterogeneous
        # (occupancy / residency blocks appear per schedule)
        cols = list(dict.fromkeys(c for r in rows for c in r))
        print(",".join(cols))
        for r in rows:
            print(",".join(str(r.get(c, "")) for c in cols))
