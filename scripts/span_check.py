"""What the program's spans (``repro.obs``) cost, and where they sit in a
device trace of one fold.

    python3 scripts/span_check.py [WORKLOAD] [SECONDS]

Run on one TPU chip from the checkout's root. It prints one JSON line:

* ``ns_per_span``: the mean cost of one empty ``obs.span`` with the
  profiler off and on, beside that of a bare ``TraceAnnotation``;
* ``fold``: the fold chain of ``WORKLOAD`` (default ``adult.cold_pallas``)
  at its configuration's size is set up as a benchmark run sets it up,
  then whole steps are profiled for SECONDS (default 0.01, so one step) as
  a traced run profiles them. It gives set-up's seconds by phase and the
  program-load counter's own seconds by kind and by the span that was
  open; for the last traced step, the seconds of its spans by name; for
  each ``repro.*`` span name on the profiler's host plane, the count and
  how many lie inside a ``bench.step`` span; and for each
  ``repro.pool.wait`` span, the device ``while`` op that ends inside it,
  and how far that op started before the span.
"""
import time

T_START = time.monotonic()

import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import data  # noqa: E402
import devtrace  # noqa: E402
import run  # noqa: E402
from jax.profiler import ProfileData, TraceAnnotation  # noqa: E402

from repro import obs  # noqa: E402


def ns_per(fn, n: int) -> float:
    t0 = time.perf_counter_ns()
    for _ in range(n):
        fn()
    return (time.perf_counter_ns() - t0) / n


def empty_span():
    with obs.span("repro.check"):
        pass


def empty_annotation():
    with TraceAnnotation("repro.check"):
        pass


def span_costs(n: int = 100_000) -> dict:
    out = {"span_off": ns_per(empty_span, n),
           "annotation_off": ns_per(empty_annotation, n)}
    tmp = tempfile.mkdtemp(prefix="span-cost-")
    jax.profiler.start_trace(tmp)
    try:
        out["span_on"] = ns_per(empty_span, n // 5)
        out["annotation_on"] = ns_per(empty_annotation, n // 5)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(tmp, ignore_errors=True)
    obs.reset()
    return out


def fold_trace(workload: str, seconds: float) -> dict:
    run.enable_compile_cache()
    c = run.resolve(workload)
    t0 = time.monotonic()
    X, y, chunks = data.cell_inputs(c.cfg, 2147480011)
    t1 = time.monotonic()
    step = c.step.STEP(c.cfg, c.traffic, X, y, chunks)
    step.setup()
    t2 = time.monotonic()
    setup = {"before_data_s": t0 - T_START, "data_s": t1 - t0,
             "folds_s": t2 - t1}
    programs: dict = {"by_kind": {}, "by_span": {}}
    for e in obs.events():
        for key, val in (("by_kind", e.name), ("by_span", e.span)):
            programs[key][val] = programs[key].get(val, 0.0) + e.own_s
    tmp = tempfile.mkdtemp(prefix="span-trace-")
    try:
        path = run.record_trace(step, tmp, seconds)
        pd = ProfileData.from_file(str(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    mine = obs.records()
    last = max(s.attrs["plan"] for s in mine if s.name == obs.PLAN)
    step_spans: dict = {}
    for s in mine:
        if s.attrs.get("plan") == last:
            step_spans[s.name] = step_spans.get(s.name, 0.0) + (
                s.t1_ns - s.t0_ns) / 1e9
    spans, whiles = [], []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name == devtrace.HOST_PLANE:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(("repro.", "bench.")))
            elif devtrace.DEVICE_PLANE.match(plane.name) and \
                    line.name == devtrace.OPS_LINE:
                whiles.extend((devtrace.op_name(e.name), e.start_ns,
                               e.start_ns + e.duration_ns)
                              for e in line.events
                              if devtrace.op_name(e.name).startswith("while"))
    steps = [(s, e) for n, s, e in spans if n == devtrace.STEP_SPAN]
    names: dict = {}
    for n, s, e in spans:
        if n.startswith("repro."):
            rec = names.setdefault(n, {"count": 0, "in_step": 0})
            rec["count"] += 1
            rec["in_step"] += any(a <= s and e <= b for a, b in steps)
    waits = []
    for n, s, e in spans:
        if n != "repro.pool.wait":
            continue
        ends = [w for w in whiles if s <= w[2] <= e]
        top = max(ends, key=lambda w: w[2] - w[1]) if ends else None
        waits.append({
            "wait_ms": (e - s) / 1e6,
            "while": top[0] if top else None,
            "while_ms": (top[2] - top[1]) / 1e6 if top else None,
            "while_started_before_wait_ms":
                (s - top[1]) / 1e6 if top else None})
    step.close()
    return {"workload": workload, "setup": setup, "programs": programs,
            "step_spans": step_spans, "steps": len(steps), "spans": names,
            "waits": waits}


def main(workload: str = "adult.cold_pallas", seconds: float = 0.01):
    out = {"device": jax.devices()[0].device_kind,
           "ns_per_span": span_costs(),
           "fold": fold_trace(workload, seconds)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:2], *map(float, sys.argv[2:3]))
