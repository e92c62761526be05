"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload adult.ato --seed 7 --seconds 40 --trace 0

Everything about a cell is found by name from ``BENCHMARK.json`` at the
checkout's root, so a new cell, traffic mix, step kind or metric is a new
file and a new entry, never an edit:

* ``bench/configs/<config>.json``: the deployment (sizes, C, gamma, k, tol);
* ``bench/traffic/<traffic>.json``: the method, kernel source and step kind;
* ``bench/steps/<step>.py``: the step kind; its ``STEP`` class is built
  from (config, traffic, X, y, chunks), ``setup()`` warms every program
  the window runs, and each call runs one step and returns what the
  program produced;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/limits/<workload>.json``: the limit of each number that the
  correctness check compares.

A run makes its data and fold partition from ``--seed``, sets up (the
first two folds, which compile every program), then starts steps back to
back until ``--seconds`` have passed; the window ends when the last
started step ends. ``fold_s`` is the window over the steps it completed.
With ``--trace 1`` the same window gives the per-layer metrics, and then
a profiler trace of whole further steps, at least ``TRACE_SECONDS`` of
them, gives the device numbers. Then the program's state is freed and the
plain reference (``bench/reference.py``) judges every fold of the window.

The last line of standard output is one JSON object; the numbers the
check compared, each beside its limit, are the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, or
outside a checkout that holds the program, the run exits nonzero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: least seconds of whole steps that a traced run profiles
TRACE_SECONDS = 2.0


def load_json(path: pathlib.Path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path: pathlib.Path, bench: pathlib.Path = BENCH):
    name = "bench_" + "_".join(path.relative_to(bench).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``workload``
    reports: those that list it, and those with no list at all, for a
    per-layer metric only where the cell reports the metric it moves."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if m.get("workloads") is None or workload in m["workloads"]}
    out = []
    for m in spec[kind]:
        listed = m.get("workloads")
        if listed is not None:
            ok = workload in listed
        else:
            ok = m["name"] in e2e if kind == "end_to_end" else m["moves"] in e2e
        if ok:
            out.append(m)
    return out


def resolve(workload: str, spec: dict | None = None,
            bench: pathlib.Path = BENCH) -> types.SimpleNamespace:
    """Every file and entry of one cell, found by name under ``bench``."""
    spec = load_json(bench.parent / "BENCHMARK.json") if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    per_layer = cell_metrics(spec, workload, "per_layer")
    return types.SimpleNamespace(
        name=workload, cell=cell, spec=spec,
        cfg=load_json(bench / "configs" / f"{cell['config']}.json"),
        traffic=traffic,
        step=load_module(bench / "steps" / f"{traffic['step']}.py", bench),
        limits=load_json(bench / "limits" / f"{workload}.json"),
        end_to_end=cell_metrics(spec, workload, "end_to_end"),
        per_layer=per_layer,
        readers={m["name"]: load_module(bench / "metrics" / f"{m['name']}.py",
                                        bench)
                 for m in per_layer})


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache``, a fixed path, so that
    only a cell's first run in a checkout compiles. Every program is kept,
    however fast it compiled, so set-up finds all of them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the programs compiled, or loaded from the persistent cache,
    while it is on: inside the window there should be none."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_):
        if self.on and name in self.EVENTS:
            self.count += 1


def record_trace(step, trace_dir, seconds: float = TRACE_SECONDS):
    """Profile whole steps, each in a ``bench.step`` span, until at least
    ``seconds`` have passed; return the trace file."""
    import jax
    import devtrace
    jax.profiler.start_trace(str(trace_dir))
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        with jax.profiler.TraceAnnotation(devtrace.STEP_SPAN):
            step()
    jax.profiler.stop_trace()
    return devtrace.xplane_file(trace_dir)


def trace_steps(step, seconds: float = TRACE_SECONDS) -> dict:
    """``record_trace`` into a temporary directory, which is removed, and
    the trace reduced (``devtrace.reduce``)."""
    import devtrace
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        path = record_trace(step, tmp, seconds)
        t1 = time.monotonic()
        reduced = devtrace.reduce(*devtrace.events(path))
        reduced["reduce_s"] = time.monotonic() - t1
        reduced["xplane_bytes"] = path.stat().st_size
        return reduced
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def judge(numbers: dict, limits: dict) -> tuple[dict, bool]:
    """Each number that ``limits`` names beside its limit, and whether
    every one lies within it: the comparison that decides ``correct``."""
    checks = {name: {"value": numbers[name], "limit": limit}
              for name, limit in limits.items()}
    return checks, all(v["value"] <= v["limit"] for v in checks.values())


def run_cell(c: types.SimpleNamespace, seed: int, seconds: float,
             trace: bool, *, rows: int | None = None, t_start=None) -> dict:
    """Set up, measure, trace and check one run of cell ``c``. ``rows``
    overrides the configuration's size (tests on the CPU)."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    import data
    import reference

    t_start = T_START if t_start is None else t_start
    cfg = dict(c.cfg)
    if rows is not None:
        cfg["published_rows"] = rows
        cfg["rows"] = rows // cfg["k"] * cfg["k"]
    dev = jax.devices()[0]
    with TraceAnnotation("bench.setup.data"):
        X, y, chunks = data.cell_inputs(cfg, seed)
    n = chunks.size
    if n != cfg["rows"]:
        raise ValueError(f"{n} rows solved, configuration says {cfg['rows']}")
    step = c.step.STEP(cfg, c.traffic, X, y, chunks)
    with TraceAnnotation("bench.setup.folds"):
        step.setup()
    counter = CompileCounter()

    counter.on = True
    t0 = time.monotonic()
    setup_s = t0 - t_start
    folds = []
    while True:
        ts = time.monotonic()
        with TraceAnnotation("bench.step"):
            fold = step()
        folds.append((fold, time.monotonic() - ts))
        if time.monotonic() - t0 >= seconds:
            break
    window_s = time.monotonic() - t0
    counter.on = False

    reduced = trace_steps(step) if trace else None
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    masks = data.train_masks(chunks)
    answers = [dict(alpha=np.asarray(f.alpha), f=np.asarray(f.f),
                    pred=f.pred, objective=f.objective, train=masks[f.fold],
                    test=chunks[f.fold]) for f, _ in folds]
    records = [dict(fold=f.fold, seed_from=f.seed_from, n_iter=f.n_iter,
                    converged=f.converged, seed_s=f.seed_s,
                    solve_s=f.solve_s, wall_s=w) for f, w in folds]
    step.close()
    del step, folds
    gc.collect()

    t_ref = time.monotonic()
    numbers = reference.check(X[:n].astype(np.float32), y[:n], cfg["C"],
                              cfg["gamma"], cfg["tol"], answers)
    ref_s = time.monotonic() - t_ref
    checks, correct = judge(numbers, c.limits)

    run = types.SimpleNamespace(folds=records, trace=reduced, cfg=cfg,
                                traffic=c.traffic, device_kind=dev.device_kind)
    if trace:
        metrics = {}
        for m in c.per_layer:
            value = c.readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"fold_s": window_s / len(records), "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(records),
           "failed": sum(not r["converged"] for r in records),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    info = {"setup_s": setup_s, "window_s": window_s,
            "compiles_in_window": counter.count, "reference_s": ref_s,
            "numbers": numbers, "folds": records,
            "trace_reduce_s": reduced["reduce_s"] if reduced else None,
            "trace_bytes": reduced["xplane_bytes"] if reduced else None}
    return {"result": out, "info": info}


def require_chips(count: int):
    """The first device, if JAX finds a TPU with at least ``count`` chips;
    otherwise None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        print(f"bench: needs {count} TPU chip(s), JAX finds {len(devs)} "
              f"{devs[0].platform} device(s) ({devs[0].device_kind})",
              file=sys.stderr)
        return None
    return devs[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT} holds no program (src/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    c = resolve(args.workload)
    import jax
    jax.config.update("jax_enable_x64", True)
    cache = enable_compile_cache()
    dev = require_chips(c.cell["chips"])
    if dev is None:
        return 3
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}  "
          f"jax {jax.__version__}  compile cache {cache}", flush=True)
    out = run_cell(c, args.seed, args.seconds, bool(args.trace))
    info, result = out["info"], out["result"]
    print(f"setup_s {info['setup_s']}  window {info['window_s']} s  "
          f"folds {len(info['folds'])}", flush=True)
    for r in info["folds"]:
        print(f"  fold {r['fold']} from {r['seed_from']}  {r}", flush=True)
    print(f"compiles in window {info['compiles_in_window']}", flush=True)
    print(f"peak device memory {result['device']['memory_peak_bytes']} bytes",
          flush=True)
    print(f"reference {info['reference_s']} s  {info['numbers']}", flush=True)
    if info["trace_reduce_s"] is not None:
        print(f"trace of {info['trace_bytes']} bytes reduced in "
              f"{info['trace_reduce_s']} s", flush=True)
    for name, v in result["checks"].items():
        verdict = "ok" if v["value"] <= v["limit"] else "FAIL"
        print(f"check {name} {v['value']} limit {v['limit']} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
