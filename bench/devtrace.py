"""Reduce a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
Each chip is a plane ``/device:TPU:<i>`` whose line ``XLA Ops`` holds one
event per device operation, named by its HLO text (``%while.28 = (...)
while(...)``); the host's threads are ``/host:CPU`` lines, where the
benchmark's own ``TraceAnnotation`` spans (``bench.*``) sit. Host and
device timestamps share one clock, in nanoseconds.

``reduce`` gives, over the traced window, which runs from the start of the
first ``bench.step`` span to the end of the last:

* ``busy_s``: the union of the intervals in which an operation ran on a
  chip, averaged over the chips; ``idle_share`` is 1 - busy / window;
* ``op_seconds``: device self time (less that of the ops nested in it)
  by operation name (the HLO instruction's name, ``while.28``), summed
  over the chips, and ``op_counts``, the number of such events;
* ``device_ops``: the ten operations that took the most device time;
* ``idle_gaps``: device idle time summed by the innermost ``bench.*``
  host span that was open in the middle of each gap (``outside`` where
  none was), the ten largest.
"""
from __future__ import annotations

import gzip
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
STEP_SPAN = "bench.step"
_HLO_NAME = re.compile(r"%?([^\s=]+)\s*=")


def op_name(text: str) -> str:
    """The HLO instruction name of a device event: ``while.28`` for
    ``%while.28 = (...) while(...)``; other names are kept as they are."""
    m = _HLO_NAME.match(text)
    return m.group(1) if m else text


def xplane_file(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def events(path):
    """``(device, spans)`` of one trace file (``.xplane.pb``, or the same
    gzipped, ``.xplane.pb.gz``): device ops as ``{plane: [(name,
    start_ns, end_ns)]}`` and the host's ``bench.*`` spans as ``[(name,
    start_ns, end_ns)]``."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    device, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return device, spans


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _span_at(spans, t):
    """Name of the shortest span that contains time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside"


def step_window(spans) -> tuple[float, float]:
    """(start_ns, end_ns) from the first ``bench.step`` span's start to the
    last one's end."""
    steps = [(s, e) for name, s, e in spans if name == STEP_SPAN]
    if not steps:
        raise ValueError(f"the trace holds no {STEP_SPAN} span")
    return min(s for s, _ in steps), max(e for _, e in steps)


def self_times(ops):
    """``[(name, self_ns)]``: each op's time less that of the ops nested
    in it (a ``while`` holds its body's ops on the same line), so that
    nothing is counted twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ops[i][0], own[i]) for i in range(len(ops))]


def reduce(device, spans, top: int = 10) -> dict:
    """Device numbers of the ``bench.step`` window (see module doc)."""
    if not device:
        raise ValueError("the trace holds no TPU device plane")
    t0, t1 = step_window(spans)
    op_seconds, op_counts, idle = {}, {}, {}
    busy_ns = 0.0
    for ops in device.values():
        clipped = [(n, max(s, t0), min(e, t1)) for n, s, e in ops
                   if e > t0 and s < t1]
        for name, own in self_times(clipped):
            op_seconds[name] = op_seconds.get(name, 0.0) + own / 1e9
            op_counts[name] = op_counts.get(name, 0) + 1
        merged = _union((s, e) for _, s, e in clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                where = _span_at(spans, (s + e) / 2)
                idle[where] = idle.get(where, 0.0) + (e - s) / 1e9
    chips = len(device)
    busy_s = busy_ns / chips / 1e9
    window_s = (t1 - t0) / 1e9

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:top]

    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s, "chips": chips,
            "op_seconds": op_seconds, "op_counts": op_counts,
            "device_ops": ranked(op_seconds),
            "idle_gaps": ranked({k: v / chips for k, v in idle.items()})}


def ops_matching(reduced: dict, prefix: str) -> tuple[float, int]:
    """(device seconds, event count) of the operations whose name is
    ``prefix`` or ``prefix.<n>``."""
    def hit(name):
        return name == prefix or name.startswith(prefix + ".")
    secs = sum(v for k, v in reduced["op_seconds"].items() if hit(k))
    count = sum(v for k, v in reduced["op_counts"].items() if hit(k))
    return secs, count
