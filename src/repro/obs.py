"""Program spans and the program-load counter, kept in memory.

``span(name, **attrs)`` times one piece of the program's work. It opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows the span on its ``/host:CPU`` plane, on the clock of the device's
operations; and on exit it appends a :class:`Span` to a bounded ring
(``records()``). Parents come from a per-thread stack of open spans, and
every span opened inside a ``repro.plan`` span carries that plan's id in
``attrs["plan"]``, so all spans of one ``run_plan`` call share it.

The span's clock readings are the program's timings: ``LanePool`` takes
``seed_s`` and ``solve_s`` from ``t0_ns``/``t1_ns`` of its ``repro.pool.seed``
and ``repro.pool.dispatch`` spans, and ``SourceCache.kernel_time`` from
its ``repro.cache.materialize`` spans (``time.perf_counter_ns``: one
measurement, not two).

The program-load counter listens to JAX's compile-path events (registered
when this module is imported) and keeps each with its end time and the
name of the innermost open span of the thread that compiled (``outside``
where none was open): ``events()`` and their totals, ``counters()``. JAX
nests some of them: ``compile`` wraps ``compile_or_get_cached``, so a
``cache_load`` lies inside a ``compile``, and a jitted function called
while another one traces emits its ``trace`` inside the outer ``trace``.
Each event therefore also carries ``own_s``, its seconds less those of
the events nested in it, and the ``own_s`` of all events add up to the
time spent on programs with no second counted twice.

Span names (DESIGN.md §Spans): ``repro.plan`` and its children
``repro.plan.prepare``, ``.analyze``, ``.evals``, ``.release``;
``repro.pool.build``, ``.run``, ``.seed``, ``.dispatch``, ``.wait``,
``.retire``; ``repro.seed.assemble`` (MIR's assembly, inside
``repro.pool.seed``); ``repro.cache.materialize``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, NamedTuple

import jax
from jax.profiler import TraceAnnotation

#: spans and events each ring keeps; past it the oldest go, and are counted
MAX_RECORDS = 1 << 16
PLAN = "repro.plan"
OUTSIDE = "outside"
#: JAX's compile-path events, by the short name the counter keeps
PROGRAM_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class Span(NamedTuple):
    id: int
    parent: int | None
    name: str
    t0_ns: int
    t1_ns: int
    attrs: dict


class Event(NamedTuple):
    """One program-load event: ``secs`` as JAX reports it, ``own_s`` less
    the events nested in it, ``t_ns`` when it ended (the spans' clock),
    ``span`` the innermost open span, ``fun`` the function JAX names."""
    name: str
    secs: float
    own_s: float
    t_ns: int
    span: str
    fun: str | None


class Recorder:
    """The rings of finished spans and program-load events, with a count
    of what each dropped, and the per-thread stacks of open spans."""

    def __init__(self, maxlen: int = MAX_RECORDS):
        self.spans: collections.deque = collections.deque(maxlen=maxlen)
        self.events: collections.deque = collections.deque(maxlen=maxlen)
        self.dropped = {"spans": 0, "events": 0}
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._plans = itertools.count(1)
        self._local = threading.local()

    def stack(self) -> list:
        """This thread's open spans, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _keep(self, ring: collections.deque, kind: str, item) -> None:
        with self._lock:
            if len(ring) == ring.maxlen:
                self.dropped[kind] += 1
            ring.append(item)

    def on_event(self, event: str, secs: float, **kwargs) -> None:
        """``jax.monitoring`` duration listener: keep the compile-path
        events, each with its own seconds (less the events of this thread
        that started inside it, which JAX reported first)."""
        name = PROGRAM_EVENTS.get(event)
        if name is None:
            return
        t_ns = time.perf_counter_ns()
        start = t_ns - int(secs * 1e9)
        done = getattr(self._local, "done", None)
        if done is None:
            done = self._local.done = collections.deque(
                maxlen=self.events.maxlen)
        nested = 0.0
        while done and done[-1][0] >= start:
            nested += done.pop()[1]
        done.append((start, secs))
        stack = self.stack()
        self._keep(self.events, "events", Event(
            name, secs, max(secs - nested, 0.0), t_ns,
            stack[-1].name if stack else OUTSIDE, kwargs.get("fun_name")))

    def span(self, name: str, **attrs: Any) -> _Open:
        return _Open(self, name, attrs)

    def records(self) -> list[Span]:
        return [Span._make(s) for s in self.spans]

    def reset(self) -> None:
        with self._lock:
            self.spans.clear()
            self.events.clear()
            self.dropped = {"spans": 0, "events": 0}


class _Open:
    """An open span; after exit, ``t0_ns``/``t1_ns`` and ``seconds`` are
    the clock readings it recorded."""
    __slots__ = ("rec", "name", "attrs", "id", "parent", "t0_ns", "t1_ns",
                 "_ann", "_stack")

    def __init__(self, rec: Recorder, name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self) -> _Open:
        stack = self._stack = self.rec.stack()
        up = stack[-1] if stack else None
        self.id = next(self.rec._ids)
        self.parent = up.id if up is not None else None
        if self.name == PLAN:
            self.attrs["plan"] = next(self.rec._plans)
        elif up is not None and "plan" in up.attrs:
            self.attrs["plan"] = up.attrs["plan"]
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        stack.append(self)
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = time.perf_counter_ns()
        self._stack.pop()
        self._ann.__exit__(*exc)
        self.rec._keep(self.rec.spans, "spans", (
            self.id, self.parent, self.name, self.t0_ns, self.t1_ns,
            self.attrs))

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


RECORDER = Recorder()
jax.monitoring.register_event_duration_secs_listener(RECORDER.on_event)


def span(name: str, **attrs: Any) -> _Open:
    """Context manager: one span of ``name`` (see the module docstring).
    ``attrs`` may be added to inside the block."""
    return RECORDER.span(name, **attrs)


def records() -> list[Span]:
    """Finished spans, oldest first."""
    return RECORDER.records()


def events() -> list[Event]:
    """Program-load events, oldest first."""
    return list(RECORDER.events)


def counters() -> dict:
    """``{name: (count, own seconds)}`` of each program-load event kind,
    and ``dropped``: how many spans and events the rings let go."""
    out: dict[str, Any] = {n: (0, 0.0) for n in PROGRAM_EVENTS.values()}
    for e in events():
        n, s = out[e.name]
        out[e.name] = (n + 1, s + e.own_s)
    out["dropped"] = dict(RECORDER.dropped)
    return out


def reset() -> None:
    """Forget every finished span and event (open spans still record)."""
    RECORDER.reset()
