"""Kernel-source factories and the compute-on-demand LRU cache.

The grid's reuse axis (one RBF matrix per gamma, shared by every C cell
and fold) used to force the cross-gamma pool to materialize ALL
``len(gammas) * n^2 * 8`` bytes up front. Joulani et al. frame CV as a
dependency structure over reusable partial solutions — our lane graph IS
that structure, so the schedule itself knows which kernel a chunk needs
next and which resident kernel is furthest from being needed. This module
makes kernel matrices **residency-managed operands**:

* a :class:`KernelSpec` *declares* a kernel source — ``(kind, gamma, X,
  backend)`` plus an optional row truncation — without computing it. A
  spec satisfies the cheap half of the engine's kernel-source protocol
  (``dtype``, ``fused``, ``nbytes``) so schedulers can type/size lanes
  without materializing, and ``materialize()`` produces the dense source
  on demand;
* a :class:`SourceCache` fronts a ``{key: source-or-spec}`` dict:
  already-dense entries are *pinned* (always resident, exactly the
  pre-cache behaviour), spec entries materialize through the cache under
  a ``max_resident`` / ``cache_bytes`` budget and are **evicted by
  schedule distance** — the resident source with the fewest remaining
  unretired lanes goes first (it is the one the schedule needs least),
  the *sticky* (currently serving) source only as a last resort, ties
  broken least-recently-used.

Eviction drops only the materialized array. Because a spec is a pure
function of ``(X, kind, gamma, backend, n)``, re-materialization rebuilds
the bit-identical matrix, and a lane's iterate sequence depends only on
its own (source, mask, C, state) — so any eviction/re-materialization
schedule preserves the pool's bit-parity invariant (covered by
tests/test_sources.py). The scheduler's packed-batch cache for an evicted
source is written back to the lanes *before* the kernel is dropped
(``on_evict``), so no solver progress is ever lost to eviction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.smo_step import feature_major_shape
from repro.svm.engine import DenseKernel, PallasRBF
from repro.svm.kernels import kernel_matrix


def is_factory(entry) -> bool:
    """True when a sources-dict entry is a factory (declares a kernel and
    materializes on demand) rather than an already-usable kernel source —
    factories expose ``materialize()``, sources expose ``row()``."""
    return callable(getattr(entry, "materialize", None)) and \
        not callable(getattr(entry, "row", None))


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A declared-but-not-computed kernel source.

    ``n`` truncates to the first ``n`` instances (the k-fold padding
    truncation). The slice is applied to ``X`` *before* the kernel call —
    computing the full ``(N, N)`` matrix and slicing after wastes
    O(N² − n²) compute and memory per materialization (and the two are
    not bit-identical at every shape, so callers that need parity with a
    truncated kernel must build it this way too, see ``core/cv.py``).

    ``kind="pallas_rbf"`` declares a *row-streaming* source: materialize
    returns a :class:`~repro.svm.engine.PallasRBF` holding only ``X[:n]``
    in two layouts — ``nbytes`` is their bytes, not n² kernel bytes, so
    the cache budget bounds such sources by data size, and ``fused`` is
    answered True without compute (WSS-1 is checked at pool
    construction, not deferred to first dispatch).
    """
    X: Any
    gamma: float = 1.0
    kind: str = "rbf"
    backend: str = "jnp"
    n: int | None = None

    @property
    def fused(self) -> bool:
        """Dense kinds materialize a plain dense source (the fused/WSS
        check re-runs against the product anyway — deferred check);
        pallas_rbf is fused by declaration."""
        return self.kind == "pallas_rbf"

    @property
    def streams_rows(self) -> bool:
        return self.kind == "pallas_rbf"

    @property
    def dtype(self):
        return self.X.dtype

    @property
    def n_rows(self) -> int:
        return int(self.X.shape[0] if self.n is None else self.n)

    @property
    def nbytes(self) -> int:
        """Resident bytes of the materialized source — what the cache
        budget accounts, known without computing anything: n² kernel
        bytes for dense kinds; for row-streaming kinds X's bytes in its
        two layouts, row-major and the kernel's padded feature-major."""
        itemsize = self.X.dtype.itemsize
        if self.kind == "pallas_rbf":
            d = int(self.X.shape[1])
            D, N = feature_major_shape(self.n_rows, d, itemsize)
            return (self.n_rows * d + D * N) * itemsize
        return self.n_rows * self.n_rows * itemsize

    def materialize(self):
        X = self.X if self.n is None else self.X[: self.n]
        if self.kind == "pallas_rbf":
            return PallasRBF(X, self.gamma)
        K = kernel_matrix(X, X, kind=self.kind, gamma=self.gamma,
                          backend=self.backend)
        K.block_until_ready()
        return DenseKernel(K)


def source_identity(entry, y=None) -> tuple | None:
    """Content identity of a sources-dict entry: equal identities declare
    the SAME kernel values, so a multi-tenant pool may serve both tenants
    from one resident kernel. ``None`` means "not identifiable — never
    dedup" (opaque custom sources).

    Labels are part of the identity when given: the pool stores one ``y``
    per source key, so two tenants may share a kernel only when they also
    share the label vector that kernel's lanes train against.

    Arrays enter as sha1 digests of their raw bytes (after the spec's own
    ``[:n]`` truncation — a truncated and an untruncated view of the same
    ``X`` are different kernels), keeping the identity hashable and cheap
    to compare without holding the data."""
    import hashlib

    import numpy as np

    def digest(a) -> str:
        a = np.ascontiguousarray(np.asarray(a))
        return hashlib.sha1(a.tobytes()).hexdigest()

    if isinstance(entry, KernelSpec):
        ident = ("spec", entry.kind, float(entry.gamma), entry.backend,
                 entry.n_rows, str(entry.dtype),
                 digest(entry.X[: entry.n_rows]))
    elif isinstance(entry, DenseKernel):
        K = entry.K
        ident = ("dense", str(K.dtype), int(K.shape[0]), digest(K))
    else:
        return None
    if y is not None:
        ident = ident + (digest(y),)
    return ident


def _source_nbytes(src) -> int:
    nb = getattr(src, "nbytes", None)
    if nb is not None:
        return int(nb)
    K = getattr(src, "K", None)
    return int(K.nbytes) if K is not None else 0


def source_nbytes(src) -> int:
    """Resident bytes a source (or spec) will occupy — the figure the
    cache budget accounts. Public alias the schedule simulator prices
    plans with."""
    return _source_nbytes(src)


def budget_fits(count: int, nbytes: int, *, max_resident: int = 0,
                cache_bytes: int = 0) -> bool:
    """THE residency budget rule (0 = unbounded), in pure form: eviction,
    the scheduler's per-chunk source selection and the schedule simulator
    (``repro.analysis.plan_sim``) all defer here, so they cannot
    desynchronize."""
    if max_resident and count > max_resident:
        return False
    return not (cache_bytes and nbytes > cache_bytes)


def pick_victim(resident, *, sticky, distance):
    """THE eviction victim rule, in pure form: ``resident`` is the
    managed keys in recency order (least-recently-used first). Non-sticky
    before sticky, then ascending schedule distance (fewest remaining
    lanes = needed least), then LRU. Shared by the live cache and the
    schedule simulator."""
    keys = list(resident)
    return min(keys, key=lambda k: (k == sticky, distance(k),
                                    keys.index(k)))


class SourceCache:
    """Residency manager for a pool's ``{key: source-or-spec}`` dict.

    * ``get(key)`` returns a usable kernel source, materializing a spec
      entry on demand. Before a materialization that would exceed the
      budget (``max_resident`` managed sources and/or ``cache_bytes``
      managed bytes; 0 = unbounded), resident managed sources are evicted
      in *schedule-distance* order: fewest remaining lanes first
      (``distance(key)``, supplied by the scheduler), the sticky source
      (``sticky()``) only if nothing else can be evicted, ties broken
      least-recently-used. ``on_evict(key)`` fires before the array is
      dropped — the scheduler writes its packed batch back there.
    * ``meta(key)`` answers the cheap protocol questions (``dtype``,
      ``fused``) without materializing: the resident source when there is
      one, else the entry itself (specs carry ``dtype``/``fused``).
    * pinned entries (already-materialized sources) are always resident,
      never evicted, and not counted against the budget — a pool built
      from dense matrices behaves exactly as before the cache existed.

    The fused/WSS-1 compatibility check runs at materialization time
    (``wss`` is the pool's selection mode): a factory's product cannot be
    inspected at pool construction, so the check is *deferred* — it fires
    on the first dispatch that would actually mis-drive the source.
    """

    def __init__(self, entries: dict, *, max_resident: int = 0,
                 cache_bytes: int = 0, wss: str = "2",
                 distance: Callable[[Any], int] | None = None,
                 sticky: Callable[[], Any] | None = None,
                 on_evict: Callable[[Any], None] | None = None,
                 on_trace: Callable | None = None):
        self._entries = dict(entries)
        self.max_resident = int(max_resident)
        self.cache_bytes = int(cache_bytes)
        self.wss = wss
        self._distance = distance or (lambda key: 0)
        self._sticky = sticky or (lambda: None)
        self.on_evict = on_evict
        # varargs event sink (the pool's ``_trace``): materialize/evict
        # events join the scheduler's trace grammar through here
        self.on_trace = on_trace
        self._resident: dict[Any, Any] = {}     # managed key -> source (LRU)
        self._pinned: dict[Any, Any] = {
            k: v for k, v in entries.items() if not is_factory(v)}
        # accounting (the grid's kernel_time and the bench peak_resident
        # block read these)
        self.kernel_time = 0.0
        self.materializations = 0
        self.evictions = 0
        self.peak_resident = len(self._pinned)
        self.peak_resident_bytes = sum(
            _source_nbytes(s) for s in self._pinned.values())

    # ------------------------------------------------------------- queries

    def resident(self, key) -> bool:
        return key in self._pinned or key in self._resident

    def pinned(self, key) -> bool:
        return key in self._pinned

    def nbytes_of(self, key) -> int:
        """Resident footprint of ``key`` — from the materialized source if
        resident, else the spec's estimate; never materializes."""
        return _source_nbytes(self.meta(key))

    @property
    def budgeted(self) -> bool:
        return bool(self.max_resident or self.cache_bytes)

    def fits(self, count: int, nbytes: int) -> bool:
        """True when ``count`` managed sources totalling ``nbytes`` bytes
        fit the budget (0 = unbounded). Defers to the pure
        :func:`budget_fits`: eviction (``_evict_for``), the scheduler's
        per-chunk source selection (``LanePool._budget_sources``) and the
        schedule simulator all share the one rule."""
        return budget_fits(count, nbytes, max_resident=self.max_resident,
                           cache_bytes=self.cache_bytes)

    def meta(self, key):
        """The entry for protocol questions that must not materialize
        (``dtype``, ``fused``): the resident source if there is one, else
        the spec itself."""
        if key in self._pinned:
            return self._pinned[key]
        return self._resident.get(key, self._entries[key])

    @property
    def resident_bytes(self) -> int:
        return sum(_source_nbytes(s) for s in self._resident.values())

    @property
    def pinned_bytes(self) -> int:
        return sum(_source_nbytes(s) for s in self._pinned.values())

    @property
    def stats(self) -> dict:
        return {"materializations": self.materializations,
                "evictions": self.evictions,
                "kernel_time": round(self.kernel_time, 4),
                "peak_resident": self.peak_resident,
                "peak_resident_bytes": self.peak_resident_bytes}

    # ----------------------------------------------------- entry lifecycle

    def add_entry(self, key, entry) -> None:
        """Admit a new entry after construction (the daemon admits plans
        into a live pool). Same pinning rule as the constructor: an
        already-usable source is pinned, a factory is managed."""
        if key in self._entries:
            raise ValueError(f"source {key!r} already present")
        self._entries[key] = entry
        if not is_factory(entry):
            self._pinned[key] = entry
            self.peak_resident = max(
                self.peak_resident, len(self._pinned) + len(self._resident))

    def remove_entry(self, key) -> None:
        """Drop an entry and any residency it holds (a drained study's
        sources leave the pool). Not an eviction: no ``on_evict`` — the
        caller has already retired every lane reading ``key``."""
        self._entries.pop(key, None)
        self._pinned.pop(key, None)
        self._resident.pop(key, None)

    # ------------------------------------------------------ materialization

    def check_fused(self, key, src) -> None:
        """The one fused/WSS-1 compatibility rule: the pool applies it to
        pinned entries at construction, the cache to factory products at
        materialization."""
        if getattr(src, "fused", False) and self.wss == "2":
            raise ValueError(
                f"source {key!r} is fused and requires WSS-1 (wss='1')")

    def _evict_for(self, incoming_bytes: int) -> None:
        """Evict managed residents until the budget admits ``incoming_bytes``
        more. Victim order: non-sticky before sticky, then ascending
        schedule distance (fewest remaining lanes = needed least), then
        least-recently-used (dict order = recency)."""
        # the `self._resident` guard keeps a single over-budget kernel
        # admissible when there is nothing left to evict
        while self._resident and not self.fits(
                len(self._resident) + 1,
                self.resident_bytes + incoming_bytes):
            # dict order = recency (LRU first); the pure rule is shared
            # with the schedule simulator
            victim = pick_victim(self._resident, sticky=self._sticky(),
                                 distance=self._distance)
            if self.on_evict is not None:
                self.on_evict(victim)
            if self.on_trace is not None:
                self.on_trace("evict", victim,
                              _source_nbytes(self._resident[victim]))
            del self._resident[victim]
            self.evictions += 1

    def get(self, key):
        """Return a usable kernel source for ``key``, materializing (and
        evicting per the budget) on demand."""
        if key in self._pinned:
            return self._pinned[key]
        src = self._resident.pop(key, None)
        if src is not None:                    # hit: refresh recency
            self._resident[key] = src
            return src
        spec = self._entries[key]
        self._evict_for(_source_nbytes(spec))
        # sources are pytrees: block on the product so kernel_time measures
        # the materialization, not its dispatch (the dense path blocks
        # inside materialize; the row-streaming path holds only X)
        with obs.span("repro.cache.materialize", source=key) as sp:
            src = jax.block_until_ready(spec.materialize())
        self.kernel_time += sp.seconds
        self.materializations += 1
        self.check_fused(key, src)
        self._resident[key] = src
        if self.on_trace is not None:
            self.on_trace("materialize", key, _source_nbytes(src))
        self.peak_resident = max(
            self.peak_resident, len(self._pinned) + len(self._resident))
        self.peak_resident_bytes = max(
            self.peak_resident_bytes,
            self.resident_bytes
            + sum(_source_nbytes(s) for s in self._pinned.values()))
        return src
