"""Bucketed active-set shrinking for the SMO engine (LIBSVM heuristic).

The paper's premise is that a seeded solve starts *near* optimal: most
alphas sit at their bounds from iteration zero and the solver polishes a
small free set — yet every iteration still pays a full O(n*d) (fused
Pallas / row-streaming RBF) or O(n) (dense row) pass. Shrinking removes
bound-locked variables from the working problem so per-iteration cost
scales with the ACTIVE fraction, which is exactly the quantity alpha
seeding makes small.

Design: shrinking is a **problem transformation at chunk granularity**,
not engine-core surgery. The engine's ``EngineState``/``_step``/chunk
programs are untouched (``shrink_every=0`` is bit-identical to today by
construction); a shrunk lane instead runs the *same* chunk programs on a
gathered compact subproblem:

* **heuristic** — every ``shrink_every`` iterations (a boundary enforced
  via the traced ``it_cap``, so cadence adds NO new program shapes), a
  variable is shrunk when it is bound-locked against the current
  ``(b_up, b_low)`` estimates: in I_up only with ``f > b_low``, or in
  I_low only with ``f < b_up`` (LIBSVM's rule in this repo's sign
  convention). Free variables never shrink, and the maximal violating
  pair is provably retained (the argmin of f over I_up has
  ``f = b_up < b_low`` whenever the gap is positive), so the compact
  problem's gap equals the full gap at the moment of shrinking.
* **bucketed compaction** — active indices are extracted with the
  fixed-shape ``jnp.nonzero(size=cap, fill_value=n)`` idiom (the
  ``ato_seed`` pattern); ``cap`` is the smallest ``shrink_quantum``
  multiple >= the active count (or the smallest declared ``shrink_caps``
  entry), so compile shapes stay O(n / quantum) per source. Pads point
  at row ``n``: gathers clamp (they replicate the last row, inert under
  the validity mask), scatters drop them — compaction round-trips are
  bit-exact with no duplicate-index hazards.
* **reconstruction contract** — when the active gap closes within
  ``10*tol`` (the compact dispatch runs at that relaxed tolerance), the
  full ``f`` is reconstructed as ``K @ (alpha*y) - y`` via the source's
  dense ``K`` or streaming ``matvec`` slab path, the lane unshrinks, and
  the solver continues on the full set to the true tolerance — so
  ``SMOResult`` keeps the full-set optimality contract (``f`` globally
  consistent, ``converged`` judged on the full gap). A lane re-shrinks
  only while its full gap stays above ``10*tol``; ``UNSHRINK_LIMIT``
  bounds the cycle count.
* **bit-determinism** — the compact iterate sequence is a pure function
  of the active VALUES: pad rows can never win the masked reductions and
  their rank-2 garbage is dropped at scatter, so re-bucketing the same
  mask at a different cap (a resume under a different ``shrink_quantum``)
  replays bit-identical alphas. Heuristic evaluations happen at exact
  ``n_iter`` boundaries (pure functions of ``n_iter``, not of the chunk
  schedule), so a mid-shrink snapshot restored under a different schedule
  shape resumes the identical trajectory — provided both bucketing rules
  take the same shrink/no-shrink decisions (guaranteed when the active
  count stays below the coarser quantum's last bucket, the practical
  case; covered by tests/test_shrink.py).

The scheduler (``svm/scheduler.py``) drives this per lane through
:class:`LaneShrink` + :func:`advance`; :func:`solve_shrunk` is the solo
reference driver (bit-identical to a width-1 pool, same contract as
``engine.solve``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import obs
from repro.svm.engine import (EngineState, SMOResult, _INF, _sets, chunk_jit,
                              finalize, init_state, optimality)
from repro.svm.precision import kdot

#: heuristic cadence when shrinking is enabled without an explicit period
#: (``shrink_every="auto"`` resolves here when the cost model approves)
DEFAULT_SHRINK_EVERY = 1024

#: shrink/unshrink cycles per lane before the endgame pins to the full set
UNSHRINK_LIMIT = 4


# --------------------------------------------------------------- bucketing

def bucket_cap(m: int, quantum: int = 128) -> int:
    """Smallest ``quantum`` multiple >= ``m`` (>= one quantum) — the
    compact buffer capacity for an active count of ``m``. Mirrors
    ``seeding._bucket_cap``'s shape-bucketing so compile shapes stay
    O(n / quantum)."""
    q = max(int(quantum), 1)
    return -(-max(int(m), 1) // q) * q


def pick_cap(m: int, n: int, quantum: int = 128, caps=None) -> int | None:
    """Capacity bucket for ``m`` active of ``n`` rows, or ``None`` when
    compaction would not reduce the shape (bucket >= n, or no declared
    cap fits). ``caps`` restricts to a declared ladder — the smallest
    declared cap that fits wins (plans that must be exactly predictable
    by the static analyzer declare their ladder)."""
    m, n = int(m), int(n)
    if caps:
        fit = [int(c) for c in caps if m <= int(c) < n]
        return min(fit) if fit else None
    cap = bucket_cap(m, quantum)
    return cap if cap < n else None


def possible_caps(n: int, quantum: int = 128, caps=None) -> tuple[int, ...]:
    """Every compact capacity :func:`pick_cap` can produce for an
    ``n``-row source — the cap enumeration ``analysis/plan_check.py``
    maps onto jitted programs (the ``possible_widths`` pattern: kept next
    to the bucketing rule so prediction and execution cannot drift)."""
    n = int(n)
    if caps:
        return tuple(sorted({int(c) for c in caps if 0 < int(c) < n}))
    q = max(int(quantum), 1)
    return tuple(range(q, n, q))


# --------------------------------------------------------------- heuristic

@jax.jit
def active_set(alpha, f, y, train_mask, C):
    """(active, gap): the LIBSVM shrink heuristic against the current
    ``(b_up, b_low)`` estimates. A variable is bound-locked (inactive)
    when it can only move the objective away from the violating pair:
    in I_up only with ``f > b_low``, or in I_low only with ``f < b_up``.
    Free variables (in both sets) and the maximal violating pair always
    stay active; rows outside ``train_mask`` never are."""
    i_up, i_low = _sets(alpha, y, train_mask, C)
    has = jnp.any(i_up) & jnp.any(i_low)
    b_up = jnp.min(jnp.where(i_up, f, _INF))
    b_low = jnp.max(jnp.where(i_low, f, -_INF))
    gap = jnp.where(has, b_low - b_up, -_INF)
    locked = (i_up & ~i_low & (f > b_low)) | (i_low & ~i_up & (f < b_up))
    return train_mask & ~locked, gap


def seed_active_mask(alpha0, f0, y, train_mask, C):
    """Initial active-candidate mask for a seeded lane (the seeding ->
    shrinking handoff): bound-locked seeded alphas start shrunk, so an
    ATO/MIR/SIR-seeded lane begins compact instead of re-deriving the set
    after its first ``shrink_every`` iterations. Re-exported by
    ``core/seeding.py``; the pool applies it at admission when
    ``shrink_on_seed`` is set."""
    active, _ = active_set(alpha0, f0, y, train_mask, C)
    return active


@jax.jit
def _gap_of(alpha, f, y, mask, C):
    return optimality(alpha, f, y, mask, C)[2]


# ----------------------------------------------------------- reconstruction

@jax.jit
def _dense_f(K, y, alpha):
    return kdot(K, alpha * y) - y


def reconstruct_f(source, y, alpha):
    """Full-set ``f = K @ (alpha*y) - y`` for unshrinking: the dense ``K``
    when the source holds one, else the streaming ``matvec`` slab path
    (``PallasRBF``/``OnDemandRBF`` — O(block*n) transient, never n^2)."""
    K = getattr(source, "K", None)
    if K is not None:
        return _dense_f(K, y, alpha)
    mv = getattr(source, "matvec", None)
    if callable(mv):
        return mv(alpha * y) - y
    raise ValueError("source has neither K nor matvec; cannot reconstruct "
                     "f to unshrink")


# ------------------------------------------------------------- lane ledger

class LaneShrink:
    """Host-side shrink ledger for ONE lane: the active mask, the bucketed
    compact buffer (indices, operands, state), and the lifecycle flags.
    The full-shape ``EngineState`` mirror stays with the caller (the
    pool's ``lane.state``); :func:`advance` keeps it fresh by scattering
    the compact state back after every chunk — alpha and the *active*
    rows of f are always current, inactive f goes stale until
    reconstruction (exactly LIBSVM's contract)."""

    def __init__(self, n: int, *, every: int, quantum: int = 128,
                 caps=None, unshrink_limit: int = UNSHRINK_LIMIT):
        self.n = int(n)
        self.every = max(int(every), 1)
        self.quantum = int(quantum)
        self.caps = tuple(int(c) for c in caps) if caps else None
        self.unshrink_limit = int(unshrink_limit)
        self.active = None            # (n,) bool — None until first shrink
        self.cap = 0                  # compact capacity; 0 = unshrunk
        self.m = 0                    # live active count (<= cap)
        self.idx = None               # (cap,) int; pads = n (dropped)
        self.cmask = None             # (cap,) bool validity mask
        self.cy = None                # (cap,) compact labels
        self.csrc = None              # compact kernel source
        self.cstate = None            # compact EngineState
        self.no_shrink = False        # endgame: full-set polish only
        self.unshrinks = 0

    @property
    def shrunk(self) -> bool:
        return self.cap > 0

    def it_cap(self, n_iter: int, max_iter: int) -> int:
        """Iteration cap for the next dispatch: stop exactly at the next
        heuristic boundary — a pure function of ``n_iter``, NOT of the
        chunk schedule, so heuristic decisions land at identical
        iteration counts under any schedule shape (the resume
        contract)."""
        if self.no_shrink and not self.shrunk:
            return int(max_iter)
        boundary = (int(n_iter) // self.every + 1) * self.every
        return min(int(max_iter), boundary)

    def mark(self, active, m: int) -> bool:
        """Adopt an active mask from a full-set heuristic evaluation (or
        a restored snapshot); returns True when a (re)compaction is now
        pending — the gather itself is lazy (:meth:`enter` runs at the
        next dispatch, where a resolved source is in scope, so intake
        never forces a kernel into residency)."""
        cap = pick_cap(m, self.n, self.quantum, self.caps)
        if cap is None:
            return False
        self.active = jnp.asarray(active, bool)
        self.m = int(m)
        if self.shrunk and cap >= self.cap:
            return False
        self.cap = cap
        self.idx = None
        self.cstate = None
        return True

    def enter(self, source, y, full: EngineState) -> None:
        """Gather the compact subproblem from the full-state mirror:
        indices via the fixed-shape nonzero idiom (pads = n: gathers
        clamp to the last row — inert under ``cmask`` — and scatters
        drop them), operands via the source's ``compact`` gather."""
        idx = jnp.nonzero(self.active, size=self.cap,
                          fill_value=self.n)[0]
        self.idx = idx
        self.cmask = jnp.arange(self.cap) < self.m
        self.cy = y[idx]
        self.csrc = source.compact(idx)
        self.cstate = EngineState(full.alpha[idx], full.f[idx],
                                  full.n_iter, jnp.zeros((), bool))

    def scatter(self, full: EngineState) -> EngineState:
        """Write the compact state back into the full mirror (pads are
        out-of-range and dropped; valid indices are unique, so the
        scatter is deterministic and bit-exact)."""
        st = self.cstate
        return EngineState(
            full.alpha.at[self.idx].set(st.alpha, mode="drop"),
            full.f.at[self.idx].set(st.f, mode="drop"),
            st.n_iter, full.done)

    def tighten(self, active_c, m_new: int) -> None:
        """Apply a boundary re-evaluation INSIDE compact mode: the mask
        tightens in place (cmask &= heuristic — value-identical whether
        or not the buffer re-buckets, the cross-quantum determinism
        contract), and the buffer re-gathers only when the bucket
        actually drops (a pure perf move)."""
        self.cmask = self.cmask & active_c
        self.m = int(m_new)
        self.active = jnp.zeros(self.n, bool).at[self.idx].set(
            self.cmask, mode="drop")
        cap = pick_cap(self.m, self.n, self.quantum, self.caps)
        if cap is not None and cap < self.cap:
            self.cap = cap
            self.idx = None
            self.cstate = None

    def unshrink(self) -> None:
        self.cap = 0
        self.m = 0
        self.idx = self.cmask = self.cy = self.csrc = self.cstate = None
        self.active = None
        self.unshrinks += 1
        if self.unshrinks >= self.unshrink_limit:
            self.no_shrink = True


def seed_shrink(ls: LaneShrink, y, train_mask, C, state: EngineState, *,
                tol: float) -> None:
    """The admission-time handoff: evaluate the heuristic on the seeded
    (alpha0, f0). A lane already inside the ``10*tol`` endgame never
    shrinks (it would unshrink immediately); otherwise bound-locked
    seeded alphas start shrunk."""
    gap = float(_gap_of(state.alpha, state.f, y, train_mask,
                        jnp.asarray(C, state.alpha.dtype)))
    if math.isnan(gap) or gap <= 10.0 * tol:
        ls.no_shrink = True
        return
    active, _ = active_set(state.alpha, state.f, y, train_mask,
                           jnp.asarray(C, state.alpha.dtype))
    ls.mark(active, int(jnp.sum(active)))


def advance(ls: LaneShrink, source, y, train_mask, C, full: EngineState, *,
            tol: float, max_iter: int):
    """Post-chunk lifecycle for one shrink-enabled lane. Returns
    ``(full_state, verdict)`` with verdict ``"run"`` (keep dispatching)
    or ``"retire"`` (full-set converged, NaN-poisoned, or
    iteration-capped — the state is reconstructed and finalizable).

    Shrunk lane, chunk done: the compact dispatch ran at ``10*tol``, so
    ``done`` means the active gap closed (reconstruct + unshrink), the
    budget ran out (reconstruct + retire), or the next heuristic
    boundary was hit (tighten the mask against the compact
    ``(b_up, b_low)``). Unshrunk lane, chunk done: true convergence
    retires; a heuristic boundary evaluates the full-set mask and may
    enter compaction.
    """
    stol = 10.0 * tol
    if ls.shrunk:
        st = ls.cstate
        full = ls.scatter(full)
        with obs.span("repro.pool.wait"):
            done = bool(st.done)
        if not done:
            return full, "run"
        n_it = int(st.n_iter)
        Cd = jnp.asarray(C, st.alpha.dtype)
        gap_c = float(_gap_of(st.alpha, st.f, ls.cy, ls.cmask, Cd))
        if gap_c <= stol or math.isnan(gap_c) or n_it >= max_iter:
            # the active gap closed within 10*tol (or the budget ran
            # out): reconstruct f over the FULL set and unshrink — the
            # SMOResult contract is full-set optimality
            f_full = reconstruct_f(source, y, full.alpha)
            full = EngineState(full.alpha, f_full, st.n_iter,
                               jnp.zeros((), bool))
            ls.unshrink()
            gap = float(_gap_of(full.alpha, full.f, y, train_mask,
                                jnp.asarray(C, full.alpha.dtype)))
            if gap <= tol or math.isnan(gap) or n_it >= max_iter:
                return full._replace(done=jnp.ones((), bool)), "retire"
            if gap <= stol:
                ls.no_shrink = True    # endgame: polish the full set
            return full, "run"
        # heuristic boundary inside compact mode
        act_c, _ = active_set(st.alpha, st.f, ls.cy, ls.cmask, Cd)
        ls.cstate = st._replace(done=jnp.zeros((), bool))
        m_new = int(jnp.sum(act_c))
        if m_new < ls.m:
            ls.tighten(act_c, m_new)
        return full, "run"

    with obs.span("repro.pool.wait"):
        done = bool(full.done)
    if not done:
        return full, "run"
    n_it = int(full.n_iter)
    gap = float(_gap_of(full.alpha, full.f, y, train_mask,
                        jnp.asarray(C, full.alpha.dtype)))
    if gap <= tol or math.isnan(gap) or n_it >= max_iter:
        return full, "retire"
    full = full._replace(done=jnp.zeros((), bool))
    if ls.no_shrink:
        return full, "run"
    if gap <= stol:
        ls.no_shrink = True            # already in the endgame
        return full, "run"
    active, _ = active_set(full.alpha, full.f, y, train_mask,
                           jnp.asarray(C, full.alpha.dtype))
    ls.mark(active, int(jnp.sum(active)))
    return full, "run"


# ------------------------------------------------------------- solo driver

def solve_shrunk(source, y, train_mask, C, alpha0, f0, *, tol: float = 1e-3,
                 max_iter: int = 10_000_000, wss: str = "2",
                 chunk_iters: int = 4096,
                 shrink_every: int = DEFAULT_SHRINK_EVERY,
                 shrink_quantum: int = 128, shrink_caps=None,
                 shrink_on_seed: bool = True,
                 n_iter0: int = 0) -> SMOResult:
    """``engine.solve`` with active-set shrinking — the reference driver
    the pool's shrink path is bit-identical to (tests/test_shrink.py).
    ``shrink_every=0`` falls back to ``engine.solve`` verbatim. The
    result satisfies the same full-set contract as ``solve``: ``f`` is
    globally consistent (reconstructed at unshrink) and ``converged``
    reflects the full-set gap at ``tol``."""
    from repro.svm import engine
    if not shrink_every:
        return engine.solve(source, y, train_mask, C, alpha0, f0, tol=tol,
                            max_iter=max_iter, wss=wss,
                            chunk_iters=chunk_iters, n_iter0=n_iter0)
    state = init_state(train_mask, alpha0, f0, n_iter0=n_iter0)
    ls = LaneShrink(int(state.alpha.shape[0]), every=shrink_every,
                    quantum=shrink_quantum, caps=shrink_caps)
    if shrink_on_seed:
        seed_shrink(ls, y, train_mask, C, state, tol=tol)
    while True:
        if ls.cap and ls.idx is None:
            ls.enter(source, y, state)
        if ls.shrunk:
            it = ls.it_cap(int(ls.cstate.n_iter), max_iter)
            ls.cstate = chunk_jit(ls.csrc, ls.cy, ls.cmask, C, 10.0 * tol,
                                  jnp.asarray(it, jnp.int64), ls.cstate,
                                  n_iters=chunk_iters, wss=wss)
        else:
            it = ls.it_cap(int(state.n_iter), max_iter)
            state = chunk_jit(source, y, train_mask, C, tol,
                              jnp.asarray(it, jnp.int64), state,
                              n_iters=chunk_iters, wss=wss)
        state, verdict = advance(ls, source, y, train_mask, C, state,
                                 tol=tol, max_iter=max_iter)
        if verdict == "retire":
            return finalize(state, y, train_mask, C, tol)
