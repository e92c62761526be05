"""The one SMO engine: a single iteration core behind pluggable kernel-row
providers, chunked resumable dispatch, and batched fold execution.

Why one engine
--------------
The repo used to carry two divergent copies of the SMO iteration: the dense
LibSVM-parity solver (``smo.py``) and the sharded on-demand-kernel solver
(``distributed.py``). Every working-set-selection or update fix had to land
twice, and the CV driver could only use the dense copy, strictly one fold at
a time. This module hosts the WSS-1/WSS-2 selection, the box-clipped rank-2
update, and the duality-gap logic exactly once; ``smo.smo_solve`` and
``distributed.smo_iterations`` are thin wrappers over it (see DESIGN.md).

KernelSource protocol
---------------------
A kernel source answers "give me kernel row i" for the engine, plus the
scalar read / scatter-update idioms that match how the row is produced.
Sources also answer the *residency* half of the protocol — ``dtype``,
``fused`` and ``nbytes`` — which must stay cheap (no kernel compute): the
lane pool's source cache (``svm/sources.py``) types and sizes lanes from
those alone, and a ``KernelSpec`` factory answers them for a kernel that
has not been materialized yet:

* ``DenseKernel``  — precomputed K; direct indexing (the LibSVM-parity path).
* ``OnDemandRBF``  — recompute K[:, i] from X each iteration
  (``impl="gather"`` dynamic-slices x_i; ``impl="onehot"`` reads x_i and all
  scalars via one-hot contractions so the instance axis can stay sharded).
* ``FusedRBF``     — WSS-1 pair selection from f alone, then BOTH kernel
  rows in one pass over X (halves the dominant HBM stream).
* ``PallasRBF``    — FusedRBF's math as ONE fused Pallas launch per
  iteration (``kernels/smo_step.py``): kernel-row pair + rank-2 f-update
  in a single blocked pass over a feature-major copy of X, never
  materializing rows in HBM.
  ``streams_rows = True`` — the engine routes the f-update through
  ``update_f(f, i, j, delta)`` instead of asking for rows.
* ``ShardedRBF``   — OnDemandRBF/FusedRBF plus logical-axis sharding
  constraints for the production mesh (the old ``distributed.py`` path).

All sources are jax pytrees: array state (K or X) is traced, configuration
(gamma, impl) is static, so jit caches one executable per source kind.

Chunked dispatch
----------------
Instead of one monolithic ``lax.while_loop`` running to convergence, the
host dispatches jit'd chunks of ``chunk_iters`` iterations and inspects the
``done`` flag between chunks. The chunk is:

* the mid-fold checkpoint unit — ``solve(..., on_chunk=...)`` lets the CV
  driver snapshot (alpha, f, n_iter) between chunks, so recovery no longer
  loses an entire in-flight fold;
* the retry unit the distributed scheduler assumes (``smo_iterations`` is
  exactly one chunk).

Convergence is detected *inside* the chunk body (a converged state passes
through untouched), which makes the same body ``vmap``-safe for batched
execution: converged folds freeze while the rest keep iterating.

Precision
---------
Sources hand out kernel values in their own dtype (float32 under the
precision policy, ``svm/precision.py``); the engine upcasts every row and
diagonal it reads to the float64 state before any arithmetic, so alpha,
f, C and the gap are float64 whatever the source holds.

Bit-parity contract
-------------------
For a given source the engine replays the seed solvers' floating-point ops
in the same order, so ``smo_solve`` (DenseKernel) and ``smo_iterations``
(ShardedRBF) produce bit-identical alpha/f to the pre-engine implementations
(covered by tests/test_engine.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.rbf import auto_interpret
from repro.kernels.smo_step import feature_major, fused_smo_step
from repro.sharding import constrain
from repro.svm.precision import STATE_DTYPE, kdot

_INF = jnp.inf
_TAU = 1e-12
#: kernel rows per block of the row-streaming sources' ``matvec``
MATVEC_BLOCK = 2048
#: kernel columns ``matvec`` sums in float32 before float64 takes over
MATVEC_SPAN = 256

#: logical-axis rules for the sharded sources (instances over pod x data,
#: features over model) — re-exported by ``repro.svm.distributed``.
RULES = {
    "inst": ("pod", "data"),
    "feat": "model",
    None: None,
}


class SMOResult(NamedTuple):
    alpha: jnp.ndarray      # (n,) dual variables (0 outside train_mask)
    f: jnp.ndarray          # (n,) optimality indicators, globally consistent
    n_iter: jnp.ndarray     # () int64 — SMO iterations executed
    converged: jnp.ndarray  # () bool
    b_up: jnp.ndarray       # () min f over I_up at exit
    b_low: jnp.ndarray      # () max f over I_low at exit


class EngineState(NamedTuple):
    """Resumable solver state — the unit chunks pass between themselves,
    checkpoints serialize, and the lane pool stacks along axis 0.

    The lane helpers below are the batched-state vocabulary: the scheduler
    ``stack``s single-lane states into a packed batch and ``lane``-extracts
    them back at retirement or for a single-lane (sequential-program)
    dispatch; ``gather``/``scatter`` compact a batched state to a lane
    subset and write it back — for callers that edit a batch in place
    (e.g. reseeding a subset of grid lanes) rather than round-tripping
    through per-lane states.
    """
    alpha: jnp.ndarray
    f: jnp.ndarray
    n_iter: jnp.ndarray   # () int — updates applied so far
    done: jnp.ndarray     # () bool — converged or iteration-capped

    @staticmethod
    def stack(states: "list[EngineState]") -> "EngineState":
        """Pack single-lane states into a batched state (axis 0 = lane)."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def lane(self, i) -> "EngineState":
        """Extract lane ``i`` of a batched state as a single-lane state."""
        return jax.tree.map(lambda a: a[i], self)

    def gather(self, idx) -> "EngineState":
        """Compact a batched state to the lanes in ``idx`` (repacking)."""
        return jax.tree.map(lambda a: a[jnp.asarray(idx)], self)

    def scatter(self, idx, sub: "EngineState") -> "EngineState":
        """Write the lanes of ``sub`` back into positions ``idx``."""
        return jax.tree.map(lambda a, b: a.at[jnp.asarray(idx)].set(b),
                            self, sub)


def _sets(alpha, y, mask, C):
    """I_up / I_low membership (paper Eq. 4): I_up = I_u + I_m, I_low = I_l + I_m."""
    pos, neg = y > 0, y < 0
    at_lo, at_hi = alpha <= 0.0, alpha >= C
    i_up = mask & ~((pos & at_hi) | (neg & at_lo))
    i_low = mask & ~((pos & at_lo) | (neg & at_hi))
    return i_up, i_low


def _guarded_first(v, m, nan):
    """First index where ``v == m`` — or the first NaN index if any (NaN
    wins, as in ``jnp.argmin``/``argmax``) — always in range."""
    idx = jnp.arange(v.shape[0])
    first = jnp.min(jnp.where(v == m, idx, v.shape[0]))
    first_nan = jnp.min(jnp.where(nan, idx, v.shape[0]))
    out = jnp.where(jnp.any(nan), first_nan, first)
    return jnp.minimum(out, v.shape[0] - 1)


def _argmin(v):
    """First index of the minimum. Same selection (and tie-breaking: first
    occurrence) as ``jnp.argmin``, but built from plain min reduces — XLA's
    variadic argmin reduce is an order of magnitude slower on CPU, and
    catastrophically so when vmapped over a fold batch.

    NaN-guarded: the naive ``v == jnp.min(v)`` is all-False when v contains
    a NaN (min propagates it), which used to return ``v.shape[0]`` — an
    out-of-range index that jax's clamped gather silently turned into
    "always pick the last row", so the solver spun on a bogus pair instead
    of surfacing the bad state.
    """
    nan = jnp.isnan(v)
    return _guarded_first(v, jnp.min(jnp.where(nan, _INF, v)), nan)


def _argmax(v):
    """First index of the maximum; NaN-guarded like ``_argmin``."""
    nan = jnp.isnan(v)
    return _guarded_first(v, jnp.max(jnp.where(nan, -_INF, v)), nan)


def optimality(alpha, f, y, train_mask, C):
    """(b_up, b_low, gap) of a state; gap = -inf when a working pair cannot
    be formed (empty I_up or I_low)."""
    i_up, i_low = _sets(alpha, y, train_mask, C)
    has = jnp.any(i_up) & jnp.any(i_low)
    b_up = jnp.min(jnp.where(i_up, f, _INF))
    b_low = jnp.max(jnp.where(i_low, f, -_INF))
    gap = jnp.where(has, b_low - b_up, -_INF)
    return b_up, b_low, gap


# --------------------------------------------------------------------------
# kernel-row providers
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class DenseKernel:
    """Precomputed kernel matrix — today's LibSVM-parity hot path.

    Direct indexing (``v[i]`` / ``.at[i].add``) is the right idiom both
    solo and under ``vmap``: the one-hot contraction alternative (which the
    sharded sources use to keep the instance axis distributed) was measured
    ~1.8x slower per batched iteration on CPU — the extra (b, n) masked
    passes cost more than the batched gathers they replace.

    The rank-2 indicator update is the plain jnp expression: f is float64
    state, which no Pallas launch may take (``svm/precision.py``).
    """

    fused = False

    def __init__(self, K):
        self.K = K

    @property
    def dtype(self):
        return self.K.dtype

    @property
    def nbytes(self) -> int:
        """Bytes held resident by this source — what the kernel-source
        cache (svm/sources.py) accounts against its byte budget."""
        return int(self.K.nbytes)

    def diag(self):
        return jnp.diagonal(self.K)

    def row(self, i):
        return self.K[i]

    def rows2(self, i, j):
        return self.K[i], self.K[j]

    def read(self, v, i):
        return v[i]

    def update_alpha(self, alpha, i, j, y_i, y_j, delta):
        alpha = alpha.at[i].add(y_i * delta)
        return alpha.at[j].add(-y_j * delta)

    def update_f(self, f, K_i, K_j, delta):
        return f + delta * (K_i - K_j)

    def rows_at(self, idx):
        """Kernel row slab K[idx, :] — same eval/reconstruction surface as
        the row-streaming sources, directly indexed."""
        return self.K[jnp.asarray(idx)]

    def matvec(self, v):
        """``K @ v`` — the unshrink reconstruction path (`shrink.py`)."""
        return kdot(self.K, v)

    def compact(self, idx):
        """Active-set gather for the shrinking scheduler: the kernel
        restricted to rows/columns ``idx`` (pads — index n — clamp to the
        last row, inert under the compact validity mask)."""
        idx = jnp.asarray(idx)
        return DenseKernel(self.K[idx][:, idx])

    def constrain(self, v):
        return v

    def tree_flatten(self):
        return (self.K,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])


@jax.jit
def _rbf_block(Xr, Xc, sq_c, gamma):
    """RBF kernel values of the rows ``Xr`` against the columns ``Xc``
    (``sq_c`` their squared norms) -> (r, c), for ``rows_at`` and
    ``block``. One program, so the result is the only (r, c) buffer: run
    op by op, each of the expression's (r, c) temporaries stays allocated
    until the device has run its consumer, and the slab's evaluation held
    seven at once (11.5 GB for a Forest fold's 6,400 held-out rows of
    64,000 on a TPU v5e)."""
    d2 = jnp.maximum(jnp.sum(Xr * Xr, -1)[:, None] + sq_c[None]
                     - 2.0 * (Xr @ Xc.T), 0.0)
    return jnp.exp(-gamma * d2)


@jax.tree_util.register_pytree_node_class
class OnDemandRBF:
    """RBF kernel rows recomputed from X each iteration (K_ii = 1).

    ``impl="gather"``: x_i = X[i] — a dynamic-slice; on a 2D-sharded X the
    SPMD partitioner lowers this to large all-gathers.

    ``impl="onehot"``: x_i = onehot(i) @ X — a skinny matvec reducing over
    the *sharded instance axis*; scalar reads and the alpha scatter use the
    same trick, dropping collective bytes per iteration ~1000x.
    """

    def __init__(self, X, gamma: float, sq_norms=None, impl: str = "gather"):
        self.X = X
        self.gamma = gamma
        self.sq_norms = jnp.sum(X * X, axis=-1) if sq_norms is None else sq_norms
        self.impl = impl

    @property
    def dtype(self):
        return self.X.dtype

    @property
    def fused(self):
        return self.impl == "onehot_fused"

    def diag(self):
        return jnp.ones(self.X.shape[0], self.X.dtype)

    def row(self, i):
        X = self.X
        if self.impl.startswith("onehot"):
            oh = (jnp.arange(X.shape[0]) == i).astype(X.dtype)
            xi = oh @ X                                 # (d,) psum over inst
        else:
            xi = X[i]                                   # (d,) gathered row
        cross = X @ xi                                  # (n,) feature-axis psum
        d2 = jnp.maximum(self.sq_norms + jnp.sum(xi * xi) - 2.0 * cross, 0.0)
        return self.constrain(jnp.exp(-self.gamma * d2))

    def rows2(self, i, j):
        """Both kernel rows in ONE pass over X (the fused-WSS-1 trick:
        halves the dominant per-iteration HBM stream; WSS-1 needs ~10-30%
        more iterations than WSS-2 — net win when memory-bound)."""
        X = self.X
        oh2 = jnp.stack([(jnp.arange(X.shape[0]) == i).astype(X.dtype),
                         (jnp.arange(X.shape[0]) == j).astype(X.dtype)])
        xij = oh2 @ X                                   # (2, d) psum over inst
        cross = X @ xij.T                               # (n, 2): one X stream
        d2 = jnp.maximum(self.sq_norms[:, None] + jnp.sum(xij * xij, 1)[None]
                         - 2.0 * cross, 0.0)
        K2 = jnp.exp(-self.gamma * d2)
        return self.constrain(K2[:, 0]), self.constrain(K2[:, 1])

    def read(self, v, i):
        if self.impl.startswith("onehot"):
            return jnp.sum(jnp.where(jnp.arange(v.shape[0]) == i, v, 0))
        return v[i]

    def update_alpha(self, alpha, i, j, y_i, y_j, delta):
        if self.impl.startswith("onehot"):
            idx = jnp.arange(alpha.shape[0])
            return alpha + jnp.where(idx == i, y_i * delta, 0.0) \
                - jnp.where(idx == j, y_j * delta, 0.0)
        alpha = alpha.at[i].add(y_i * delta)
        return alpha.at[j].add(-y_j * delta)

    def update_f(self, f, K_i, K_j, delta):
        return f + delta * (K_i - K_j)

    def rows_at(self, idx):
        """Kernel row slab K[idx, :] -> (t, n) — the evaluation path for
        K-less sources: O(t*n) transient, never n^2 resident."""
        return _rbf_block(self.X[jnp.asarray(idx)], self.X, self.sq_norms,
                          self.gamma)

    def block(self, rows, cols):
        """Kernel block K[rows][:, cols] -> (r, c): ``rows_at``'s expression
        over the columns ``cols`` alone, what a seed transform reads of K
        (SIR's K[R, T]) without the (r, n) slab."""
        cols = jnp.asarray(cols)
        return _rbf_block(self.X[jnp.asarray(rows)], self.X[cols],
                          self.sq_norms[cols], self.gamma)

    def matvec(self, v, *, block: int = MATVEC_BLOCK):
        """Streaming ``K @ v`` (f0 of seeded lanes, objectives, unshrink
        reconstruction): kernel row blocks are formed and reduced
        immediately, O(block*n) transient memory. Each row's float32 sums
        run over ``MATVEC_SPAN`` columns and float64 sums over the rest
        (``kdot``'s ``span``): a seeded f0 sums some n/2 products of up to
        C, and float32 over all n of them would put its error at ~1e-3 by
        64,000 rows."""
        n, d = self.X.shape
        pad = (-n) % block
        Xb = jnp.pad(self.X, ((0, pad), (0, 0))).reshape(-1, block, d)
        sqb = jnp.pad(self.sq_norms, (0, pad)).reshape(-1, block)
        # columns padded to whole spans: finite kernel values times v's 0
        cpad = (-n) % MATVEC_SPAN
        Xc = jnp.pad(self.X, ((0, cpad), (0, 0)))
        sqc = jnp.pad(self.sq_norms, (0, cpad))
        vc = jnp.pad(v, (0, cpad))

        def one(args):
            xb, sb = args
            d2 = jnp.maximum(sb[:, None] + sqc[None] - 2.0 * (xb @ Xc.T),
                             0.0)
            return kdot(jnp.exp(-self.gamma * d2), vc, span=MATVEC_SPAN)

        return jax.lax.map(one, (Xb, sqb)).reshape(-1)[:n]

    def compact(self, idx):
        """Active-set gather for the shrinking scheduler: the same source
        kind over ``X[idx]`` (so a compact ``PallasRBF`` streams only the
        active bytes). Pads — index n — clamp to the last row, inert under
        the compact validity mask. Goes through the pytree so every
        subclass compacts with its own aux config intact."""
        children, aux = self.tree_flatten()
        idx = jnp.asarray(idx)
        return type(self).tree_unflatten(aux,
                                         tuple(c[idx] for c in children))

    def constrain(self, v):
        return v

    def tree_flatten(self):
        return (self.X, self.sq_norms), (self.gamma, self.impl)

    @classmethod
    def tree_unflatten(cls, aux, children):
        X, sq_norms = children
        gamma, impl = aux
        return cls(X, gamma, sq_norms, impl)


@jax.tree_util.register_pytree_node_class
class FusedRBF(OnDemandRBF):
    """One-pass two-row RBF evaluation; forces WSS-1 pair selection (the
    second index must come from f alone so both rows stream together)."""

    def __init__(self, X, gamma: float, sq_norms=None, impl: str = "onehot_fused"):
        super().__init__(X, gamma, sq_norms, impl="onehot_fused")


@jax.tree_util.register_pytree_node_class
class PallasRBF(OnDemandRBF):
    """Row-streaming RBF source over the fused Pallas step kernel.

    Holds X twice, row-major and feature-major (``nbytes`` counts both:
    O(n*d), not n² kernel bytes): each SMO iteration is one blocked pass
    over the feature-major ``Xt`` that computes the WSS-1 pair's kernel
    rows on the VPU and applies ``f += delta * (K_i - K_j)`` in the same
    launch (``kernels/smo_step.py``) — the rows never hit HBM. ``Xt`` is
    built once, here, padded to the launch's blocks, and travels with the
    source as a pytree child, so no chunk program transposes X. The
    row-major X serves the pair's two feature rows, read by index
    (``_pair``), not by the one-hot product the sharded sources use (X
    lives on one device, and the product would be a second pass over all
    of it), and ``rows_at``/``block``/``matvec``.
    ``streams_rows = True`` tells the engine to route the update
    through ``update_f(f, i, j, delta)`` / ``kij(i, j)`` instead of
    materializing rows; selection must therefore be WSS-1 (``fused``).

    Interpret-mode contract: on CPU (``interpret=None`` auto) with no
    ``bm``/``bk`` the kernel runs the parity configuration over the
    row-major X, so every op matches ``FusedRBF``'s jnp expression and
    alpha/f are bit-identical to ``FusedRBF``, solo and vmapped under the
    lane pool (tests/test_engine.py). Blocked launches — compiled ones,
    with blocks derived from (n, d) and VMEM
    (``kernels/smo_step.compiled_blocks``) unless ``bm``/``bk`` are given
    — read ``Xt``, need a float32 X (the precision policy), and carry the
    usual allclose guarantee only.
    """

    streams_rows = True

    def __init__(self, X, gamma: float, sq_norms=None,
                 impl: str = "onehot_fused", *, bm: int | None = None,
                 bk: int | None = None, interpret: bool | None = None,
                 Xt=None):
        super().__init__(X, gamma, sq_norms, impl="onehot_fused")
        self.bm = bm
        self.bk = bk
        self.interpret = auto_interpret(interpret)
        self.Xt = feature_major(X, bm, bk) if Xt is None else Xt

    @property
    def nbytes(self) -> int:
        """Resident bytes are X's two layouts — the whole point: the cache
        budget bounds rows-from-X sources by O(n*d), not O(n^2)."""
        return int(self.X.nbytes) + int(self.Xt.nbytes)

    def _pair(self, i, j):
        """The WSS pair's feature rows (2, d), read by index: 2·d floats,
        where ``rows2``'s one-hot product makes a second pass over all of
        X. The values are the same bits (the one-hot product at
        ``highest`` precision reproduces a row exactly); X is on one
        device here, so no sharded axis asks for the one-hot form. Two
        dynamic slices, not one gather: on a v5e the gather of the same
        two rows took 23 µs per SMO iteration, the slices and their stack
        under 1."""
        return jnp.stack([self.X[i], self.X[j]])

    def kij(self, i, j):
        """K[i, j] for the eta denominator without keeping a row around.

        Interpret mode reuses the inherited one-pass ``rows2`` expression
        so the scalar is bit-identical to FusedRBF's (the parity
        contract); compiled mode uses the O(d) pair-only evaluation.
        """
        if self.interpret:
            K_i, _ = self.rows2(i, j)
            return self.read(K_i, j)
        xij = self._pair(i, j)
        d2 = jnp.maximum(jnp.sum((xij[0] - xij[1]) ** 2), 0.0)
        return jnp.exp(-self.gamma * d2)

    def update_f(self, f, i, j, delta):
        xij = self._pair(i, j)
        return fused_smo_step(f, self.X, xij, self.sq_norms, delta,
                              gamma=self.gamma, bm=self.bm, bk=self.bk,
                              interpret=self.interpret, Xt=self.Xt)

    def compact(self, idx):
        """The active-set source over ``X[idx]`` (pads, index n, clamp to
        the last row), with its feature-major copy built anew: ``Xt``'s
        instances lie on its second axis, and its padding follows the
        compacted n."""
        idx = jnp.asarray(idx)
        return PallasRBF(self.X[idx], self.gamma, self.sq_norms[idx],
                         bm=self.bm, bk=self.bk, interpret=self.interpret)

    # rows_at / block / matvec (the eval-slab, seed-block and
    # streaming-matvec paths) are inherited from OnDemandRBF — the
    # expressions are row-streaming already, and sharing one definition
    # keeps the reconstruction path bit-identical across the RBF source
    # family.

    def tree_flatten(self):
        return (self.X, self.sq_norms, self.Xt), \
            (self.gamma, self.impl, self.bm, self.bk, self.interpret)

    @classmethod
    def tree_unflatten(cls, aux, children):
        X, sq_norms, Xt = children
        gamma, impl, bm, bk, interpret = aux
        return cls(X, gamma, sq_norms, impl, bm=bm, bk=bk,
                   interpret=interpret, Xt=Xt)


@jax.tree_util.register_pytree_node_class
class ShardedRBF(OnDemandRBF):
    """OnDemandRBF plus logical-axis sharding constraints — the production
    mesh path (instances over ("pod","data"), features over "model"). Off
    a mesh scope the constraints are no-ops, so the same source serves
    single-device tests and the 512-chip dry-run."""

    def constrain(self, v):
        return constrain(v, ("inst",), RULES)


# --------------------------------------------------------------------------
# the single iteration core
# --------------------------------------------------------------------------

def _step(source, y, train_mask, C, diag, tol, it_cap, wss, state):
    """One SMO iteration: WSS pair selection + box-clipped rank-2 update.

    A state that is already optimal (or iteration-capped) passes through
    bit-unchanged with ``done`` set — this is what makes the same body safe
    under ``vmap`` (converged folds freeze) and lets chunks over-dispatch
    without overshooting.
    """
    alpha, f, it, done = state
    i_up, i_low = _sets(alpha, y, train_mask, C)
    has = jnp.any(i_up) & jnp.any(i_low)
    b_up = jnp.min(jnp.where(i_up, f, _INF))
    b_low = jnp.max(jnp.where(i_low, f, -_INF))
    gap = jnp.where(has, b_low - b_up, -_INF)
    # a NaN gap (NaN in f on an active row) can never satisfy gap <= tol, so
    # the solver would burn max_iter on a poisoned state; halt instead and
    # let finalize report converged=False (the bad state surfaces)
    done = done | (gap <= tol) | (it >= it_cap) | jnp.isnan(gap)

    # --- select i: minimal f over I_up ---
    i = _argmin(jnp.where(i_up, f, _INF))
    f_i = source.read(f, i)
    streams = getattr(source, "streams_rows", False)
    if wss == "2":
        # LibSVM WSS-2: among j in I_low with f_j > f_i, maximise
        # (f_j - f_i)^2 / eta_j.
        K_i = source.row(i).astype(f.dtype)
        diff = f - f_i
        eta = jnp.maximum(source.read(diag, i) + diag - 2.0 * K_i, _TAU)
        gain = jnp.where(i_low & (diff > 0), diff * diff / eta, -_INF)
        j = _argmax(gain)
        K_j = source.row(j).astype(f.dtype)
    else:
        # WSS-1 (maximal violating pair): j from f alone, so fused sources
        # can evaluate both kernel rows in a single pass — and streaming
        # sources can defer them to the fused update launch entirely.
        j = _argmax(jnp.where(i_low, f, -_INF))
        if not streams:
            K_i, K_j = (r.astype(f.dtype) for r in source.rows2(i, j))

    # --- analytic 2-variable update, delta >= 0 along (+y_i, -y_j) ---
    f_j = source.read(f, j)
    a_i, a_j = source.read(alpha, i), source.read(alpha, j)
    y_i, y_j = source.read(y, i), source.read(y, j)
    # K[i,j] for the eta denominator: a scalar hook for streaming sources
    # (no row in scope), the hoisted row read otherwise (pure dataflow —
    # bit-identical to reading it inline below)
    K_ij = (source.kij(i, j).astype(f.dtype) if streams
            else source.read(K_i, j))
    eta_ij = jnp.maximum(source.read(diag, i) + source.read(diag, j)
                         - 2.0 * K_ij, _TAU)
    delta = (f_j - f_i) / eta_ij
    hi_i = jnp.where(y_i > 0, C - a_i, a_i)
    hi_j = jnp.where(y_j > 0, a_j, C - a_j)
    delta = jnp.maximum(jnp.minimum(jnp.minimum(delta, hi_i), hi_j), 0.0)
    alpha_new = source.update_alpha(alpha, i, j, y_i, y_j, delta)
    alpha_new = jnp.clip(alpha_new, 0.0, C)  # kill fp dust at the box boundary
    # rank-2 update keeps f consistent for ALL rows (incl. masked);
    # streaming sources fuse row computation into the update launch
    if streams:
        f_new = source.constrain(source.update_f(f, i, j, delta))
    else:
        f_new = source.constrain(source.update_f(f, K_i, K_j, delta))

    alpha = jnp.where(done, alpha, alpha_new)
    f = jnp.where(done, f, f_new)
    it = jnp.where(done, it, it + 1)
    return EngineState(alpha, f, it, done)


def smo_chunk(source, y, train_mask, C, state: EngineState, *,
              n_iters: int, wss: str = "2", tol: float = 1e-3,
              it_cap=None) -> EngineState:
    """Run up to ``n_iters`` SMO iterations from ``state``.

    Pure function of its inputs with static shapes — safe to jit, to chain
    (chunk N+1 continues chunk N's iterate sequence bit-exactly), and to
    ``vmap`` over a batch of states/masks. ``it_cap`` (traced) bounds total
    ``n_iter`` across chunks, so a tail chunk never needs a retrace.
    """
    if source.fused and wss == "2":
        raise ValueError("fused kernel sources evaluate both rows in one "
                         "pass and require WSS-1 (wss='1')")
    C = jnp.asarray(C, STATE_DTYPE)
    if it_cap is None:
        it_cap = jnp.iinfo(jnp.int32).max
    it_cap = jnp.asarray(it_cap, state.n_iter.dtype)
    diag = source.diag().astype(STATE_DTYPE)
    step = functools.partial(_step, source, y, train_mask, C, diag, tol,
                             it_cap, wss)

    def cond(carry):
        s, t = carry
        return (~s.done) & (t < n_iters)

    def body(carry):
        s, t = carry
        return step(s), t + 1

    state, _ = jax.lax.while_loop(cond, body, (state, jnp.zeros((), jnp.int32)))
    return state


@functools.partial(jax.jit, static_argnames=("n_iters", "wss"))
def chunk_jit(source, y, train_mask, C, tol, it_cap, state, n_iters, wss):
    """Jitted single-lane chunk — the dispatch unit of ``solve`` and the
    lane pool's width-1 (sequential-program) path."""
    return smo_chunk(source, y, train_mask, C, state, n_iters=n_iters,
                     wss=wss, tol=tol, it_cap=it_cap)


@functools.partial(jax.jit, static_argnames=("n_iters", "wss"))
def chunk_batched_jit(source, y, train_masks, Cs, tol, it_caps, states,
                      n_iters, wss):
    """One chunk over a batch of folds: a single top-level while_loop whose
    body vmaps ``_step`` over (train_mask, C, it_cap, state); source and y
    are shared across the batch. Per-fold convergence masking comes from the
    ``done`` freeze inside ``_step`` — a converged fold's state passes
    through bit-unchanged while stragglers keep iterating. (vmapping the
    body, not the while_loop, avoids the batching rule's second layer of
    full-state selects per iteration.) ``it_caps`` is per-lane — scheduler
    lanes carry their own iteration budgets — a scalar broadcasts."""
    it_caps = jnp.broadcast_to(jnp.asarray(it_caps, states.n_iter.dtype),
                               states.done.shape)
    diag = source.diag().astype(STATE_DTYPE)

    def one(mask, C, cap, state):
        return _step(source, y, mask, jnp.asarray(C, STATE_DTYPE), diag,
                     tol, cap, wss, state)

    def cond(carry):
        s, t = carry
        return jnp.any(~s.done) & (t < n_iters)

    def body(carry):
        s, t = carry
        return jax.vmap(one)(train_masks, Cs, it_caps, s), t + 1

    states, _ = jax.lax.while_loop(cond, body,
                                   (states, jnp.zeros((), jnp.int32)))
    return states


def stack_sources(sources):
    """Stack same-kind, same-shape kernel sources along a new leading lane
    axis (array leaves stack, static aux must agree) — the operand for
    ``chunk_batched_sources_jit``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *sources)


@functools.partial(jax.jit, static_argnames=("n_iters", "wss"))
def chunk_batched_sources_jit(sources, ys, train_masks, Cs, tol, it_caps,
                              states, n_iters, wss):
    """One chunk over a batch of lanes that each carry their OWN kernel
    operands: ``sources`` is a stacked source pytree (``stack_sources``,
    leading axis = lane) and ``ys`` is (b, n). This is the shrinking
    scheduler's compact-group program — every shrunk lane gathered its own
    active rows, so even lanes bucketed to the same ``(source, width,
    cap)`` program differ in operand *values*. vmap maps the source's
    array leaves (K or X) per lane and closes over the shared static
    config, so one program serves the whole bucket."""
    it_caps = jnp.broadcast_to(jnp.asarray(it_caps, states.n_iter.dtype),
                               states.done.shape)

    def one(src, y, mask, C, cap, state):
        return _step(src, y, mask, jnp.asarray(C, STATE_DTYPE),
                     src.diag().astype(STATE_DTYPE), tol, cap, wss, state)

    def cond(carry):
        s, t = carry
        return jnp.any(~s.done) & (t < n_iters)

    def body(carry):
        s, t = carry
        return jax.vmap(one)(sources, ys, train_masks, Cs, it_caps, s), t + 1

    states, _ = jax.lax.while_loop(cond, body,
                                   (states, jnp.zeros((), jnp.int32)))
    return states


# --------------------------------------------------------------------------
# single solve
# --------------------------------------------------------------------------

def init_state(train_mask, alpha0, f0, n_iter0=0) -> EngineState:
    """Entry transform shared by every wrapper: zero alphas outside the
    training mask, cast to the float64 state dtype, reset the done flag."""
    alpha0 = jnp.where(train_mask, alpha0, 0.0)
    return EngineState(alpha0.astype(STATE_DTYPE), f0.astype(STATE_DTYPE),
                       jnp.asarray(n_iter0, jnp.int64), jnp.zeros((), bool))


def finalize(state: EngineState, y, train_mask, C, tol) -> SMOResult:
    """Close an ``EngineState`` into an ``SMOResult``: optimality is a pure
    function of (alpha, f), so finalizing a restored snapshot reproduces the
    pre-crash result exactly (the lane pool and the Study resume rely on
    this)."""
    b_up, b_low, gap = optimality(state.alpha, state.f, y, train_mask, C)
    return SMOResult(alpha=state.alpha, f=state.f, n_iter=state.n_iter,
                     converged=gap <= tol, b_up=b_up, b_low=b_low)


def solve(source, y, train_mask, C, alpha0, f0, *, tol: float = 1e-3,
          max_iter: int = 10_000_000, wss: str = "2",
          chunk_iters: int | None = None, on_chunk=None,
          n_iter0: int = 0) -> SMOResult:
    """Solve the masked dual SVM to convergence over any kernel source.

    ``chunk_iters=None`` dispatches one chunk sized ``max_iter`` (a single
    device program, like the old monolithic solver). With ``chunk_iters=m``
    the host inspects ``done`` every m iterations and calls
    ``on_chunk(state)`` between chunks — the mid-fold checkpoint hook.
    ``n_iter0`` pre-loads the iteration counter when resuming a checkpointed
    partial solve, so ``n_iter`` accounting survives a restart.
    """
    state = init_state(train_mask, alpha0, f0, n_iter0=n_iter0)
    n = chunk_iters if chunk_iters is not None else max_iter
    # cap counts TOTAL updates incl. the pre-loaded n_iter0, so a resumed
    # solve stops exactly where the uninterrupted one would have
    it_cap = jnp.asarray(max_iter, jnp.int64)
    while True:
        state = chunk_jit(source, y, train_mask, C, tol, it_cap, state,
                          n_iters=n, wss=wss)
        if chunk_iters is None or bool(state.done):
            break
        if on_chunk is not None:
            on_chunk(state)
    return finalize(state, y, train_mask, C, tol)

