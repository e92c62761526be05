"""Alpha-seeding algorithms (the paper's contribution).

All seeders share one contract::

    alpha0 = seeder(K, y, C, prev, S_idx, R_idx, T_idx, ...)

where ``prev`` is the previous fold's ``SMOResult`` (its ``f`` is globally
consistent with its ``alpha`` for ALL instances — the solver maintains f for
masked rows too, see ``repro.svm.smo``), and the index arrays partition the
instance axis for the fold transition h -> h+1:

* ``S_idx`` — shared instances ((k-2) chunks),
* ``R_idx`` — removed (were in fold h's train set, become fold h+1's test),
* ``T_idx`` — added   (fold h's test set, join fold h+1's train set).

Every seeder returns ``alpha0`` that satisfies the box constraint
``0 <= alpha <= C`` and the equality constraint ``sum(y * alpha) = 0`` over
the NEW training set (S + T) — SMO's pairwise updates preserve the equality
constraint, so a violated start would never be repaired by the solver.

The constraint repair (paper §3 "Adjusting alpha'_T") is ``water_fill``:
uniformly shift beta = y*alpha by a scalar c, with box clipping, where c is
found by bisection on the monotone function sum(clip(beta - c)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.scipy.linalg

from repro import obs
from repro.svm.precision import STATE_DTYPE, kdot
from repro.svm.smo import SMOResult

_INF = jnp.inf


# --------------------------------------------------------------------------
# constraint repair
# --------------------------------------------------------------------------

def water_fill(beta: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
               target: jnp.ndarray, iters: int = 100) -> jnp.ndarray:
    """Return clip(beta - c, lo, hi) with scalar c s.t. the sum == target.

    ``sum(clip(beta - c, lo, hi))`` is monotone non-increasing in c, so c is
    found by bisection. ``target`` is clamped to the feasible [sum(lo),
    sum(hi)] first; callers handle any residual (see ``repair_equality``).
    """
    target = jnp.clip(target, jnp.sum(lo), jnp.sum(hi))
    c_lo = jnp.min(beta - hi) - 1.0   # => all at hi: sum maximal
    c_hi = jnp.max(beta - lo) + 1.0   # => all at lo: sum minimal

    def body(_, carry):
        c_lo, c_hi = carry
        c = 0.5 * (c_lo + c_hi)
        s = jnp.sum(jnp.clip(beta - c, lo, hi))
        too_big = s > target
        return jnp.where(too_big, c, c_lo), jnp.where(too_big, c_hi, c)

    c_lo, c_hi = jax.lax.fori_loop(0, iters, body, (c_lo, c_hi))
    c = 0.5 * (c_lo + c_hi)
    out = jnp.clip(beta - c, lo, hi)
    # final exact touch-up on the single freest coordinate to kill bisection
    # residue (keeps sum(y*alpha)=0 at fp-exact level for the solver)
    resid = target - jnp.sum(out)
    room = jnp.where(resid >= 0, hi - out, out - lo)
    j = jnp.argmax(room)
    fix = jnp.sign(resid) * jnp.minimum(jnp.abs(resid), room[j])
    return out.at[j].add(fix)


def _box(y: jnp.ndarray, C) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Box for beta = y * alpha: y=+1 -> [0, C]; y=-1 -> [-C, 0]."""
    lo = jnp.where(y > 0, 0.0, -C)
    hi = jnp.where(y > 0, C, 0.0)
    return lo, hi


def repair_equality(alpha0: jnp.ndarray, y: jnp.ndarray, C,
                    S_idx: jnp.ndarray, T_idx: jnp.ndarray) -> jnp.ndarray:
    """Make sum(y*alpha) over S+T exactly 0, touching T first (paper), and
    only spilling into S in the infeasible corner case (label-skewed folds).
    Both stages are no-ops when already satisfied."""
    beta = y * alpha0
    s_S = jnp.sum(beta[S_idx])
    lo_T, hi_T = _box(y[T_idx], C)
    beta_T = water_fill(beta[T_idx], lo_T, hi_T, -s_S)
    alpha0 = alpha0.at[T_idx].set(y[T_idx] * beta_T)
    # residual (only nonzero if -s_S was outside T's box-feasible range)
    resid = s_S + jnp.sum(beta_T)
    lo_S, hi_S = _box(y[S_idx], C)
    beta_S = water_fill(beta[S_idx], lo_S, hi_S, jnp.sum(beta[S_idx]) - resid)
    alpha0 = alpha0.at[S_idx].set(y[S_idx] * beta_S)
    return alpha0


def _bias(prev: SMOResult, y: jnp.ndarray, train_mask: jnp.ndarray, C) -> jnp.ndarray:
    """b with f_i = b on the free set (paper Constraint 5)."""
    free = train_mask & (prev.alpha > 0) & (prev.alpha < C)
    nf = jnp.sum(free)
    mean_f = jnp.sum(jnp.where(free, prev.f, 0.0)) / jnp.maximum(nf, 1)
    return jnp.where(nf > 0, mean_f, 0.5 * (prev.b_up + prev.b_low))


# --------------------------------------------------------------------------
# grid transitions: seed across adjacent C cells (same fold, same gamma)
# --------------------------------------------------------------------------

@jax.jit
def scale_seed_C(alpha: jnp.ndarray, y: jnp.ndarray, C_old, C_new,
                 train_mask: jnp.ndarray) -> jnp.ndarray:
    """Warm-start the (C_new, gamma) grid cell from the (C_old, gamma)
    solution of the SAME fold.

    Bounded SVs sit at alpha = C, and the bound scales linearly with C, so
    ``alpha * C_new / C_old`` is a strong predictor of the neighbour cell's
    solution (free SVs move less; SMO polishes them). Scaling preserves
    ``sum(y * alpha) = 0`` up to fp error; the water-fill repair makes it
    exact again after box clipping. Rows outside ``train_mask`` stay 0.

    This generalizes the paper's fold-chain warm start to the C axis of a
    hyper-parameter grid (see ``repro.core.grid``).
    """
    s = jnp.asarray(C_new, alpha.dtype) / jnp.asarray(C_old, alpha.dtype)
    beta = y * alpha * s
    lo, hi = _box(y, C_new)
    lo = jnp.where(train_mask, lo, 0.0)
    hi = jnp.where(train_mask, hi, 0.0)
    beta = water_fill(jnp.clip(beta, lo, hi), lo, hi, jnp.zeros((), alpha.dtype))
    return y * beta


# --------------------------------------------------------------------------
# cold start (the LibSVM baseline)
# --------------------------------------------------------------------------

def cold_seed(K, y, C, prev, S_idx, R_idx, T_idx, **_):
    return jnp.zeros_like(y, dtype=STATE_DTYPE)


# --------------------------------------------------------------------------
# MIR — Multiple Instance Replacement (paper Eq. 13-18, Algorithm 2)
# --------------------------------------------------------------------------

#: MIR's f32 ridge, relative to the mean diagonal of A^T A: large enough
#: to keep the Gram matrix positive definite in f32 (whose resolution is
#: ~1e-7 of that diagonal), small enough to leave the fit's well-determined
#: directions alone (DESIGN.md §Precision policy)
MIR_RIDGE = 1e-4

#: kernel columns per block of MIR's assembly: a block is one (n, 512)
#: column slab of K (123 MB at mnist's n = 60,000) and the (|T| + |R|, 512)
#: rows of it that the system reads
MIR_BLOCK = 512


def _mir_rhs(y, C, prev: SMOResult, S_idx, R_idx):
    """``(df, beta_R)`` of Eq. 17: df_i = b - f_i on I_u + I_l and 0 on
    I_m, over all n rows (only those of X = S + R are read), and the
    removed rows' beta = y * alpha."""
    X_idx = jnp.concatenate([S_idx, R_idx])
    alpha, f = prev.alpha, prev.f
    mask_prev = jnp.zeros(y.shape, bool).at[X_idx].set(True)
    b = _bias(prev, y, mask_prev, C)
    free = (alpha > 0) & (alpha < C)
    return jnp.where(free, 0.0, b - f), (y * alpha)[R_idx]


def _mir_start(beta_T, y, C, alpha, S_idx, R_idx, T_idx):
    """MIR's start from its least-squares ``beta_T``: alpha_S kept, the
    box and equality constraints repaired per the paper's AdjustAlpha."""
    beta_R = (y * alpha)[R_idx]
    lo, hi = _box(y[T_idx], C)
    beta_T = water_fill(jnp.clip(beta_T, lo, hi), lo, hi, jnp.sum(beta_R))
    alpha0 = jnp.zeros_like(alpha).at[S_idx].set(alpha[S_idx])
    alpha0 = alpha0.at[T_idx].set(y[T_idx] * beta_T)
    return repair_equality(alpha0, y, C, S_idx, T_idx)


def _ridge_solve(G, Atb):
    """``argmin ||A x - b||`` from its normal equations ``G = A^T A`` and
    ``Atb = A^T b``, ridge-regularized and solved through a Cholesky
    factor in ``G``'s dtype (f32), returned as f64 state. The TPU compiler
    aborts on f32 SVD (which ``lstsq`` needs), f64 ``lstsq`` compiles for
    minutes even at 3,001 x 300, and QR takes 90 s to compile at adult's
    32,561 x 3,256 — while the Cholesky compiles in about 20 s. A
    non-finite solve falls back to zeros, which the repair then fills."""
    lam = MIR_RIDGE * jnp.trace(G) / G.shape[0]
    G = G + lam * jnp.eye(G.shape[0], dtype=G.dtype)
    x = jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(G),
                                   Atb.astype(G.dtype))
    return jnp.where(jnp.isfinite(x), x, 0.0).astype(STATE_DTYPE)


@functools.partial(jax.jit, static_argnames=("block",))
def _mir_assemble(K, y, C, prev: SMOResult, S_idx, R_idx, T_idx, *,
                  block: int):
    """MIR's normal equations, ``G = A^T A + 1 1^T`` (|T|, |T|) in f32 and
    ``A^T rhs + sum(beta_R)`` (|T|,) in f64, for ``A = K[X, T]`` and
    ``rhs = df + K[X, R] @ beta_R`` over the previous training set
    X = S + R (``mir_seed``), streamed from K in blocks of ``block``
    consecutive kernel indices, so that no (|X|, |T|) slab exists.

    Block j is the column slab ``K[:, j*block:(j+1)*block]`` (one strided
    copy) and its rows T and R (a row gather); by the kernel's symmetry
    those are row block j of ``K[:, T]`` and ``K[:, R]``, and its rows
    outside X (T's own, and those a last block's start, clamped back into
    K, repeats) are masked."""
    n, t = y.shape[0], T_idx.shape[0]
    df, beta_R = _mir_rhs(y, C, prev, S_idx, R_idx)
    in_X = jnp.zeros(n, bool).at[S_idx].set(True).at[R_idx].set(True)
    TR = jnp.concatenate([T_idx, R_idx])

    def body(j, carry):
        G, Atb = carry
        start = jnp.minimum(j * block, n - block)
        cols = start + jnp.arange(block)
        rows = in_X[cols] & (cols >= j * block)
        seg = jax.lax.dynamic_slice_in_dim(K, start, block, axis=1)[TR]
        A_T = jnp.where(rows[None, :], seg[:t], 0.0)        # (|T|, block)
        rhs = jnp.where(rows, df[cols] + kdot(seg[t:].T, beta_R), 0.0)
        G = G + jnp.dot(A_T, A_T.T, precision=jax.lax.Precision.HIGHEST)
        return G, Atb + kdot(A_T, rhs)

    G, Atb = jax.lax.fori_loop(
        0, -(-n // block), body,
        (jnp.zeros((t, t), K.dtype), jnp.zeros(t, STATE_DTYPE)))
    # the equality constraint, one more row of ones in A
    return G + 1.0, Atb + jnp.sum(beta_R)


@jax.jit
def _mir_solve(G, Atb, y, C, alpha, S_idx, R_idx, T_idx):
    """MIR's least squares from its normal equations, then the repair."""
    return _mir_start(_ridge_solve(G, Atb), y, C, alpha, S_idx, R_idx, T_idx)


@jax.jit
def _mir_seed_lstsq(K, y, C, prev: SMOResult, S_idx, R_idx, T_idx):
    """``mir_seed`` for an f64 K, the reference path: the least-squares
    system built as slabs of K and solved by LAPACK-style ``lstsq``."""
    X_idx = jnp.concatenate([S_idx, R_idx])
    df, beta_R = _mir_rhs(y, C, prev, S_idx, R_idx)
    rhs = df[X_idx] + kdot(K[jnp.ix_(X_idx, R_idx)], beta_R)
    A = K[jnp.ix_(X_idx, T_idx)]
    # append the equality constraint as one more row of the LS system
    A_full = jnp.concatenate([A, jnp.ones((1, T_idx.shape[0]), K.dtype)], 0)
    rhs_full = jnp.concatenate([rhs, jnp.sum(beta_R)[None]], 0)
    beta_T = jnp.linalg.lstsq(A_full, rhs_full)[0]
    return _mir_start(beta_T, y, C, prev.alpha, S_idx, R_idx, T_idx)


def mir_seed(K, y, C, prev: SMOResult, S_idx, R_idx, T_idx):
    """Keep alpha_S; solve one least-squares system for alpha'_T.

    Eq. 17, divided through by y_i (Q_ij = y_i y_j K_ij), in terms of
    beta_t = y_t alpha'_t:   K[X,T] @ beta_T  =  df + K[X,R] @ beta_R
    plus the equality row    1^T beta_T       =  1^T beta_R
    with df_i = b - f_i on I_u + I_l and 0 on I_m (rows i over the previous
    training set X = S + R). The box/equality constraints are then
    repaired per the paper's AdjustAlpha.

    Over an f32 K the system is solved in two programs: the assembly of
    its normal equations, streamed from K in blocks (``_mir_assemble``,
    timed by the ``repro.seed.assemble`` span, synced), and the
    ridge-Cholesky solve with the repair (``_mir_solve``). Its result is
    only a start point: the f64 repair and the solver that follows fix
    the equality constraint and the optimum. An f64 K takes ``lstsq``
    over slabs of K (``_mir_seed_lstsq``).
    """
    if K.dtype == STATE_DTYPE:
        return _mir_seed_lstsq(K, y, C, prev, S_idx, R_idx, T_idx)
    n = y.shape[0]
    block = min(MIR_BLOCK, n)
    with obs.span("repro.seed.assemble", blocks=-(-n // block),
                  rows=int(S_idx.shape[0] + R_idx.shape[0])):
        G, Atb = jax.block_until_ready(_mir_assemble(
            K, y, C, prev, S_idx, R_idx, T_idx, block=block))
    return _mir_solve(G, Atb, y, C, prev.alpha, S_idx, R_idx, T_idx)


# --------------------------------------------------------------------------
# SIR — Single Instance Replacement (paper Eq. 19-21, Algorithm 3)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fallback",))
def sir_seed(K, y, C, prev: SMOResult, S_idx, R_idx, T_idx,
             rng_key: jax.Array | None = None, fallback: str = "random"):
    """Greedy replacement: each removed x_r inherits its alpha to the most
    similar (max kernel value) unused same-label x_t, followed by constraint
    repair.

    ``fallback`` controls the label-less case (no unused same-label x_t):

    * ``"random"`` — the paper's rule: a random unused pick. A sign-flipped
      beta lands on one coordinate; the repair then shifts every T beta.
    * ``"skip"`` — beyond-paper: drop that alpha and let the (uniform,
      diffuse) repair absorb the mass. Avoids poisoning single coordinates
      with large wrong-sign alphas, which SMO then diffuses over the whole
      free set.
    """
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    m = R_idx.shape[0]
    K_RT = K[R_idx][:, T_idx]
    same = (y[R_idx][:, None] == y[T_idx][None, :])
    alpha_R = prev.alpha[R_idx]
    priority = jax.random.uniform(rng_key, (T_idx.shape[0],), STATE_DTYPE)

    def body(r, carry):
        beta_T, used = carry
        scores = jnp.where(same[r] & ~used, K_RT[r], -_INF)
        t_best = jnp.argmax(scores)
        found = scores[t_best] > -_INF
        t_rand = jnp.argmax(jnp.where(~used, priority, -_INF))
        t = jnp.where(found, t_best, t_rand)
        any_free = jnp.any(~used)
        if fallback == "skip":
            write = any_free & found
        else:
            write = any_free
        beta_T = jnp.where(write,
                           beta_T.at[t].set(y[T_idx][t] * alpha_R[r]), beta_T)
        used = jnp.where(write, used.at[t].set(True), used)
        return beta_T, used

    beta_T, _ = jax.lax.fori_loop(
        0, m, body, (jnp.zeros(T_idx.shape[0], STATE_DTYPE),
                     jnp.zeros(T_idx.shape[0], bool)))

    lo, hi = _box(y[T_idx], C)
    beta_T = water_fill(jnp.clip(beta_T, lo, hi), lo, hi,
                        jnp.sum((y * prev.alpha)[R_idx]))
    alpha0 = jnp.zeros_like(prev.alpha).at[S_idx].set(prev.alpha[S_idx])
    alpha0 = alpha0.at[T_idx].set(y[T_idx] * beta_T)
    return repair_equality(alpha0, y, C, S_idx, T_idx)


# --------------------------------------------------------------------------
# ATO — Adjusting Alpha Towards Optimum (paper Eq. 7-11, Algorithm 1)
# --------------------------------------------------------------------------
#
# Two implementations share the per-step ramp/retire/graduate semantics:
#
# * ``ato_seed``     — fixed-shape ``lax.while_loop``: the dynamic M/T/R
#   index sets become boolean masks, the per-step least-squares system is a
#   bordered KKT solve over a padded working set, and the whole transition
#   (ramp + constraint repair) runs as ONE jitted device program with zero
#   host syncs inside the loop (see DESIGN.md §Jittable ATO).
# * ``ato_seed_ref`` — the eager host-side loop kept as the executable
#   reference (paper-faithful pinv least squares); the parity contract is
#   covered by tests/test_seeding.py.


def ato_seed_ref(K, y, C, prev: SMOResult, S_idx, R_idx, T_idx,
                 max_steps: int = 30, tol: float = 1e-3):
    """Karasuyama/Takeuchi-style incremental-decremental ramp (reference).

    Host-side loop (the working sets change size every step; the dense
    (1+|M|) x |M| pseudo-inverse dominates — exactly the cost profile the
    paper reports for ATO). Eager jnp ops; terminates when R is drained or
    after ``max_steps`` (then clamps alpha_R to 0, as the remaining mass is
    small) and always ends with the exact constraint repair.
    """
    y = jnp.asarray(y, STATE_DTYPE)
    alpha = prev.alpha.copy()
    f = prev.f.copy()
    n = y.shape[0]
    in_S, in_T, in_R = _transition_masks(n, S_idx, R_idx, T_idx)
    T_active = in_T
    R_active = in_R & (alpha > 0)
    alpha = jnp.where(in_T, 0.0, alpha)

    for _ in range(max_steps):
        if not bool(jnp.any(R_active)) and not bool(jnp.any(T_active)):
            break
        train_now = in_S | (in_T & ~T_active)
        free = train_now & (alpha > 0) & (alpha < C)
        b = (jnp.sum(jnp.where(free, f, 0.0)) / jnp.maximum(jnp.sum(free), 1)
             if bool(jnp.any(free)) else 0.5 * (prev.b_up + prev.b_low))

        M = jnp.where(free)[0]
        Tc = jnp.where(T_active)[0]
        Rc = jnp.where(R_active)[0]
        vT = C - alpha[Tc]                     # per-unit ramp-up of alpha_T
        vR = -alpha[Rc]                        # per-unit ramp-down of alpha_R
        # Phi = pinv([y_M; Q_MM]) [y_T y_R; Q_MT Q_MR] [C1-a_T; -a_R] (Eq.10)
        if M.size > 0:
            yM = y[M]
            Q_MM = (yM[:, None] * yM[None, :]) * K[M][:, M]
            Q_MT = (yM[:, None] * y[Tc][None, :]) * K[M][:, Tc]
            Q_MR = (yM[:, None] * y[Rc][None, :]) * K[M][:, Rc]
            A1 = jnp.concatenate([yM[None, :], Q_MM], 0)
            rhs = jnp.concatenate([(y[Tc] @ vT + y[Rc] @ vR)[None],
                                   Q_MT @ vT + Q_MR @ vR], 0)
            Phi = jnp.linalg.pinv(A1) @ rhs
        else:
            Phi = jnp.zeros((0,), STATE_DTYPE)
        # per-unit df (Eq. 11 divided by y_i): g_i = -sum_M y_m Phi_m K_im
        #   + sum_T y_t (C-a_t) K_it - sum_R y_r a_r K_ir
        g = kdot(K[:, Tc], y[Tc] * vT) + kdot(K[:, Rc], y[Rc] * vR)
        if M.size > 0:
            g = g - kdot(K[:, M], y[M] * Phi)
        # step size: smallest eta>0 putting some bound instance's f at b (Eq.5)
        bound = train_now & ~free
        safe_g = jnp.where(jnp.abs(g) > 1e-12, g, 1.0)
        etas = jnp.where(bound & (jnp.abs(g) > 1e-12), (b - f) / safe_g, _INF)
        etas = jnp.where(etas > 1e-12, etas, _INF)
        eta = float(jnp.minimum(jnp.min(etas), 1.0)) if etas.size else 1.0
        if not jnp.isfinite(eta):
            eta = 1.0
        # apply
        if M.size > 0:
            alpha = alpha.at[M].add(-eta * Phi)
        alpha = alpha.at[Tc].add(eta * vT)
        alpha = alpha.at[Rc].add(eta * vR)
        alpha = jnp.clip(alpha, 0.0, C)
        f = f + eta * g
        # retire drained R instances; graduate T instances that meet Eq. 5
        R_active = R_active & (alpha > 1e-12 * max(C, 1.0))
        fT, aT = f[Tc], alpha[Tc]
        ok_m = (aT > 0) & (aT < C) & (jnp.abs(fT - b) <= tol)
        ok_u = ((y[Tc] > 0) & (aT <= 0) | ((y[Tc] < 0) & (aT >= C))) & (fT >= b - tol)
        ok_l = ((y[Tc] > 0) & (aT >= C) | ((y[Tc] < 0) & (aT <= 0))) & (fT <= b + tol)
        T_active = T_active.at[Tc].set(~(ok_m | ok_u | ok_l))
        if eta >= 1.0:
            break

    alpha = jnp.where(in_R, 0.0, alpha)   # R must leave the training set
    return repair_equality(alpha, y, C, S_idx, T_idx)


def _bucket_cap(m: int, n: int) -> int:
    """Working-set pad for the ATO ramp: the smallest rung >= m of a
    ladder of multiples of 128 growing by ~sqrt(2) per rung (128, 256,
    384, 512, 768, 1024, 1536, 2048, 2944, 4096, 5888, 8192, ...),
    clamped to [1, n].

    Every distinct pad is one compiled ramp program, and on a TPU at
    adult's size that compile takes 40-75 s (mostly the bordered LU), so
    the rungs must be few: O(log n) per problem size, where plain
    multiples of 128 gave O(n / 128) — about one per fold transition. A
    rung pads the LU by at most sqrt(2) in width (~2.8x in flops)."""
    j = 0
    while True:
        cap = -(-int(128 * 2 ** (j / 2)) // 128) * 128
        if cap >= m:
            return max(1, min(cap, n))
        j += 1


#: widest ATO working set: a (4,097)-square bordered system is 134 MB in
#: f64 and compiles in ~40 s on a TPU; wider free sets take the coupled
#: selection (``_ato_ramp``)
ATO_MAX_M = 4096


def _ramp_cap(m: int, n: int) -> tuple[int, bool]:
    """(m_cap, coupled) for an exact working-set bound ``m``."""
    cap = _bucket_cap(m, n)
    return (cap, False) if cap <= ATO_MAX_M else (ATO_MAX_M, True)


def _bordered_solve(B, rhs, lu_dtype):
    """Solve the f64 bordered KKT system ``B x = rhs`` through an LU in
    ``lu_dtype``. Below f64 the solve takes one step of f64 iterative
    refinement against the f64 ``B`` (same factors, residual in f64), which
    recovers most of the digits the f32 factorization drops; an f64 LU (the
    reference path) is solved directly."""
    if lu_dtype == B.dtype:
        return jnp.linalg.solve(B, rhs)
    lu = jax.scipy.linalg.lu_factor(B.astype(lu_dtype))
    x = jax.scipy.linalg.lu_solve(lu, rhs.astype(lu_dtype)).astype(B.dtype)
    resid = rhs - B @ x
    return x + jax.scipy.linalg.lu_solve(
        lu, resid.astype(lu_dtype)).astype(B.dtype)


def _ato_ramp(K, y, C, alpha, f, b_fallback, in_S, in_T, in_R, tol,
              m_cap: int, max_steps: int, coupled: bool = False):
    """Fixed-shape ATO ramp: ``ato_seed_ref``'s loop with masks for the
    M/T/R sets and a bordered KKT solve for Phi. Pure traced function —
    jit- and vmap-safe.

    The free set M is always a subset of (initially-free S rows) + T: a
    bounded row's alpha never moves (only M/T-active/R-active alphas do), so
    it can never become free, while graduated T rows can. Callers therefore
    pad the working set to ``m_cap >= |free S at entry| + |T|``, which is
    exact — overflow is impossible, not just unlikely.

    ``coupled=True`` is for a bound above ``ATO_MAX_M`` (adult at its
    published size keeps ~90% of its rows free, so the exact pad is all of
    n: a 32,561-square bordered LU per step, 8.5 GB in f64, more than a
    16 GB chip holds beside K). M is then the ``m_cap`` free rows that the
    pure T/R ramp moves most (largest ``|K @ w|``); the other free rows
    keep their alphas for the ramp, and the solver that follows settles
    them. Seeding never moves the fixed point, only the iteration count.
    """
    n = y.shape[0]
    C = jnp.asarray(C, STATE_DTYPE)
    thresh = 1e-12 * jnp.maximum(C, 1.0)
    valid = jnp.arange(m_cap)
    # the bordered system is factored in the kernel dtype: LU has no f64
    # lowering on the TPU, and an f32 K carries no more than f32 anyway.
    # A ridge only regularizes if it sits above the factorization's
    # resolution: 1e-10 relative for f64, 100 ulps for f32.
    lu_dtype = K.dtype
    ridge = max(1e-10, 100 * float(jnp.finfo(lu_dtype).eps))

    def cond(carry):
        _alpha, _f, T_act, R_act, step, stop = carry
        return (step < max_steps) & ~stop & (jnp.any(R_act) | jnp.any(T_act))

    def body(carry):
        alpha, f, T_act, R_act, step, _ = carry
        train_now = in_S | (in_T & ~T_act)
        free = train_now & (alpha > 0) & (alpha < C)
        nf = jnp.sum(free)
        b = jnp.where(nf > 0,
                      jnp.sum(jnp.where(free, f, 0.0)) / jnp.maximum(nf, 1),
                      b_fallback)
        # ramp directions: T ramps up to C, R ramps down to 0 (per unit eta)
        v = jnp.where(T_act, C - alpha, 0.0) - jnp.where(R_act, alpha, 0.0)
        w = y * v
        # fixed-shape working set: indices of M padded to m_cap (padding
        # lanes gather row 0 but are masked out of every product below)
        if coupled:
            score = jnp.where(free, jnp.abs(kdot(K, w)), -1.0)
            idx = jax.lax.top_k(score.astype(jnp.float32), m_cap)[1]
            lane = valid < jnp.minimum(nf, m_cap)
        else:
            idx = jnp.nonzero(free, size=m_cap, fill_value=0)[0]
            lane = valid < nf
        yM = jnp.where(lane, y[idx], 0.0)
        Q = (yM[:, None] * yM[None, :]) * K[jnp.ix_(idx, idx)]
        # Bordered KKT system replacing the reference's pinv least squares
        # (Eq. 10): unknown (db, Phi) with the equality row enforced exactly
        #     [0    yM^T] [db ]   [sum(w)        ]
        #     [yM   Q_MM] [Phi] = [yM * (K_M: @ w)]
        # Padding lanes carry an identity diagonal and zero rhs (Phi = 0
        # there); a small relative ridge keeps the LU finite on duplicate
        # instances, and a non-finite solve falls back to Phi = 0 (pure
        # T/R ramp — the M-empty behaviour).
        lam = ridge * (1.0 + jnp.max(jnp.abs(jnp.diagonal(Q))))
        B = jnp.zeros((m_cap + 1, m_cap + 1), STATE_DTYPE)
        B = B.at[0, 0].set(jnp.where(nf > 0, 0.0, 1.0))
        B = B.at[0, 1:].set(yM)
        B = B.at[1:, 0].set(yM)
        B = B.at[1:, 1:].set(Q + jnp.diag(jnp.where(lane, lam, 1.0)))
        r0 = jnp.where(nf > 0, jnp.sum(w), 0.0)
        r = yM * kdot(K[idx], w)
        sol = _bordered_solve(B, jnp.concatenate([r0[None], r]), lu_dtype)
        Phi = jnp.where(lane & jnp.isfinite(sol[1:]), sol[1:], 0.0)
        Phi_full = jnp.zeros(n, STATE_DTYPE).at[idx].add(
            jnp.where(lane, Phi, 0.0))
        # per-unit df (Eq. 11 divided by y_i), one kernel matvec
        g = kdot(K, w - y * Phi_full)
        # step size: smallest eta>0 putting some bound instance's f at b
        bound = train_now & ~free
        live = jnp.abs(g) > 1e-12
        safe_g = jnp.where(live, g, 1.0)
        etas = jnp.where(bound & live, (b - f) / safe_g, _INF)
        etas = jnp.where(etas > 1e-12, etas, _INF)
        eta = jnp.minimum(jnp.min(etas), 1.0)
        eta = jnp.where(jnp.isfinite(eta), eta, jnp.ones((), STATE_DTYPE))
        # apply (M, T-active, R-active are disjoint: one fused update)
        alpha_new = jnp.clip(alpha + eta * (v - Phi_full), 0.0, C)
        f_new = f + eta * g
        # retire drained R instances; graduate T instances that meet Eq. 5
        R_new = R_act & (alpha_new > thresh)
        ok_m = (alpha_new > 0) & (alpha_new < C) & (jnp.abs(f_new - b) <= tol)
        ok_u = (((y > 0) & (alpha_new <= 0)) | ((y < 0) & (alpha_new >= C))) \
            & (f_new >= b - tol)
        ok_l = (((y > 0) & (alpha_new >= C)) | ((y < 0) & (alpha_new <= 0))) \
            & (f_new <= b + tol)
        T_new = T_act & ~(ok_m | ok_u | ok_l)
        return (alpha_new, f_new, T_new, R_new, step + 1, eta >= 1.0)

    carry = (jnp.where(in_T, 0.0, alpha), f, in_T, in_R & (alpha > 0),
             jnp.zeros((), jnp.int32), jnp.zeros((), bool))
    alpha, *_ = jax.lax.while_loop(cond, body, carry)
    return jnp.where(in_R, 0.0, alpha)   # R must leave the training set


@functools.partial(jax.jit,
                   static_argnames=("m_cap", "max_steps", "coupled"))
def _ato_seed_jit(K, y, C, alpha, f, b_fallback, in_S, in_T, in_R,
                  S_idx, T_idx, tol, *, m_cap, max_steps, coupled=False):
    out = _ato_ramp(K, y, C, alpha, f, b_fallback, in_S, in_T, in_R, tol,
                    m_cap, max_steps, coupled)
    return repair_equality(out, y, jnp.asarray(C, STATE_DTYPE), S_idx, T_idx)


def _transition_masks(n, S_idx, R_idx, T_idx):
    in_T = jnp.zeros(n, bool).at[T_idx].set(True)
    in_R = jnp.zeros(n, bool).at[R_idx].set(True)
    in_S = jnp.zeros(n, bool).at[S_idx].set(True)
    return in_S, in_T, in_R


def ato_seed(K, y, C, prev: SMOResult, S_idx, R_idx, T_idx,
             max_steps: int = 30, tol: float = 1e-3):
    """Jittable ATO: ``ato_seed_ref``'s ramp as one fixed-shape device
    program (see ``_ato_ramp``). The single host sync below sizes the padded
    working set BEFORE the loop; everything else — including the constraint
    repair — runs on device.
    """
    y = jnp.asarray(y, STATE_DTYPE)
    n = y.shape[0]
    in_S, in_T, in_R = _transition_masks(n, S_idx, R_idx, T_idx)
    nf0 = int(jnp.sum(in_S & (prev.alpha > 0) & (prev.alpha < C)))
    m_cap, coupled = _ramp_cap(nf0 + int(T_idx.shape[0]), n)
    b_fb = 0.5 * (prev.b_up + prev.b_low)
    return _ato_seed_jit(K, y, C, prev.alpha, prev.f, b_fb, in_S, in_T, in_R,
                         S_idx, T_idx, tol, m_cap=m_cap,
                         max_steps=int(max_steps), coupled=coupled)


# --------------------------------------------------------------------------
# LOO baselines: AVG (DeCoste & Wagstaff 2000) and TOP (Lee et al. 2004)
# --------------------------------------------------------------------------

@jax.jit
def avg_seed_loo(K, y, C, alpha, t: jnp.ndarray):
    """Remove instance t; distribute beta_t = y_t alpha_t uniformly over the
    free set, iterating the spill of box-clipped excess (paper suppl.)."""
    beta = y * alpha
    resid = beta[t]
    beta = beta.at[t].set(0.0)
    lo, hi = _box(y, C)
    lo = lo.at[t].set(0.0)
    hi = hi.at[t].set(0.0)
    free0 = (alpha > 0) & (alpha < C)
    free0 = free0.at[t].set(False)

    def body(_, carry):
        beta, resid = carry
        room = jnp.where(resid >= 0, hi - beta, beta - lo)
        can = free0 & (room > 1e-15)
        d = jnp.maximum(jnp.sum(can), 1)
        share = resid / d
        add = jnp.clip(jnp.where(can, share, 0.0),
                       -(beta - lo), hi - beta)
        beta = beta + add
        return beta, resid - jnp.sum(add)

    beta, resid = jax.lax.fori_loop(0, 8, body, (beta, resid))
    alpha0 = y * water_fill(beta, lo, hi, 0.0)
    return alpha0


@jax.jit
def top_seed_loo(K, y, C, alpha, t: jnp.ndarray):
    """Remove instance t; spill beta_t into instances by descending kernel
    similarity K(x_j, x_t) until absorbed (paper suppl., TOP)."""
    beta = y * alpha
    resid = beta[t]
    beta = beta.at[t].set(0.0)
    lo, hi = _box(y, C)
    lo = lo.at[t].set(0.0)
    hi = hi.at[t].set(0.0)
    sim = K[:, t].at[t].set(-_INF)
    order = jnp.argsort(-sim)

    def body(i, carry):
        beta, resid = carry
        j = order[i]
        room = jnp.where(resid >= 0, hi[j] - beta[j], lo[j] - beta[j])
        take = jnp.clip(resid, jnp.minimum(room, 0.0), jnp.maximum(room, 0.0))
        return beta.at[j].add(take), resid - take

    beta, resid = jax.lax.fori_loop(0, y.shape[0] - 1, body, (beta, resid))
    return y * water_fill(beta, lo, hi, 0.0)


SEEDERS = {"cold": cold_seed, "ato": ato_seed, "ato_ref": ato_seed_ref,
           "mir": mir_seed, "sir": sir_seed}

# Seeding -> shrinking handoff (DESIGN.md §Shrinking): a seeded start is
# not just an alpha0 — it implies an initial ACTIVE-SET estimate. Rows the
# seeder left bound-locked against the seeded (b_up, b_low) can start
# shrunk instead of waiting shrink_every iterations to be discovered; the
# pool evaluates this at admission (``shrink_on_seed``) on every transform's
# output through the same heuristic the solver uses mid-run. Re-exported
# here so seeding-layer callers can inspect the mask a transform implies
# without importing the solver-side module.
from repro.svm.shrink import seed_active_mask  # noqa: E402,F401


# --------------------------------------------------------------------------
# named seed transforms — the Study API's admission vocabulary
# --------------------------------------------------------------------------
#
# A transform maps a retired lane's ``SMOResult`` to the next lane's start
# point under one shared contract::
#
#     alpha0 = TRANSFORMS[name](K, y, C, prev, **params)
#
# where (K, y) come from the depending lane's kernel source, C is ITS box
# bound, and ``params`` are the plan-declared keyword arguments (index
# sets, the neighbour C, the held-out instance...). Plans reference
# transforms BY NAME (plus params) instead of closures, so a lane graph is
# data: it can be rebuilt identically on resume, and the same edge
# description works for fold chains, C-adjacent grid warm starts and LOO
# rounds. ``repro.core.study`` finishes the admission by computing
# ``f0 = init_f(K, y, alpha0)``.

TRANSFORMS: dict[str, callable] = {}


def register_transform(name: str):
    """Register a seed transform under ``name`` (see TRANSFORMS above)."""
    def deco(fn):
        TRANSFORMS[name] = fn
        return fn
    return deco


@register_transform("fold")
def fold_transform(K, y, C, prev, *, method, S_idx, R_idx, T_idx):
    """The paper's fold-transition seeders by name: ``method`` picks the
    SEEDERS entry (ato / ato_ref / mir / sir / cold), the index sets
    describe the h-1 -> h transition (module docstring)."""
    return SEEDERS[method](K, y, C, prev, S_idx, R_idx, T_idx)


@register_transform("scale_C")
def scale_C_transform(K, y, C, prev, *, C_old, train_mask):
    """C-adjacent grid warm start: scale the (C_old, gamma) solution of the
    SAME fold to this lane's C (``scale_seed_C``)."""
    return scale_seed_C(prev.alpha, y, C_old, C, train_mask)


#: scale_C never touches K, so the Study API admits it on K-less
#: (row-streaming) sources, deriving f0 from the source's streaming matvec
scale_C_transform.kernel_free = True


@register_transform("loo_avg")
def loo_avg_transform(K, y, C, prev, *, t):
    """LOO round entry (DeCoste & Wagstaff AVG): remove instance ``t`` from
    ``prev``'s solution, spreading its mass over the free set."""
    return avg_seed_loo(K, y, C, prev.alpha, jnp.asarray(t))


@register_transform("loo_top")
def loo_top_transform(K, y, C, prev, *, t):
    """LOO round entry (Lee et al. TOP): spill instance ``t``'s mass by
    descending kernel similarity."""
    return top_seed_loo(K, y, C, prev.alpha, jnp.asarray(t))
