"""Plain reference for the C-SVM dual that every cell solves.

Imports nothing of the program. It holds the problem as the configuration
states it (paper Problem (1), RBF kernel values in float32, solver state
in float64) and two plain pieces of code:

* ``check`` judges answers one by one: given a fold's alpha as the program
  returned it, it recomputes the gradient f = K (alpha * y) - y from X with
  its own kernel, and from it the optimality gap, the bias, the dual
  objective and the held-out predictions. The numbers it returns compare
  the program's answer with those;
* ``smo`` is a textbook SMO solve (maximal violating pair) over a dense K
  that ``kernel`` builds; ``smo_rows`` is the same solve with the pair's
  two kernel rows computed from X in every iteration, for a K that does
  not fit the chip. Over the kernel ``rbf_high`` (the matmul one step
  below the configuration's precision) either is the control: what an
  answer in a lower precision looks like to ``check``.

Kernel rows are computed in blocks of rows, so the check fits beside
nothing else on the chip once the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: rows of K computed at once by ``kv_product``
BLOCK = 1024


#: features summed per pass of ``sq_dist``'s loop
GROUP = 16


def sq_dist(A, B):
    """|a - b|^2 for rows of A (m, d) and B (n, d) in float32, summed from
    the differences feature by feature: no cancellation, so a distance
    near 0 (the diagonal) comes out near 0, not at float32 noise of
    |a|^2 + |b|^2."""
    d = A.shape[1]
    pad = (-d) % GROUP
    A = jnp.pad(A, ((0, 0), (0, pad)))
    B = jnp.pad(B, ((0, 0), (0, pad)))

    def body(g, acc):
        a = jax.lax.dynamic_slice_in_dim(A, g * GROUP, GROUP, axis=1)
        b = jax.lax.dynamic_slice_in_dim(B, g * GROUP, GROUP, axis=1)
        for c in range(GROUP):
            acc = acc + (a[:, c, None] - b[None, :, c]) ** 2
        return acc

    acc = jnp.zeros((A.shape[0], B.shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, (d + pad) // GROUP, body, acc)


def rbf(A, B, gamma):
    """float32 RBF kernel values exp(-gamma * |a - b|^2), (m, d) x (n, d)
    -> (m, n)."""
    return jnp.exp(-gamma * sq_dist(A, B))


def dot_3pass(A, B):
    """A @ B.T for float32 A, B as three bfloat16 products (hi*hi + hi*lo
    + lo*hi, float32 sums): what a TPU does for a float32 matmul at
    precision ``high``, written out so that it is the same on every
    backend."""
    a1 = A.astype(jnp.bfloat16)
    a2 = (A - a1.astype(jnp.float32)).astype(jnp.bfloat16)
    b1 = B.astype(jnp.bfloat16)
    b2 = (B - b1.astype(jnp.float32)).astype(jnp.bfloat16)

    def mm(x, y):
        return jnp.dot(x, y.T, preferred_element_type=jnp.float32)

    return mm(a1, b1) + (mm(a1, b2) + mm(a2, b1))


def rbf_high(A, B, gamma):
    """The RBF kernel as the program forms it, |a|^2 + |b|^2 - 2 a.b, with
    the matmul at precision ``high``: the control's kernel, one step of
    precision below the configuration's (float32 at ``highest``)."""
    an = jnp.sum(A * A, axis=1)[:, None]
    bn = jnp.sum(B * B, axis=1)[None, :]
    return jnp.exp(-gamma * jnp.maximum(an + bn - 2.0 * dot_3pass(A, B), 0.0))


def _block_rows(n: int, most: int = 2048) -> int:
    """The largest divisor of n that is at most ``most``."""
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


@functools.partial(jax.jit, static_argnames=("fn", "block"),
                   donate_argnums=0)
def _put_rows(K, X, r0, gamma, fn, block):
    rows = jax.lax.dynamic_slice_in_dim(X, r0, block)
    return jax.lax.dynamic_update_slice_in_dim(K, fn(rows, X, gamma), r0, 0)


def kernel(X, gamma, fn=rbf):
    """The dense (n, n) float32 kernel ``fn`` of X, written block of rows
    by block of rows into one buffer that each call donates, so that the
    chip holds one K and one block (one program building all of K would
    hold a second K for its loop)."""
    X = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    block = _block_rows(n)
    K = jnp.zeros((n, n), jnp.float32)
    for r0 in range(0, n, block):
        K = _put_rows(K, X, r0, gamma, fn=fn, block=block)
    return K


#: ``kkt_excess`` of an alpha that is not feasible (finite, so that the
#: result line stays plain JSON)
INFEASIBLE = 1e9

#: columns of K summed in float32 before the partial sums go to float64
SPAN = 256


@functools.partial(jax.jit, static_argnames=("block", "fn"))
def _kv_block(Xp, X, V_hl, gamma, r0, block, fn):
    n, F2 = V_hl.shape
    Kb = fn(jax.lax.dynamic_slice_in_dim(Xp, r0, block), X, gamma)
    Kb = Kb.reshape(block, n // SPAN, SPAN)
    part = jnp.einsum("bcj,cjf->bcf", Kb, V_hl.reshape(n // SPAN, SPAN, F2),
                      precision=HIGHEST, preferred_element_type=jnp.float32)
    return jnp.sum(part.astype(jnp.float64), axis=1)


def kv_product(X, V, gamma, block: int = BLOCK, fn=rbf,
               rows=None) -> np.ndarray:
    """K @ V for the float32 kernel ``fn`` (``rbf`` by default) of X (n, d)
    and float64 V (n, F), as float64 (n, F) on the host; with ``rows``,
    only those rows of it, (len(rows), F). V is split into float32 high
    and low parts, so the product keeps V's digits; float32 sums run over
    SPAN columns at a time and float64 sums over the rest, so what is left
    is float32 accumulation over SPAN terms."""
    X = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    Xr = X if rows is None else X[jnp.asarray(rows)]
    m = Xr.shape[0]
    V = np.asarray(V, np.float64)
    hi = V.astype(np.float32)
    lo = (V - hi.astype(np.float64)).astype(np.float32)
    # zero columns of K (rows of V) past n add nothing
    pad = (-n) % SPAN
    V_hl = jnp.asarray(np.pad(np.concatenate([hi, lo], axis=1),
                              ((0, pad), (0, 0))))
    Xc = jnp.pad(X, ((0, pad), (0, 0)))
    block = min(block, m)
    Xp = jnp.pad(Xr, ((0, (-m) % block), (0, 0)))
    F = V.shape[1]
    out = np.empty((m, F))
    for r0 in range(0, m, block):
        r1 = min(r0 + block, m)
        o = np.asarray(_kv_block(Xp, Xc, V_hl, gamma, r0, block, fn))
        out[r0:r1] = o[: r1 - r0, :F] + o[: r1 - r0, F:]
    return out


def check(X, y, C, gamma, tol, folds) -> dict:
    """Compare each answer in ``folds`` with what the reference computes
    from its alpha, and return the worst of each number over the folds.

    A fold is a dict with ``alpha`` and ``f`` (n,), ``train`` (n,) bool,
    ``test`` (t,) indices, ``pred`` (t,) in {-1, +1} and ``objective``,
    all as the program returned them. The numbers:

    * ``kkt_excess``: how far the gap max_{I_low} f - min_{I_up} f of the
      answer's alpha, under the reference's f, exceeds ``tol``;
      ``INFEASIBLE`` where alpha leaves its box, is nonzero off the
      training rows, or breaks sum(alpha * y) = 0 by more than 1e-9 * C * n;
    * ``f_err``: the widest gap between the answer's f and the reference's;
    * ``obj_rel``: the answer's dual objective against the reference's
      objective of the same alpha, relative;
    * ``pred_mismatch``: held-out predictions that differ from the
      reference's, which takes its own bias (LIBSVM's rule: the mean of
      -f over free vectors) and decision values.

    A number that is not finite (an answer holding NaN) reads
    ``INFEASIBLE``.
    """
    y = np.asarray(y, np.float64)
    A = np.stack([np.asarray(fd["alpha"], np.float64) for fd in folds], 1)
    KV = kv_product(X, A * y[:, None], gamma)
    n = y.shape[0]
    per_fold = {"kkt_excess": [], "f_err": [], "obj_rel": [],
                "pred_mismatch": []}
    for c, fd in enumerate(folds):
        alpha, train = A[:, c], np.asarray(fd["train"], bool)
        test = np.asarray(fd["test"])
        f = KV[:, c] - y
        feasible = (np.all(alpha >= 0.0) and np.all(alpha <= C)
                    and not np.any(alpha[~train])
                    and abs(np.dot(alpha, y)) <= 1e-9 * C * n)
        pos, neg = y > 0, y < 0
        up = train & ((pos & (alpha < C)) | (neg & (alpha > 0)))
        low = train & ((pos & (alpha > 0)) | (neg & (alpha < C)))
        b_up, b_low = np.min(f[up]), np.max(f[low])
        excess = max(b_low - b_up - tol, 0.0) if feasible else INFEASIBLE
        free = train & (alpha > 0) & (alpha < C)
        b = -np.mean(f[free]) if free.any() else -(b_up + b_low) / 2.0
        pred = np.where(KV[test, c] + b >= 0, 1, -1)
        obj = np.sum(alpha) - 0.5 * np.dot(alpha * y, f + y)
        per_fold["kkt_excess"].append(excess)
        per_fold["f_err"].append(np.max(np.abs(
            np.asarray(fd["f"], np.float64) - f)))
        per_fold["obj_rel"].append(abs(float(fd["objective"]) - obj)
                                   / max(abs(obj), np.finfo(float).tiny))
        per_fold["pred_mismatch"].append(np.sum(np.asarray(fd["pred"]) != pred))
    out = {k: float(np.max(v)) for k, v in per_fold.items()}
    out["pred_mismatch"] = int(np.sum(per_fold["pred_mismatch"]))
    return {k: v if np.isfinite(v) else INFEASIBLE for k, v in out.items()}


def _mvp_loop(pair, y, train, C, tol, max_iter):
    """SMO from zero with the maximal violating pair, state in y's dtype;
    ``pair(i, j)`` gives the pair's kernel rows K_i, K_j and K_ii, K_jj in
    that dtype."""
    dtype = y.dtype
    n = y.shape[0]
    tau = jnp.asarray(1e-12, dtype)

    def sets(alpha):
        pos, neg = y > 0, y < 0
        up = train & ((pos & (alpha < C)) | (neg & (alpha > 0)))
        low = train & ((pos & (alpha > 0)) | (neg & (alpha < C)))
        return up, low

    def gap(alpha, f):
        up, low = sets(alpha)
        return (jnp.max(jnp.where(low, f, -jnp.inf))
                - jnp.min(jnp.where(up, f, jnp.inf)))

    def cond(s):
        alpha, f, it = s
        return (gap(alpha, f) > tol) & (it < max_iter)

    def body(s):
        alpha, f, it = s
        up, low = sets(alpha)
        i = jnp.argmin(jnp.where(up, f, jnp.inf))
        j = jnp.argmax(jnp.where(low, f, -jnp.inf))
        K_i, K_j, K_ii, K_jj = pair(i, j)
        eta = jnp.maximum(K_ii + K_jj - 2.0 * K_i[j], tau)
        delta = (f[j] - f[i]) / eta
        hi_i = jnp.where(y[i] > 0, C - alpha[i], alpha[i])
        hi_j = jnp.where(y[j] > 0, alpha[j], C - alpha[j])
        delta = jnp.clip(delta, 0.0, jnp.minimum(hi_i, hi_j))
        alpha = alpha.at[i].add(y[i] * delta).at[j].add(-y[j] * delta)
        alpha = jnp.clip(alpha, 0.0, C)
        return alpha, f + delta * (K_i - K_j), it + 1

    alpha0 = jnp.zeros(n, dtype)
    return jax.lax.while_loop(cond, body, (alpha0, -y, jnp.zeros((), jnp.int32)))


@functools.partial(jax.jit, static_argnames=("max_iter",))
def _smo(K, y, train, C, tol, max_iter):
    diag = jnp.diagonal(K).astype(y.dtype)

    def pair(i, j):
        return K[i].astype(y.dtype), K[j].astype(y.dtype), diag[i], diag[j]

    return _mvp_loop(pair, y, train, C, tol, max_iter)


@functools.partial(jax.jit, static_argnames=("fn", "max_iter"))
def _smo_rows(X, gamma, y, train, C, tol, fn, max_iter):
    def pair(i, j):
        R = fn(X[jnp.stack([i, j])], X, gamma).astype(y.dtype)
        return R[0], R[1], R[0, i], R[1, j]

    return _mvp_loop(pair, y, train, C, tol, max_iter)


def smo(K, y, train, C, tol, max_iter: int, dtype=jnp.float64):
    """Solve one fold from zero by SMO with the maximal violating pair, in
    state precision ``dtype``: returns (alpha, f, n_iter) in that dtype."""
    y = jnp.asarray(y, dtype)
    return _smo(K, y, jnp.asarray(train, bool), jnp.asarray(C, dtype),
                jnp.asarray(tol, dtype), max_iter=int(max_iter))


def smo_rows(X, gamma, fn, y, train, C, tol, max_iter: int):
    """``smo`` in float64 state over the float32 kernel ``fn`` of X (n, d)
    with no dense K: each iteration computes the pair's two kernel rows
    from X, as ``kernel`` computes every row, so the solve holds X and its
    state alone."""
    f64 = jnp.float64
    return _smo_rows(jnp.asarray(X, jnp.float32), gamma, jnp.asarray(y, f64),
                     jnp.asarray(train, bool), jnp.asarray(C, f64),
                     jnp.asarray(tol, f64), fn=fn, max_iter=int(max_iter))


def _bias_objective(y, alpha, f, train, C):
    """A solve's bias (LIBSVM's rule: the mean of -f over free vectors)
    and dual objective, in the solve's own precision."""
    free = train & (alpha > 0) & (alpha < C)
    b = -jnp.sum(jnp.where(free, f, 0.0)) / jnp.maximum(jnp.sum(free), 1)
    return b, jnp.sum(alpha) - 0.5 * jnp.dot(alpha * y, f + y)


def evaluate(K, test, y, alpha, f, train, C):
    """Held-out predictions and dual objective of a solve, in the solve's
    own precision: the control's counterpart of the program's evaluation.
    The decision values of all rows are taken from K in one product (a
    gather of K's test rows would not fit beside K on one chip at
    webdata's size), and the held-out rows ``test`` kept."""
    dtype = alpha.dtype
    y = jnp.asarray(y, dtype)
    b, obj = _bias_objective(y, alpha, f, train, C)
    v = alpha * y
    hi = v.astype(K.dtype)
    lo = (v - hi.astype(dtype)).astype(K.dtype)
    Kv = jnp.dot(K, jnp.stack([hi, lo], axis=1), precision=HIGHEST)
    dec = Kv[test, 0].astype(dtype) + Kv[test, 1].astype(dtype) + b
    pred = jnp.where(dec >= 0, 1, -1)
    return pred, obj


def evaluate_rows(X, gamma, fn, test, y, alpha, f, train, C):
    """``evaluate`` for a solve with no dense K (``smo_rows``): the
    held-out rows' decision values are their rows of K (alpha * y), taken
    from X in blocks of rows by ``kv_product`` over the solve's kernel
    ``fn``."""
    y = jnp.asarray(y, alpha.dtype)
    b, obj = _bias_objective(y, alpha, f, train, C)
    v = np.asarray(alpha * y, np.float64)
    dec = kv_product(X, v[:, None], gamma, fn=fn, rows=test)[:, 0] + float(b)
    return np.where(dec >= 0, 1, -1), obj
