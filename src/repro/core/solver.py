"""Iterative Krylov solvers for the even-odd preconditioned Wilson system.

All solvers are matrix-free (take a linear-operator callable), run under
``lax.while_loop`` so they jit/pjit cleanly, and treat pytrees of complex
arrays as vectors.  CGNR (CG on the normal equations) is the robust
workhorse for the non-Hermitian ``Dhat``; BiCGStab is the faster
alternative the paper's solver stack (QWS) uses in practice.

Two production features beyond the single-RHS f32 path:

* **Multi-RHS batching** — ``cg_batched`` / ``cgnr_batched`` /
  ``bicgstab_batched`` iterate a whole block of right-hand sides (leading
  ``nrhs`` axis) through ONE batched operator application per iteration,
  with *per-column* Krylov scalars and a per-column convergence mask:
  converged columns freeze (their updates are zeroed) while the loop runs
  until every column converged or ``max_iters``.
* **Block CG** — :func:`blockcg_batched` (``method="blockcg"``) upgrades
  the batched normal-equations solve from shared operator *traffic* to a
  shared Krylov *space*: small nrhs x nrhs Gram solves mix every search
  direction into every column, cutting iteration count on RHS blocks
  with overlapping spectral content.  Low-mode deflation / recycling of
  repeated solves on one gauge lives in :mod:`repro.core.deflate` and
  plugs in here as a Galerkin initial guess (``deflation=`` in
  :func:`_run_krylov` / ``deflated=`` in :func:`make_native_solve`).
  All normal-equations methods report the TRUE-system relative residual
  at exit (see :func:`_true_system_result` for the metric contract).
* **Mixed-precision iterative refinement** — :func:`make_refined_solve`
  (``SolveSpec(inner_dtype="f32")`` through the public API) runs the
  Krylov iteration in a cheap inner dtype (f32 default, bf16 optional)
  and wraps it in an f64 outer loop: true residual recomputed in f64,
  correction solved in the inner dtype, repeat until the *f64* tolerance
  is met.  The expensive f64 operator is applied once per outer pass
  instead of twice per Krylov iteration — the QWS / Kanamori-Matsufuru
  single-precision-inner strategy.
* **Compensated (f32-accumulate) reductions** — Krylov scalars of bf16
  vector domains are accumulated in f32 and cast back down at the axpy
  (see :data:`COMPENSATED_REDUCTIONS`), so ``inner_dtype="bf16"``
  converges at ``inner_tol`` values where naive bf16 accumulation
  stalls on saturated norms.
* **Divergence guards** (``guard=True``, the default) — every
  ``while_loop`` cond carries a non-finite check on the residual (the
  structural invariant analysis rule J6 asserts), so a poisoned state
  can never buy another iteration, and the loop body freezes a
  non-finite column/solve **bit-exactly** at its last finite iterate
  via ``where``-selects (the alpha-zeroing freeze alone cannot:
  ``0 * NaN = NaN``).  A residual that makes no new minimum for
  ``stagnation_window`` consecutive iterations triggers a
  deterministic restart — the Krylov space is re-seeded from the
  current iterate's true residual — up to ``max_restarts`` times,
  after which the column freezes.  Both paths report through the
  ``diverged`` field of :class:`SolveResult` instead of the old
  silent NaN exit whose ``converged`` came from a NaN comparison.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .lattice import EXACT


# Krylov scalars (<a,b>, |r|^2, alpha/beta/rho/omega) of sub-f32 vector
# domains (bf16 planar vectors of the mixed-precision inner solve)
# accumulate in f32.  A naive bf16 sum saturates once the partial sum
# reaches ~256 x the element magnitude (half-ulp rounding swallows every
# further term), so |b|^2 of a few-thousand-element vector is off by an
# order of magnitude and alpha/beta turn to noise — the solve stalls.
# f32 accumulation fixes the scalars while the vectors (and all the
# bandwidth-heavy operator work) stay bf16: the scalars are cast back to
# the leaf dtype at the axpy, never promoting the iterate.  Module-level
# so tests can flip it to demonstrate the stall.
COMPENSATED_REDUCTIONS = True

_LOW_PRECISION = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


def _acc(x):
    """Upcast a sub-f32 leaf to the f32 accumulation dtype (no-op for
    f32/f64/complex leaves, or with compensation disabled)."""
    if COMPENSATED_REDUCTIONS and x.dtype in _LOW_PRECISION:
        return x.astype(jnp.float32)
    return x


def _apply_scalar(alpha, leaf):
    """``alpha`` ready to multiply ``leaf`` without promoting it: an f32
    Krylov scalar meeting a bf16 leaf is cast *down* (bf16 stays the
    vector dtype; the scalar was merely accumulated more accurately)."""
    if not hasattr(alpha, "astype") or not hasattr(leaf, "dtype"):
        return alpha
    if jnp.result_type(alpha.dtype, leaf.dtype) != jnp.dtype(leaf.dtype):
        return alpha.astype(leaf.dtype)
    return alpha


def _vdot(a, b):
    leaves_a, leaves_b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return sum(jnp.vdot(_acc(x), _acc(y), precision=EXACT)
               for x, y in zip(leaves_a, leaves_b))


def _axpy(alpha, x, y):
    return jax.tree_util.tree_map(
        lambda xi, yi: _apply_scalar(alpha, yi) * xi + yi, x, y)


def _scale(alpha, x):
    return jax.tree_util.tree_map(lambda xi: alpha * xi, x)


def _norm2(x):
    return _vdot(x, x).real


# --- per-column (batched) vector algebra; leading axis = RHS index ------

def _bvdot(a, b):
    """Per-column ``<a, b>``: reduces every axis but the leading one
    (f32-accumulated for sub-f32 leaves, like :func:`_vdot`)."""
    leaves_a, leaves_b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    out = None
    for x, y in zip(leaves_a, leaves_b):
        x, y = _acc(x), _acc(y)
        s = jnp.sum((jnp.conj(x) * y).reshape(x.shape[0], -1), axis=1)
        out = s if out is None else out + s
    return out


def _bnorm2(x):
    return _bvdot(x, x).real


def _bb(alpha, leaf):
    """Broadcast a per-column scalar ``(nrhs,)`` against a leaf."""
    return alpha.reshape(alpha.shape + (1,) * (leaf.ndim - 1))


def _baxpy(alpha, x, y):
    """``y + alpha * x`` with a per-column ``alpha`` (cast down to the
    leaf dtype so an f32-accumulated scalar never promotes the batch)."""
    return jax.tree_util.tree_map(
        lambda xi, yi: _bb(_apply_scalar(alpha, xi), xi) * xi + yi, x, y)


# --- block (shared-Krylov) algebra; leading axis = RHS index -----------

def _bgram(a, b):
    """Block Gram matrix ``G[i, j] = <a_i, b_j>`` over the leading RHS
    axis (f32-accumulated for sub-f32 leaves, like :func:`_bvdot`)."""
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    out = None
    for x, y in zip(leaves_a, leaves_b):
        x, y = _acc(x), _acc(y)
        g = jnp.matmul(jnp.conj(x).reshape(x.shape[0], -1),
                       y.reshape(y.shape[0], -1).T, precision=EXACT)
        out = g if out is None else out + g
    return out


def _bcomb(coef, x, y=None):
    """Block column mixing ``y_j + sum_i coef[i, j] * x_i`` — the
    nrhs x nrhs direction-sharing step of block CG (``coef`` cast down
    like :func:`_baxpy` so an f32-accumulated Gram solve never promotes
    the batch)."""
    def leaf(xi, yi=None):
        c = _apply_scalar(coef, xi)
        upd = jnp.tensordot(c, xi, axes=((0,), (0,)), precision=EXACT)
        return upd if yi is None else upd + yi
    if y is None:
        return jax.tree_util.tree_map(leaf, x)
    return jax.tree_util.tree_map(leaf, x, y)


def _tiny(dtype):
    """Breakdown threshold: far below any meaningful Krylov scalar but
    above the denormal underflow that poisons the division chain."""
    real = jnp.finfo(jnp.zeros((), dtype).real.dtype)
    return real.tiny ** 0.5


def _nz(d, tiny):
    """Guard a denominator: the quotient is only *consumed* where
    ``|d| > tiny``, but a 0/0 in a dead lane would still produce a NaN
    that survives the masking multiply (``NaN * 0 = NaN``) — replace
    dead-lane denominators with 1 so every division is finite."""
    return jnp.where(jnp.abs(d) > tiny, d, jnp.ones_like(d))


# Divergence-guard defaults (see the module docstring): a column that
# makes no new residual minimum for a full window is stagnating; it gets
# this many deterministic restarts before freezing as diverged.
STAGNATION_WINDOW = 50
MAX_RESTARTS = 1

# Block CG replaces its recursive residual with the true residual at
# this cadence when the caller leaves recompute_every at 0 (see
# blockcg_batched: the orthonormalized recursion NEEDS reliable updates
# for a trustworthy convergence test; the other solvers keep 0 = never).
BLOCKCG_RECOMPUTE_DEFAULT = 50


def _swhere(flag, new, old):
    """Whole-solve freeze-select over a pytree: ``new`` where the scalar
    ``flag`` else ``old``.  The guard's bit-exact freeze — unlike the
    alpha-zeroing freeze, a ``where`` cannot be poisoned by a NaN on the
    rejected side (``0 * NaN = NaN`` would)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(flag, n, o), new, old)


def _bwhere(mask, new, old):
    """Per-column freeze-select: ``mask`` is ``(nrhs,)``, broadcast
    against every leaf of the batched pytrees."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(_bb(mask, n), n, o), new, old)


def _stagnation_reset(recompute_every, k, mask, rr1, best, since):
    """Re-baseline the stagnation window at a true-residual recompute.

    The ``recompute_every`` replacement is a drift *correction*: the
    recomputed ``|r|^2`` routinely reads higher than the stale recursive
    minimum the detector has been tracking, and feeding it into the
    ``best``/``since`` comparison as-is counts the correction as "no
    improvement" — iterations burn toward a spurious restart and, past
    ``max_restarts``, a false ``diverged`` on a perfectly healthy solve.
    At a recompute iteration the corrected residual IS the new baseline:
    reset ``best`` to it and the no-improvement counter to zero.
    ``mask`` limits the reset to columns that accepted the update
    (scalar ``True`` for the unbatched solvers).  Note the flip side:
    with ``recompute_every < stagnation_window`` the window can never
    fill between two corrections, so genuine stagnation is then judged
    per recompute interval (document, don't "fix" — the true residual
    is the more trustworthy signal).
    """
    if not recompute_every:
        return best, since
    recomp = jnp.logical_and((k + 1) % recompute_every == 0, mask)
    best = jnp.where(recomp, rr1, best)
    since = jnp.where(recomp, jnp.zeros_like(since), since)
    return best, since


class SolveResult(NamedTuple):
    x: jax.Array
    iterations: jnp.ndarray
    residual: jnp.ndarray      # relative residual |r| / |b|
    converged: jnp.ndarray
    # Divergence-guard verdict (scalar, or per-column for the batched
    # solvers): the state went non-finite or stagnated past the restart
    # budget and was frozen at its last good iterate.  Disjoint from
    # ``converged``; a plain breakdown freeze stays (False, False).
    diverged: jnp.ndarray = False


def _result(x, iters, rel, conv, div) -> SolveResult:
    """Assemble a SolveResult with the exit-time divergence fold: a
    non-finite *relative residual* is divergence even when the loop
    never tripped a guard (guard=False, or a NaN RHS whose column was
    never active) — the old silent-NaN exit reported ``converged`` from
    a NaN comparison instead."""
    div = jnp.logical_or(div, jnp.logical_not(jnp.isfinite(rel)))
    return SolveResult(x, iters, rel,
                       jnp.logical_and(conv, jnp.logical_not(div)), div)


def _col_field(v, lo: int, hi: int):
    """Slice a per-column result field to columns ``[lo, hi)``; scalar
    fields (an unbatched solve's iterations, the default ``diverged=
    False``) pass through unchanged."""
    if hasattr(v, "ndim") and getattr(v, "ndim", 0) >= 1:
        return v[lo:hi]
    return v


def split_columns(res, bounds):
    """Split a batched solve result back into per-request results.

    ``bounds`` is a sequence of ``(lo, hi)`` column ranges over the
    leading RHS axis — the serving layer's coalescing map (request i
    occupies columns ``lo_i..hi_i`` of the batch it rode in).  Every
    per-column field (``x``, ``iterations``, ``residual``,
    ``converged``, ``diverged``) is sliced, so each request gets back
    exactly its own columns' iteration counts, exit residuals, and
    convergence/divergence verdicts — the per-column freeze semantics
    make these independently meaningful (a frozen converged column is
    bit-identical to what an unshared solve of it would have kept).
    Works on :class:`SolveResult` and (duck-typed) on
    :class:`RefinedResult` — scalar bookkeeping fields
    (``outer_iterations``, ``f64_applies``, ...) are shared by the
    whole batch and pass through to every part.
    """
    parts = []
    for lo, hi in bounds:
        lo, hi = int(lo), int(hi)
        if lo < 0 or hi <= lo:
            raise ValueError(
                f"column bounds must be 0 <= lo < hi; got ({lo}, {hi})")
        parts.append(type(res)(*[
            _col_field(v, lo, hi) for v in res]))
    return parts


class RefinedResult(NamedTuple):
    """Result of a mixed-precision (iterative-refinement) solve.

    First four fields match :class:`SolveResult` so existing callers
    duck-type; the extras quantify the precision split: ``f64_applies``
    counts applications of the f64 operator (the pure-f64 solve pays
    ~2 per Krylov iteration; refinement pays 1 per outer pass), and
    ``inner_iterations`` the total inner-dtype Krylov iterations.
    ``escalations`` records each precision-escalation step the outer
    loop took (inner-dtype ladder rung names, in order) and
    ``diverged`` mirrors :class:`SolveResult`.
    """
    x: jax.Array
    iterations: jnp.ndarray
    residual: jnp.ndarray
    converged: jnp.ndarray
    outer_iterations: int
    f64_applies: int
    inner_iterations: int
    diverged: jnp.ndarray = False
    escalations: tuple = ()


def cg(op: Callable, b, x0=None, *, tol: float = 1e-6, max_iters: int = 1000,
       recompute_every: int = 0, guard: bool = True,
       stagnation_window: int = STAGNATION_WINDOW,
       max_restarts: int = MAX_RESTARTS,
       project: Optional[Callable] = None) -> SolveResult:
    """Conjugate gradients for a Hermitian positive-definite ``op``.

    ``recompute_every > 0`` replaces the recursively-updated residual
    with the true residual ``b - op(x)`` every that many iterations
    (inside the ``while_loop``), bounding floating-point drift on long
    solves (0 = never).  ``guard`` enables the divergence guard
    (non-finite freeze + stagnation restart, see the module docstring);
    ``guard=False`` keeps the bare recurrence for A/B overhead
    measurements and the J6 seeded-violation test.  ``project``
    (deflated CG; :func:`repro.core.deflate.make_projector`) is applied
    to the residual wherever a search direction is (re)built, keeping
    every direction A-orthogonal to the deflation subspace; ``None``
    keeps the recurrence bit-exactly undeflated.
    """
    proj = project if project is not None else (lambda v: v)
    x = x0 if x0 is not None else _scale(0.0, b)
    r = _axpy(-1.0, op(x), b)
    p = proj(r)
    rr = _norm2(r)
    b2 = _norm2(b)
    tiny = _tiny(rr.dtype)
    tol2 = (tol * tol) * b2

    def cond(state):
        x, r, p, rr, good, div, best, since, restarts, k = state
        go = jnp.logical_and(
            jnp.logical_and(rr > tol2, k < max_iters), good)
        if guard:
            # The non-finite guard lives in the COND (J6 asserts the
            # is_finite primitive here): a poisoned residual can never
            # buy another iteration.
            go = jnp.logical_and(go, jnp.logical_and(
                jnp.isfinite(rr), jnp.logical_not(div)))
        return go

    def body(state):
        x, r, p, rr, good, div, best, since, restarts, k = state
        ap = op(p)
        pap = _vdot(p, ap).real
        # Breakdown guard: pap ~ 0 (numerically nullspace direction)
        # would scale the update by garbage — freeze and exit instead.
        ok = pap > tiny
        alpha = jnp.where(ok, rr / _nz(pap, tiny), 0.0)
        x1 = _axpy(alpha, p, x)
        r1 = _axpy(-alpha, ap, r)
        if recompute_every:
            r1 = jax.lax.cond(
                (k + 1) % recompute_every == 0,
                lambda xk: _axpy(-1.0, op(xk), b),
                lambda _: r1, x1)
        rr1 = _norm2(r1)
        beta = rr1 / rr
        p1 = _axpy(beta, p, proj(r1))
        if not guard:
            return (x1, r1, p1, rr1, ok, div, best, since, restarts,
                    k + 1)
        # Non-finite freeze: keep the last finite iterate bit-exactly.
        finite = jnp.isfinite(rr1)
        x1 = _swhere(finite, x1, x)
        r1 = _swhere(finite, r1, r)
        p1 = _swhere(finite, p1, p)
        rr1 = jnp.where(finite, rr1, rr)
        div = jnp.logical_or(div, jnp.logical_not(finite))
        # Stagnation: no new residual minimum for a full window ->
        # deterministic restart (re-seed the Krylov space from the
        # current iterate's true residual); past the restart budget,
        # freeze and report diverged.
        improved = rr1 < best
        best = jnp.minimum(best, rr1)
        since = jnp.where(improved, 0, since + 1)
        best, since = _stagnation_reset(
            recompute_every, k, finite, rr1, best, since)
        stag = jnp.logical_and(finite, since >= stagnation_window)
        restart = jnp.logical_and(stag, restarts < max_restarts)

        def reseed(xk):
            rt = _axpy(-1.0, op(xk), b)
            return rt, _norm2(rt)

        r1, rr1 = jax.lax.cond(restart, reseed,
                               lambda _: (r1, rr1), x1)
        p1 = _swhere(restart, proj(r1), p1)
        best = jnp.where(restart, rr1, best)
        since = jnp.where(restart, 0, since)
        restarts = restarts + restart.astype(jnp.int32)
        div = jnp.logical_or(div, jnp.logical_and(
            stag, jnp.logical_not(restart)))
        return x1, r1, p1, rr1, ok, div, best, since, restarts, k + 1

    state = (x, r, p, rr, jnp.bool_(True), jnp.bool_(False), rr,
             jnp.int32(0), jnp.int32(0), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, state)
    x, rr, div, k = out[0], out[3], out[5], out[9]
    rel = jnp.sqrt(rr / jnp.maximum(b2, 1e-30))
    return _result(x, k, rel, rel <= tol, div)


def cg_batched(op: Callable, b, x0=None, *, tol: float = 1e-6,
               max_iters: int = 1000, recompute_every: int = 0,
               guard: bool = True,
               stagnation_window: int = STAGNATION_WINDOW,
               max_restarts: int = MAX_RESTARTS,
               project: Optional[Callable] = None) -> SolveResult:
    """Batched CG: one operator application per iteration for the whole
    RHS block, per-column scalars, per-column convergence freezing.

    A column whose residual reaches tolerance has its updates zeroed
    (``alpha = beta = 0``) from then on — its ``x``/``r`` are frozen
    bit-exactly while the remaining columns keep iterating.  With
    ``guard`` (default), a column that goes non-finite or stagnates
    past the restart budget freezes the same way and reports through
    the per-column ``diverged`` mask; healthy columns are untouched
    (all scalars are per-column, so their trajectories are independent
    of the poisoned one).  Returns per-column ``iterations`` /
    ``residual`` / ``converged`` / ``diverged``.  ``project`` is the
    (batched) deflation projector, applied as in :func:`cg`.
    """
    proj = project if project is not None else (lambda v: v)
    x = x0 if x0 is not None else _scale(0.0, b)
    r = b if x0 is None else _axpy(-1.0, op(x), b)
    p = proj(r)
    rr = _bnorm2(r)
    b2 = _bnorm2(b)
    tiny = _tiny(rr.dtype)
    tol2 = (tol * tol) * b2
    # A non-finite source column is never active (NaN > tol2 is False):
    # it sits at x = 0 with iters = 0 and exits through the diverged
    # fold in _result.
    active = rr > tol2
    iters = jnp.zeros(rr.shape, jnp.int32)
    div = jnp.logical_not(jnp.isfinite(rr)) if guard \
        else jnp.zeros(rr.shape, bool)

    def cond(state):
        x, r, p, rr, active, iters, div, best, since, restarts, k = state
        if guard:
            # Only columns with a finite residual can buy iterations
            # (per-column analogue of the scalar guard; J6 asserts the
            # is_finite primitive structurally).
            live = jnp.logical_and(active, jnp.isfinite(rr))
            return jnp.logical_and(jnp.any(live), k < max_iters)
        return jnp.logical_and(jnp.any(active), k < max_iters)

    def body(state):
        x, r, p, rr, active, iters, div, best, since, restarts, k = state
        ap = op(p)
        pap = _bvdot(p, ap).real
        # Breakdown guard: a (numerically) nullspace search direction
        # gives pap ~ 0 — freeze that column instead of scaling by a
        # garbage alpha (mirrors the bicgstab guards).
        ok = jnp.logical_and(active, pap > tiny)
        af = ok.astype(rr.dtype)
        alpha = af * rr / _nz(pap, tiny)
        x1 = _baxpy(alpha, p, x)
        r1 = _baxpy(-alpha, ap, r)
        if recompute_every:
            r1 = jax.lax.cond(
                (k + 1) % recompute_every == 0,
                lambda xk: _axpy(-1.0, op(xk), b),
                lambda _: r1, x1)
        rr1 = _bnorm2(r1)
        beta = af * rr1 / _nz(rr, tiny)
        p1 = _baxpy(beta, p, proj(r1))
        if guard:
            # Per-column freeze: only active columns whose new residual
            # stayed finite accept the update (where-select, so a NaN
            # column cannot leak through the zeroed-alpha arithmetic).
            finite = jnp.isfinite(rr1)
            accept = jnp.logical_and(active, finite)
            x1 = _bwhere(accept, x1, x)
            r1 = _bwhere(accept, r1, r)
            p1 = _bwhere(accept, p1, p)
            rr1 = jnp.where(accept, rr1, rr)
            newly_bad = jnp.logical_and(active, jnp.logical_not(finite))
            div = jnp.logical_or(div, newly_bad)
            # Per-column stagnation -> deterministic restart.
            improved = rr1 < best
            best = jnp.where(accept, jnp.minimum(best, rr1), best)
            since = jnp.where(
                accept, jnp.where(improved, 0, since + 1), since)
            best, since = _stagnation_reset(
                recompute_every, k, accept, rr1, best, since)
            stag = jnp.logical_and(accept, since >= stagnation_window)
            restart = jnp.logical_and(stag, restarts < max_restarts)
            exhausted = jnp.logical_and(stag, jnp.logical_not(restart))

            def reseed(args):
                xk, r_, p_, rr_ = args
                rt = _axpy(-1.0, op(xk), b)
                rt2 = _bnorm2(rt)
                return (_bwhere(restart, rt, r_),
                        _bwhere(restart, proj(rt), p_),
                        jnp.where(restart, rt2, rr_))

            r1, p1, rr1 = jax.lax.cond(
                jnp.any(restart), reseed,
                lambda a: (a[1], a[2], a[3]), (x1, r1, p1, rr1))
            best = jnp.where(restart, rr1, best)
            since = jnp.where(restart, 0, since)
            restarts = restarts + restart.astype(jnp.int32)
            div = jnp.logical_or(div, exhausted)
            active_new = jnp.logical_and(
                jnp.logical_or(ok, restart), rr1 > tol2)
            active_new = jnp.logical_and(
                active_new, jnp.logical_not(div))
        else:
            active_new = jnp.logical_and(ok, rr1 > tol2)
        leaving = jnp.logical_and(active, jnp.logical_not(active_new))
        iters = jnp.where(leaving, k + 1, iters)
        return (x1, r1, p1, rr1, active_new, iters, div, best, since,
                restarts, k + 1)

    state = (x, r, p, rr, active, iters, div, rr,
             jnp.zeros(rr.shape, jnp.int32),
             jnp.zeros(rr.shape, jnp.int32), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, state)
    x, rr, active, iters, div, k = (out[0], out[3], out[4], out[5],
                                    out[6], out[10])
    iters = jnp.where(active, k, iters)      # unconverged: ran to the end
    rel = jnp.sqrt(rr / jnp.maximum(b2, 1e-30))
    return _result(x, iters, rel, rel <= tol, div)


def _true_system_result(res, op, b, tol, batched) -> SolveResult:
    """Fold a normal-equations solve back to the TRUE-system metric.

    The convergence metric contract: every normal-equations solver
    (``cgnr``, ``method="cg"``, ``method="blockcg"``) *iterates* — and
    meets ``tol`` — in the normal-equation metric
    ``|A^dag r| / |A^dag b|``, but *reports* the true-system relative
    residual ``|b - A x| / |b|`` (one extra operator apply, outside the
    loop), so ``SolveResult.residual`` is comparable across every
    method and with the independent full-system check the CLI prints.
    The two metrics differ by up to a condition-number factor, hence
    the documented 10x slack on the exit-time ``converged`` test; the
    inner solve's divergence verdict carries over unchanged.
    """
    r = _axpy(-1.0, op(res.x), b)
    nrm = _bnorm2 if batched else _norm2
    rel = jnp.sqrt(nrm(r) / jnp.maximum(nrm(b), 1e-30))
    return _result(res.x, res.iterations, rel, rel <= tol * 10,
                   res.diverged)


def cgnr(op: Callable, op_dag: Callable, b, x0=None, *,
         tol: float = 1e-6, max_iters: int = 1000,
         recompute_every: int = 0, guard: bool = True,
         stagnation_window: int = STAGNATION_WINDOW,
         max_restarts: int = MAX_RESTARTS,
         project: Optional[Callable] = None) -> SolveResult:
    """CG on the normal equations ``op^dag op x = op^dag b``.

    Residual metric: iterates to ``tol`` in the normal-equation metric,
    reports the true-system relative residual (see
    :func:`_true_system_result`).
    """
    bn = op_dag(b)

    def normal(v):
        return op_dag(op(v))

    res = cg(normal, bn, x0, tol=tol, max_iters=max_iters,
             recompute_every=recompute_every, guard=guard,
             stagnation_window=stagnation_window,
             max_restarts=max_restarts, project=project)
    return _true_system_result(res, op, b, tol, batched=False)


def cgnr_batched(op: Callable, op_dag: Callable, b, x0=None, *,
                 tol: float = 1e-6, max_iters: int = 1000,
                 recompute_every: int = 0, guard: bool = True,
                 stagnation_window: int = STAGNATION_WINDOW,
                 max_restarts: int = MAX_RESTARTS,
                 project: Optional[Callable] = None) -> SolveResult:
    """Batched CGNR; per-column true residuals of the original system."""
    bn = op_dag(b)

    def normal(v):
        return op_dag(op(v))

    res = cg_batched(normal, bn, x0, tol=tol, max_iters=max_iters,
                     recompute_every=recompute_every, guard=guard,
                     stagnation_window=stagnation_window,
                     max_restarts=max_restarts, project=project)
    return _true_system_result(res, op, b, tol, batched=True)


def bicgstab(op: Callable, b, x0=None, *, tol: float = 1e-6,
             max_iters: int = 1000, recompute_every: int = 0,
             guard: bool = True,
             stagnation_window: int = STAGNATION_WINDOW,
             max_restarts: int = MAX_RESTARTS) -> SolveResult:
    """BiCGStab for general (non-Hermitian) ``op``.

    Works on any pytree vector domain: the Krylov scalars take the dtype
    of ``<b, b>`` (complex for complex spinors, real for planar-native
    vectors, where the operator is the real representation of ``Dhat``).
    ``recompute_every`` as in :func:`cg` (reliable-updates style
    true-residual replacement).

    Breakdown guards: BiCGStab's recurrence divides by ``rho``,
    ``<r0, v>`` and ``<t, t>`` (via ``omega``); any of them underflowing
    would turn the whole state into NaN inside the ``while_loop``.  Each
    is checked against a tiny threshold — on breakdown the update scalars
    are zeroed (state freezes at the last good iterate), the loop exits,
    and the result honestly reports the frozen residual with
    ``converged=False`` instead of NaN.

    The divergence guard (``guard``, default on) adds the non-finite
    cond check + bit-exact freeze, and a stagnation restart that
    re-seeds the *whole* Krylov space — shadow residual ``r0``, zeroed
    ``p``/``v``, unit scalars — from the current iterate's true
    residual.
    """
    x = x0 if x0 is not None else _scale(0.0, b)
    r = _axpy(-1.0, op(x), b)
    one = jnp.ones((), dtype=_vdot(b, b).dtype)
    tiny = _tiny(one.dtype)
    b2 = _norm2(b)
    rr0 = _norm2(r)
    tol2 = (tol * tol) * b2
    zero_v = _scale(0.0, b)

    def cond(state):
        (x, r, r0, p, v, rho, alpha, omega, rr, good, div, best,
         since, restarts, k) = state
        go = jnp.logical_and(
            jnp.logical_and(rr > tol2, k < max_iters), good)
        if guard:
            go = jnp.logical_and(go, jnp.logical_and(
                jnp.isfinite(rr), jnp.logical_not(div)))
        return go

    def body(state):
        (x, r, r0, p, v, rho, alpha, omega, rr, good, div, best,
         since, restarts, k) = state
        rho_new = _vdot(r0, r)
        ok = jnp.logical_and(jnp.abs(rho_new) > tiny,
                             jnp.logical_and(jnp.abs(rho) > tiny,
                                             jnp.abs(omega) > tiny))
        okc = ok.astype(rho_new.dtype)
        beta = okc * (rho_new / _nz(rho, tiny)) * (alpha / _nz(omega, tiny))
        p1 = _axpy(beta, _axpy(-omega, v, p), r)
        v1 = op(p1)
        r0v = _vdot(r0, v1)
        ok = jnp.logical_and(ok, jnp.abs(r0v) > tiny)
        okc = ok.astype(rho_new.dtype)
        alpha1 = okc * rho_new / _nz(r0v, tiny)
        s = _axpy(-alpha1, v1, r)
        t = op(s)
        tt = _vdot(t, t).real
        ok = jnp.logical_and(ok, tt > tiny)
        okc = ok.astype(rho_new.dtype)
        omega1 = okc * _vdot(t, s) / _nz(tt, tiny).astype(rho_new.dtype)
        x1 = _axpy(alpha1, p1, _axpy(omega1, s, x))
        r1 = _axpy(-omega1, t, s)
        if recompute_every:
            r1 = jax.lax.cond(
                (k + 1) % recompute_every == 0,
                lambda xk: _axpy(-1.0, op(xk), b),
                lambda _: r1, x1)
        rr1 = _norm2(r1)
        if not guard:
            return (x1, r1, r0, p1, v1, rho_new, alpha1, omega1, rr1,
                    ok, div, best, since, restarts, k + 1)
        # Non-finite freeze at the last finite iterate (bit-exact).
        finite = jnp.isfinite(rr1)
        x1 = _swhere(finite, x1, x)
        r1 = _swhere(finite, r1, r)
        p1 = _swhere(finite, p1, p)
        v1 = _swhere(finite, v1, v)
        rho1 = jnp.where(finite, rho_new, rho)
        alpha1 = jnp.where(finite, alpha1, alpha)
        omega1 = jnp.where(finite, omega1, omega)
        rr1 = jnp.where(finite, rr1, rr)
        div = jnp.logical_or(div, jnp.logical_not(finite))
        # Stagnation -> restart: fresh shadow residual, zeroed search
        # space, unit scalars, all seeded from the true residual.
        improved = rr1 < best
        best = jnp.minimum(best, rr1)
        since = jnp.where(improved, 0, since + 1)
        best, since = _stagnation_reset(
            recompute_every, k, finite, rr1, best, since)
        stag = jnp.logical_and(finite, since >= stagnation_window)
        restart = jnp.logical_and(stag, restarts < max_restarts)

        def reseed(xk):
            rt = _axpy(-1.0, op(xk), b)
            return rt, _norm2(rt)

        r1, rr1 = jax.lax.cond(restart, reseed,
                               lambda _: (r1, rr1), x1)
        r0 = _swhere(restart, r1, r0)
        p1 = _swhere(restart, zero_v, p1)
        v1 = _swhere(restart, zero_v, v1)
        rho1 = jnp.where(restart, one, rho1)
        alpha1 = jnp.where(restart, one, alpha1)
        omega1 = jnp.where(restart, one, omega1)
        best = jnp.where(restart, rr1, best)
        since = jnp.where(restart, 0, since)
        restarts = restarts + restart.astype(jnp.int32)
        div = jnp.logical_or(div, jnp.logical_and(
            stag, jnp.logical_not(restart)))
        # A restart also clears a same-iteration breakdown: the frozen
        # scalars were just re-seeded.
        good = jnp.logical_or(ok, restart)
        return (x1, r1, r0, p1, v1, rho1, alpha1, omega1, rr1, good,
                div, best, since, restarts, k + 1)

    state = (x, r, r, zero_v, zero_v, one, one, one, rr0,
             jnp.bool_(True), jnp.bool_(False), rr0, jnp.int32(0),
             jnp.int32(0), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, state)
    x, rr, div, k = out[0], out[8], out[10], out[14]
    rel = jnp.sqrt(rr / jnp.maximum(b2, 1e-30))
    return _result(x, k, rel, rel <= tol, div)


def bicgstab_batched(op: Callable, b, x0=None, *, tol: float = 1e-6,
                     max_iters: int = 1000, recompute_every: int = 0,
                     guard: bool = True,
                     stagnation_window: int = STAGNATION_WINDOW,
                     max_restarts: int = MAX_RESTARTS) -> SolveResult:
    """Batched BiCGStab with per-column convergence AND breakdown masks.

    Converged columns freeze (scalars zeroed, iterate kept bit-exact);
    broken-down columns freeze the same way but stay unconverged —
    ``converged[j] = False`` for them instead of a NaN-poisoned batch.
    The divergence guard (default on) where-freezes non-finite columns
    bit-exactly, restarts stagnating columns from their true residual,
    and reports both through the per-column ``diverged`` mask; healthy
    columns are bit-for-bit independent of poisoned ones (every Krylov
    scalar is per-column and the operator acts column-wise).
    """
    x = x0 if x0 is not None else _scale(0.0, b)
    r = b if x0 is None else _axpy(-1.0, op(x), b)
    sdtype = _bvdot(b, b).dtype
    tiny = _tiny(sdtype)
    n = jax.tree_util.tree_leaves(b)[0].shape[0]
    one = jnp.ones((n,), dtype=sdtype)
    zero_v = _scale(0.0, b)
    b2 = _bnorm2(b)
    tol2 = (tol * tol) * b2
    rr0 = _bnorm2(r)
    active = rr0 > tol2
    iters = jnp.zeros((n,), jnp.int32)
    div = jnp.logical_not(jnp.isfinite(rr0)) if guard \
        else jnp.zeros((n,), bool)

    def cond(state):
        rr, active, k = state[8], state[9], state[15]
        if guard:
            live = jnp.logical_and(active, jnp.isfinite(rr))
            return jnp.logical_and(jnp.any(live), k < max_iters)
        return jnp.logical_and(jnp.any(active), k < max_iters)

    def body(state):
        (x, r, r0, p, v, rho, alpha, omega, rr, active, iters, div,
         best, since, restarts, k) = state
        rho_new = _bvdot(r0, r)
        ok = jnp.logical_and(
            active,
            jnp.logical_and(jnp.abs(rho_new) > tiny,
                            jnp.logical_and(jnp.abs(rho) > tiny,
                                            jnp.abs(omega) > tiny)))
        okc = ok.astype(sdtype)
        beta = okc * (rho_new / _nz(rho, tiny)) * (alpha / _nz(omega, tiny))
        # Frozen columns get beta = 0 -> p := r (harmless: their alpha /
        # omega below are 0, so x and r never move again).
        p1 = _baxpy(beta, _baxpy(-omega * okc, v, p), r)
        v1 = op(p1)
        r0v = _bvdot(r0, v1)
        ok = jnp.logical_and(ok, jnp.abs(r0v) > tiny)
        okc = ok.astype(sdtype)
        alpha1 = okc * rho_new / _nz(r0v, tiny)
        s = _baxpy(-alpha1, v1, r)
        t = op(s)
        tt = _bvdot(t, t).real
        ok = jnp.logical_and(ok, tt > tiny)
        okc = ok.astype(sdtype)
        omega1 = okc * _bvdot(t, s) / _nz(tt, tiny).astype(sdtype)
        x1 = _baxpy(alpha1, p1, _baxpy(omega1, s, x))
        r1 = _baxpy(-omega1, t, s)
        if recompute_every:
            r1 = jax.lax.cond(
                (k + 1) % recompute_every == 0,
                lambda xk: _axpy(-1.0, op(xk), b),
                lambda _: r1, x1)
        rr1 = _bnorm2(r1)
        rho1, alpha_o, omega_o = rho_new, alpha1, omega1
        if guard:
            # Per-column bit-exact freeze of non-finite columns.
            finite = jnp.isfinite(rr1)
            accept = jnp.logical_and(active, finite)
            x1 = _bwhere(accept, x1, x)
            r1 = _bwhere(accept, r1, r)
            p1 = _bwhere(accept, p1, p)
            v1 = _bwhere(accept, v1, v)
            rho1 = jnp.where(accept, rho_new, rho)
            alpha_o = jnp.where(accept, alpha1, alpha)
            omega_o = jnp.where(accept, omega1, omega)
            rr1 = jnp.where(accept, rr1, rr)
            newly_bad = jnp.logical_and(active, jnp.logical_not(finite))
            div = jnp.logical_or(div, newly_bad)
            # Per-column stagnation -> full Krylov-space re-seed.
            improved = rr1 < best
            best = jnp.where(accept, jnp.minimum(best, rr1), best)
            since = jnp.where(
                accept, jnp.where(improved, 0, since + 1), since)
            best, since = _stagnation_reset(
                recompute_every, k, accept, rr1, best, since)
            stag = jnp.logical_and(accept, since >= stagnation_window)
            restart = jnp.logical_and(stag, restarts < max_restarts)
            exhausted = jnp.logical_and(stag, jnp.logical_not(restart))

            def reseed(args):
                x_, r_, r0_, p_, v_, rr_ = args
                rt = _axpy(-1.0, op(x_), b)
                rt2 = _bnorm2(rt)
                return (_bwhere(restart, rt, r_),
                        _bwhere(restart, rt, r0_),
                        _bwhere(restart, zero_v, p_),
                        _bwhere(restart, zero_v, v_),
                        jnp.where(restart, rt2, rr_))

            r1, r0, p1, v1, rr1 = jax.lax.cond(
                jnp.any(restart), reseed,
                lambda a: (a[1], a[2], a[3], a[4], a[5]),
                (x1, r1, r0, p1, v1, rr1))
            rho1 = jnp.where(restart, one, rho1)
            alpha_o = jnp.where(restart, one, alpha_o)
            omega_o = jnp.where(restart, one, omega_o)
            best = jnp.where(restart, rr1, best)
            since = jnp.where(restart, 0, since)
            restarts = restarts + restart.astype(jnp.int32)
            div = jnp.logical_or(div, exhausted)
            active_new = jnp.logical_and(
                jnp.logical_or(ok, restart), rr1 > tol2)
            active_new = jnp.logical_and(
                active_new, jnp.logical_not(div))
        else:
            # Columns that broke down this iteration (ok went False
            # while still active and unconverged) freeze too: drop them
            # from the active set so the loop can terminate.
            active_new = jnp.logical_and(ok, rr1 > tol2)
        leaving = jnp.logical_and(active, jnp.logical_not(active_new))
        iters = jnp.where(leaving, k + 1, iters)
        return (x1, r1, r0, p1, v1, rho1, alpha_o, omega_o, rr1,
                active_new, iters, div, best, since, restarts, k + 1)

    state = (x, r, r, zero_v, zero_v, one, one, one, rr0, active,
             iters, div, rr0, jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), jnp.int32), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, state)
    x, rr, active, iters, div, k = (out[0], out[8], out[9], out[10],
                                    out[11], out[15])
    iters = jnp.where(active, k, iters)
    rel = jnp.sqrt(rr / jnp.maximum(b2, 1e-30))
    return _result(x, iters, rel, rel <= tol, div)

def blockcg_batched(op: Callable, b, x0=None, *, tol: float = 1e-6,
                    max_iters: int = 1000, recompute_every: int = 0,
                    guard: bool = True,
                    stagnation_window: int = STAGNATION_WINDOW,
                    max_restarts: int = MAX_RESTARTS,
                    project: Optional[Callable] = None) -> SolveResult:
    """Block CG: ONE Krylov space shared by the whole RHS block.

    Where :func:`cg_batched` runs nrhs *independent* recurrences that
    merely share operator applications, block CG searches the sum of
    the columns' Krylov spaces: every iteration solves small
    nrhs x nrhs systems and mixes every search direction into every
    column, so columns with overlapping spectral content (point sources
    on one gauge, noise dilutions) converge in fewer iterations than
    any of them would alone — the multi-RHS batching that already
    amortizes gauge-field traffic now also amortizes iteration count.
    Requires a Hermitian positive-definite ``op``; ``method="blockcg"``
    runs it on the normal equations of the Wilson Schur system.

    This is the residual-orthonormalized variant (Dubrulle's BCGrQ, the
    form lattice production code uses): the residual block is kept as
    ``R = Q S`` with ``Q`` orthonormalized every iteration by a
    Cholesky QR of the small Gram matrix and ``S`` the accumulated
    upper-triangular product.  Plain O'Leary block CG loses the
    residual block's rank in finite precision on ill-conditioned
    systems (the ``R^H R`` solve amplifies rounding until the block
    diverges); orthonormalizing ``Q`` keeps every small solve
    well-conditioned.  Rank-deficiency guards reuse the breakdown-freeze
    machinery: the small Gram/curvature matrices carry a relative ~eps
    identity ridge (invisible at full rank, decisive for duplicate or
    numerically dependent RHS columns), so exactly repeated sources
    stay solvable instead of poisoning the block.

    Per-column convergence freeze, bit-exactly: a column that leaves
    the active set has its ``S`` column zeroed, after which the shared
    recursion can never move its ``x`` again.  The divergence guard
    mirrors :func:`cg_batched` — ``is_finite`` in the loop cond (J6),
    per-column where-freeze of ``x``, stagnation restart re-seeding the
    block from the true residual — with one block-structural caveat: a
    mid-solve operator fault lives in the SHARED direction space, so it
    can freeze the whole block (every unconverged column reports
    ``diverged``), not just one column as in the independent recurrence.

    Residual metric: the loop iterates on the recursive ``S`` product,
    whose accumulated rounding drifts below the true residual on long
    f32 solves; ``recompute_every`` replaces the whole block with the
    true residual (fresh QR) every N iterations — recommended for tight
    tolerances — and the returned ``residual`` is always re-measured
    from ``b - op(x)`` at exit (one extra apply), with the documented
    10x slack on ``converged``.
    """
    # The S-product's drift is intrinsic to the orthonormalized block
    # recursion, so blockcg treats recompute_every=0 as "solver
    # default" (a true-residual replacement every 50 iterations), not
    # "never" — without reliable updates the recursive convergence test
    # is not trustworthy on long f32 solves.  Pass an explicit cadence
    # to override.
    recompute_every = recompute_every or BLOCKCG_RECOMPUTE_DEFAULT
    proj = project if project is not None else (lambda v: v)
    zero_v = jax.tree_util.tree_map(jnp.zeros_like, b)
    x = x0 if x0 is not None else zero_v
    r = b if x0 is None else _axpy(-1.0, op(x), b)
    rr0 = _bnorm2(r)
    b2 = _bnorm2(b)
    tiny = _tiny(rr0.dtype)
    tol2 = (tol * tol) * b2
    n = rr0.shape[0]
    gdtype = _bgram(b, b).dtype
    eye = jnp.eye(n, dtype=gdtype)
    eps = jnp.finfo(jnp.zeros((), gdtype).real.dtype).eps
    gzero = jnp.zeros((), gdtype)

    finite0 = jnp.isfinite(rr0)
    div = jnp.logical_not(finite0) if guard \
        else jnp.zeros(rr0.shape, bool)
    active = jnp.logical_and(rr0 > tol2, finite0)
    if guard:
        # The QR / Gram mixing COUPLES columns: a non-finite source
        # column would poison every small matrix it touches (and
        # 0 * NaN = NaN survives coefficient masking).  Park poisoned
        # columns on true zeros; they are never active and exit through
        # the diverged fold.
        r = _bwhere(finite0, r, zero_v)
        x = _bwhere(finite0, x, zero_v)

    def _chol_qr(rt):
        """Cholesky QR of the stacked block: ``rt = Q C`` with ``Q``
        orthonormal rows and ``C`` upper triangular.  The relative ~eps
        identity ridge is the rank-deficiency guard: a duplicate RHS
        column makes the Gram matrix exactly singular, and the ridge
        keeps the factorization finite while the dependent direction's
        C entries collapse to ~sqrt(eps) — it simply stops contributing
        new Krylov directions."""
        g = _bgram(rt, rt)
        g = 0.5 * (g + jnp.conj(g).T)
        dg = jnp.abs(jnp.diagonal(g))
        lam = (eps * n) * jnp.maximum(jnp.max(dg), tiny)
        low = jnp.linalg.cholesky(g + lam.astype(gdtype) * eye)
        inv_cl = jnp.linalg.inv(jnp.conj(low))
        q = jax.tree_util.tree_map(
            lambda leaf: jnp.tensordot(_apply_scalar(inv_cl, leaf),
                                       leaf, axes=((1,), (0,)),
                                       precision=EXACT), rt)
        return q, jnp.conj(low).T

    def _snorm2(s):
        """Per-column |R|^2 from the S factor (Q is orthonormal, so
        the residual column norms are the S column norms)."""
        return jnp.sum(jnp.abs(s) ** 2, axis=0).real.astype(rr0.dtype)

    qm, c0 = _chol_qr(r)
    s = jnp.where(active[None, :], c0, gzero)
    p = proj(qm)
    rr = _snorm2(s)

    def cond(state):
        rr, active, k = state[4], state[5], state[11]
        if guard:
            live = jnp.logical_and(active, jnp.isfinite(rr))
            return jnp.logical_and(jnp.any(live), k < max_iters)
        return jnp.logical_and(jnp.any(active), k < max_iters)

    def body(state):
        (x, qm, p, s, rr, active, iters, div, best, since, restarts,
         k) = state
        ap = op(p)
        if guard:
            # A direction the operator poisoned must not reach the
            # mixing step (0 * NaN = NaN would spread it everywhere):
            # park it on zeros — the ridged curvature solve then gives
            # it a finite, negligible coefficient row.
            apfin = jnp.isfinite(_bnorm2(ap))
            ap = _bwhere(apfin, ap, zero_v)
        xi = _bgram(p, ap)
        xi = 0.5 * (xi + jnp.conj(xi).T)
        dxi = jnp.abs(jnp.diagonal(xi))
        lam = (eps * n) * jnp.maximum(jnp.max(dxi), tiny)
        alpha = jnp.linalg.inv(xi + lam.astype(gdtype) * eye)
        if project is not None:
            # Deflated directions break the BCGrQ identity P^H Q = I
            # the plain step relies on; the exact small step is
            # M = (P^H A P)^{-1} (P^H Q) — one extra nrhs x nrhs Gram.
            alpha = jnp.matmul(alpha, _bgram(p, qm), precision=EXACT)
        x1 = _bcomb(jnp.matmul(alpha, s, precision=EXACT), p, x)
        t = _bcomb(-alpha, ap, qm)
        qm1, c1 = _chol_qr(t)
        s1 = jnp.matmul(c1, s, precision=EXACT)
        p1 = _bcomb(jnp.conj(c1).T, p, proj(qm1))
        rr1 = _snorm2(s1)
        recomp = ((k + 1) % recompute_every == 0) if recompute_every \
            else jnp.bool_(False)
        if guard:
            finite = jnp.isfinite(rr1)
            accept = jnp.logical_and(active, finite)
            x1 = _bwhere(accept, x1, x)
            rr1 = jnp.where(accept, rr1, rr)
            newly_bad = jnp.logical_and(active, jnp.logical_not(finite))
            div = jnp.logical_or(div, newly_bad)
            improved = rr1 < best
            best = jnp.where(accept, jnp.minimum(best, rr1), best)
            since = jnp.where(
                accept, jnp.where(improved, 0, since + 1), since)
            # The recompute_every x stagnation interaction: a residual
            # replacement is a drift correction, not stagnation — the
            # window is re-baselined below (after the replacement), and
            # a replacement iteration never counts toward a restart.
            stag = jnp.logical_and(
                jnp.logical_and(accept, since >= stagnation_window),
                jnp.logical_not(recomp))
            restart = jnp.logical_and(stag, restarts < max_restarts)
            exhausted = jnp.logical_and(stag, jnp.logical_not(restart))
            restarts = restarts + restart.astype(jnp.int32)
            div = jnp.logical_or(div, exhausted)
            active_new = jnp.logical_and(
                active, jnp.logical_and(jnp.logical_not(div),
                                        rr1 > tol2))
            trigger = jnp.logical_or(recomp, jnp.any(restart))
        else:
            active_new = jnp.logical_and(active, rr1 > tol2)
            trigger = recomp
        # Bit-exact per-column freeze: a column out of the active set
        # has its S column zeroed — the shared recursion can never move
        # its x again (and a NaN S entry of a frozen column is scrubbed
        # rather than multiplied by zero).
        s1 = jnp.where(active_new[None, :], s1, gzero)

        def replace(args):
            # True-residual replacement (reliable update) / stagnation
            # restart: rebuild the whole block state from b - op(x) with
            # a fresh QR; the search space restarts from the residual.
            xa, s_, qm_, p_, rr_ = args
            rt = _axpy(-1.0, op(xa), b)
            if guard:
                rtfin = jnp.isfinite(_bnorm2(rt))
                rt = _bwhere(rtfin, rt, zero_v)
            qm2, c2 = _chol_qr(rt)
            s2 = jnp.where(active_new[None, :], c2, gzero)
            return xa, s2, qm2, proj(qm2), _snorm2(s2)

        if recompute_every or guard:
            _, s1, qm1, p1, rr_t = jax.lax.cond(
                trigger, replace, lambda a: a,
                (x1, s1, qm1, p1, rr1))
            rr1 = jnp.where(active_new, rr_t, rr1)
        if guard:
            # Window re-baseline at a replacement/restart: the fresh
            # true residual is the new best; a no-improvement streak
            # measured against the drifted recursive norm is void.
            rebase = jnp.logical_and(trigger, active_new)
            best = jnp.where(rebase, rr1, best)
            since = jnp.where(rebase, 0, since)
        leaving = jnp.logical_and(active, jnp.logical_not(active_new))
        iters = jnp.where(leaving, k + 1, iters)
        return (x1, qm1, p1, s1, rr1, active_new, iters, div, best,
                since, restarts, k + 1)

    state = (x, qm, p, s, rr, active,
             jnp.zeros(rr.shape, jnp.int32), div, rr,
             jnp.zeros(rr.shape, jnp.int32),
             jnp.zeros(rr.shape, jnp.int32), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, state)
    x, rr, active, iters, div, k = (out[0], out[4], out[5], out[6],
                                    out[7], out[11])
    iters = jnp.where(active, k, iters)
    # Exit-time true residual (one extra apply): the recursive S
    # product's drift never reaches the caller — blockcg REPORTS the
    # recomputed |b - op(x)| / |b|, converged with the documented 10x
    # slack against it (the loop met tol in the recursive metric).
    rt = _axpy(-1.0, op(x), b)
    rel = jnp.sqrt(_bnorm2(rt) / jnp.maximum(b2, 1e-30))
    return _result(x, iters, rel, rel <= tol * 10, div)


# Krylov methods valid on the (non-Hermitian) even-odd Schur system.
# "cg" is plain CG run on the normal equations Dhat^dag Dhat x =
# Dhat^dag rhs — the same system "cgnr" solves; "blockcg" is the
# shared-Krylov block variant of the same normal-equations solve
# (degenerates to "cg" for a single RHS).  All three iterate in the
# normal-equation metric and report the true-system residual (see
# _true_system_result).  repro.api.SolveSpec derives its method choices
# (and the CLI's --method list) from this tuple — extend HERE, not in
# the CLI.
KRYLOV_METHODS = ("cg", "cgnr", "bicgstab", "blockcg")

# Methods that iterate the Hermitian positive-definite normal equations
# Dhat^dag Dhat — the operator a low-mode deflation subspace
# (repro.core.deflate) is built for; bicgstab iterates Dhat itself, so
# a normal-equations Galerkin guess does not apply.
DEFLATABLE_METHODS = ("cg", "cgnr", "blockcg")


def _run_krylov(method: str, dhat, dhat_dag, rhs, *, tol, max_iters,
                recompute_every, batched: bool = False,
                guard: bool = True,
                stagnation_window: int = STAGNATION_WINDOW,
                max_restarts: int = MAX_RESTARTS, deflation=None):
    """Dispatch one native-domain Krylov solve of ``Dhat x = rhs``.

    ``deflation`` (a :class:`repro.core.deflate.DeflationBasis`)
    deflates the normal-equations methods two ways at once: the
    Galerkin low-mode guess ``x0 = W (W^H A W)^{-1} W^H (A^dag rhs)``
    solves the subspace block up front, and the A-orthogonal projector
    (:func:`repro.core.deflate.make_projector`) is applied to every new
    search direction so the Krylov loop stays out of the deflated modes
    for the whole solve (same metric, same tolerance semantics, fewer
    iterations — robust even for an approximate basis).
    """
    kw = dict(tol=tol, max_iters=max_iters,
              recompute_every=recompute_every, guard=guard,
              stagnation_window=stagnation_window,
              max_restarts=max_restarts)
    if deflation is not None and method not in DEFLATABLE_METHODS:
        raise ValueError(
            f"deflation applies to the normal-equations methods "
            f"{DEFLATABLE_METHODS}, not {method!r}")

    def _guess(bn):
        if deflation is None:
            return None
        from repro.core.deflate import galerkin_guess
        return galerkin_guess(deflation, bn, batched=batched)

    if deflation is not None:
        from repro.core.deflate import make_projector
        kw["project"] = make_projector(deflation, batched=batched)

    if method in ("cg", "blockcg"):
        if batched:
            fn = blockcg_batched if method == "blockcg" else cg_batched
        else:
            # A single RHS has no block to share its Krylov space with:
            # blockcg degenerates to plain CG.
            fn = cg

        def normal(v):
            return dhat_dag(dhat(v))

        bn = dhat_dag(rhs)
        res = fn(normal, bn, _guess(bn), **kw)
        return _true_system_result(res, dhat, rhs, tol, batched)
    if method == "cgnr":
        fn = cgnr_batched if batched else cgnr
        x0 = _guess(dhat_dag(rhs)) if deflation is not None else None
        return fn(dhat, dhat_dag, rhs, x0, **kw)
    if method == "bicgstab":
        fn = bicgstab_batched if batched else bicgstab
        return fn(dhat, rhs, **kw)
    raise ValueError(
        f"unknown method {method!r}; choose from {KRYLOV_METHODS}")


_INNER_DTYPES = {
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    # top rung of the escalation ladder (inner solve at full precision;
    # only useful when the outer loop escalated its way up there, or
    # for A/B-ing refinement overhead against a pure-f64 solve)
    "f64": jnp.float64, "float64": jnp.float64,
}

# Precision-escalation ladder (cheap -> exact): when a refined solve's
# outer residual stops contracting, make_refined_solve climbs one rung
# and rebuilds the inner operator there (see ``bops_factory``).
ESCALATION_LADDER = ("bf16", "f32", "f64")


def resolve_inner_dtype(inner_dtype):
    """Map an inner-dtype spelling (``"f32"``/``"bf16"``/...) or dtype to
    the jnp dtype; the single source of truth the CLI reuses too."""
    if isinstance(inner_dtype, str):
        try:
            return _INNER_DTYPES[inner_dtype.lower()]
        except KeyError:
            raise ValueError(
                f"unknown inner_dtype {inner_dtype!r}; "
                f"choose from {sorted(set(_INNER_DTYPES))}") from None
    return jnp.dtype(inner_dtype).type


def make_native_solve(bops, kappa, *, method: str = "cgnr",
                      tol: float = 1e-6, max_iters: int = 2000,
                      recompute_every: int = 0, batched: bool = False,
                      guard: bool = True,
                      stagnation_window: int = STAGNATION_WINDOW,
                      max_restarts: int = MAX_RESTARTS,
                      deflated: bool = False):
    """Build the native-domain Schur-solve pipeline for a bound operator.

    Returns ``fn(v_e, v_o) -> (x, v_xi_o, SolveResult)`` working entirely
    on native vectors of ``bops`` (no encode/decode, no placement): the
    Eq. (4) RHS build, the Krylov iteration, and the Eq. (5) odd
    reconstruction.  The returned function is side-effect free and
    jit-compatible — :class:`repro.api.SolveSession` wraps it in ``jax.jit``
    once per ``(SolveSpec, rhs shape)`` key, which is what makes the
    second and every later same-shape solve skip tracing entirely.

    ``deflated=True`` returns ``fn(v_e, v_o, deflation)`` instead: the
    deflation basis is a pytree ARGUMENT of the jitted solve, not a
    closure constant — a recycled basis that grows between solves
    (fixed shapes, changing values) updates the guess without ever
    retracing the executable.
    """
    if batched:
        hop_eo_nat = bops.hop_eo_native_batched
        hop_oe_nat = bops.hop_oe_native_batched
        dhat_nat = bops.apply_dhat_native_batched
        dhat_dag_nat = bops.apply_dhat_dagger_native_batched
    else:
        hop_eo_nat, hop_oe_nat = bops.hop_eo_native, bops.hop_oe_native
        dhat_nat = bops.apply_dhat_native
        dhat_dag_nat = bops.apply_dhat_dagger_native

    def _solve(v_e, v_o, deflation):
        # RHS of Eq. (4): eta_e + kappa * H_eo eta_o  (D_eo = -kappa H_eo).
        rhs = _axpy(kappa, hop_eo_nat(v_o), v_e)
        res = _run_krylov(
            method,
            lambda v: dhat_nat(v, kappa),
            lambda v: dhat_dag_nat(v, kappa),
            rhs, tol=tol, max_iters=max_iters,
            recompute_every=recompute_every, batched=batched,
            guard=guard, stagnation_window=stagnation_window,
            max_restarts=max_restarts, deflation=deflation)
        # Eq. (5): xi_o = eta_o + kappa * H_oe xi_e.
        v_xi_o = _axpy(kappa, hop_oe_nat(res.x), v_o)
        return res.x, v_xi_o, res

    if deflated:
        def solve_native_deflated(v_e, v_o, deflation):
            return _solve(v_e, v_o, deflation)
        return solve_native_deflated

    def solve_native(v_e, v_o):
        return _solve(v_e, v_o, None)

    return solve_native


def make_refined_solve(bops, U64_e, U64_o, kappa, *, method: str = "cgnr",
                       tol: float = 1e-10, max_iters: int = 2000,
                       recompute_every: int = 0, inner_tol: float = 1e-4,
                       max_outer: int = 25, batched: bool = False,
                       guard: bool = True,
                       stagnation_window: int = STAGNATION_WINDOW,
                       max_restarts: int = MAX_RESTARTS,
                       inner_dtype="f32", escalate: bool = True,
                       bops_factory=None, stall_factor: float = 0.9,
                       snapshot=None):
    """Build a reusable mixed-precision iterative-refinement solve.

    ``bops`` is the *inner* backend, already bound at the cheap inner
    dtype; ``U64_e`` / ``U64_o`` is the gauge for the f64 reference
    operator (upcast to complex128 here).  The f64 operator and hops are
    jitted **once at build time**, so a caller holding the returned
    ``fn(eta_e, eta_o) -> (xi_e, xi_o, RefinedResult)`` (e.g. a
    :class:`repro.api.SolveSession` cache entry) pays the f64 traces on
    the first solve only.  The outer loop itself is Python-level — a
    handful of passes with data-dependent exit — so it is rebuilt per
    call by design; the expensive pieces (f64 operator, inner Krylov
    ``while_loop``) reuse their jit caches across calls.

    Outer loop: f64 true residual of ``Dhat x = rhs``, then a correction
    solve ``Dhat e = r`` in the inner dtype through ``bops``'s native
    domain, ``x += e``, until the **f64** relative residual meets
    ``tol``.  The f64 operator is applied once per outer pass — versus
    ~2 per Krylov iteration for a pure-f64 solve — and all the
    bandwidth-hungry iterating happens at the inner dtype's traffic.

    **Precision escalation** (``escalate``, on by default, active when a
    ``bops_factory`` is supplied): when an outer pass fails to contract
    the residual by ``stall_factor`` — or the inner solve's divergence
    guard trips — the inner dtype climbs :data:`ESCALATION_LADDER` from
    its starting rung (``inner_dtype``) and the inner operator is
    rebuilt via ``bops_factory(rung_name) -> bops``.  Each step taken is
    recorded in ``RefinedResult.escalations``; at the ``"f64"`` rung the
    correction residual is handed to the inner solve at complex128.

    ``snapshot`` (a :class:`repro.resilience.RefinementSnapshot`) makes
    the outer loop resumable: the f64 iterate is checkpointed after
    every correction, and a later call resumes from the newest one
    instead of from zero.
    """
    from . import evenodd

    if jnp.zeros((), jnp.float64).dtype != jnp.dtype(jnp.float64):
        raise ValueError(
            "mixed-precision refinement needs float64 for the outer "
            "residual: enable x64 (jax.config.update('jax_enable_x64', "
            "True) or the repro.compat.enable_x64 context)")

    U64_e = U64_e.astype(jnp.complex128)
    U64_o = U64_o.astype(jnp.complex128)

    def _maybe_vmap(fn):
        return jax.vmap(fn) if batched else fn

    dhat64 = jax.jit(_maybe_vmap(
        lambda v: evenodd.apply_dhat(U64_e, U64_o, v, kappa)))
    hop_eo64 = jax.jit(_maybe_vmap(
        lambda v: evenodd.hop_eo(U64_e, U64_o, v)))
    hop_oe64 = jax.jit(_maybe_vmap(
        lambda v: evenodd.hop_oe(U64_e, U64_o, v)))

    def _inner_ops(bops_):
        if batched:
            return (bops_.to_domain_batched, bops_.from_domain_batched,
                    bops_.apply_dhat_native_batched,
                    bops_.apply_dhat_dagger_native_batched)
        return (bops_.to_domain, bops_.from_domain,
                bops_.apply_dhat_native, bops_.apply_dhat_dagger_native)

    bnorm = _bnorm2 if batched else _norm2

    ladder = list(ESCALATION_LADDER)
    start = inner_dtype if isinstance(inner_dtype, str) else "f32"
    start = {"float32": "f32", "bfloat16": "bf16",
             "float64": "f64"}.get(start.lower(), start.lower())
    start_rung = ladder.index(start) if start in ladder \
        else ladder.index("f32")

    def refined(eta_e, eta_o):
        eta64_e = eta_e.astype(jnp.complex128)
        eta64_o = eta_o.astype(jnp.complex128)
        rhs64 = eta64_e + kappa * hop_eo64(eta64_o)
        f64_applies = 1  # the hop above
        b2 = bnorm(rhs64)

        x64 = jnp.zeros_like(rhs64)
        start_outer = 0
        if snapshot is not None:
            x64, start_outer, _ = snapshot.resume(x64)
        inner_iters = 0
        # Per-column (batched) / scalar (unbatched) total inner
        # iterations, matching the batched SolveResult contract
        # RefinedResult duck-types.
        iters_acc = jnp.zeros(b2.shape, jnp.int32)
        cur = bops
        to_dom, from_dom, dhat_nat, dhat_dag_nat = _inner_ops(cur)
        rung = start_rung
        escalations = []
        inner_div = None
        best_worst = None
        outer = start_outer
        rel = None
        for outer in range(start_outer + 1, max_outer + 1):
            r64 = rhs64 - dhat64(x64)
            f64_applies += 1
            rel = jnp.sqrt(bnorm(r64) / jnp.maximum(b2, 1e-300))
            if bool(jnp.all(rel <= tol)):
                break
            # Escalation trigger: the previous pass failed to contract
            # the worst-column residual by stall_factor, or its inner
            # solve tripped the divergence guard.
            worst = float(jnp.max(rel))
            stalled = (best_worst is not None
                       and not worst < best_worst * stall_factor)
            tripped = inner_div is not None and bool(jnp.any(inner_div))
            if ((stalled or tripped) and escalate
                    and bops_factory is not None):
                while rung + 1 < len(ladder):
                    rung += 1
                    try:
                        cur = bops_factory(ladder[rung])
                    except Exception:       # rung unavailable: keep
                        continue            # climbing
                    to_dom, from_dom, dhat_nat, dhat_dag_nat = \
                        _inner_ops(cur)
                    escalations.append(ladder[rung])
                    best_worst = None       # fresh contraction baseline
                    break
            if best_worst is None or worst < best_worst:
                best_worst = worst
            # Correction solve in the inner dtype, native domain (the
            # f64 rung keeps the correction residual at complex128).
            cdt = jnp.complex128 if ladder[rung] == "f64" \
                else jnp.complex64
            v = to_dom(r64.astype(cdt))
            res = _run_krylov(
                method,
                lambda w: dhat_nat(w, kappa),
                lambda w: dhat_dag_nat(w, kappa),
                v, tol=inner_tol, max_iters=max_iters,
                recompute_every=recompute_every, batched=batched,
                guard=guard, stagnation_window=stagnation_window,
                max_restarts=max_restarts)
            inner_div = res.diverged
            x64 = x64 + from_dom(res.x).astype(jnp.complex128)
            iters_acc = iters_acc + res.iterations.astype(jnp.int32)
            inner_iters += int(jnp.max(res.iterations))
            if snapshot is not None:
                snapshot.save(outer, x64)
        else:
            # Outer budget exhausted: report the residual of the final
            # iterate, not the one from before the last correction.
            r64 = rhs64 - dhat64(x64)
            f64_applies += 1
            rel = jnp.sqrt(bnorm(r64) / jnp.maximum(b2, 1e-300))
        diverged = jnp.logical_not(jnp.isfinite(rel))
        if inner_div is not None:
            # An inner guard trip only counts as divergence if the
            # outer loop never recovered the column to tolerance.
            diverged = jnp.logical_or(diverged, jnp.logical_and(
                inner_div, jnp.logical_not(rel <= tol)))
        converged = jnp.logical_and(rel <= tol,
                                    jnp.logical_not(diverged))

        xi_o64 = eta64_o + kappa * hop_oe64(x64)
        f64_applies += 1
        xi_e = x64.astype(eta_e.dtype)
        xi_o = xi_o64.astype(eta_o.dtype)
        return xi_e, xi_o, RefinedResult(
            x=xi_e, iterations=iters_acc, residual=rel,
            converged=converged, outer_iterations=outer,
            f64_applies=f64_applies, inner_iterations=inner_iters,
            diverged=diverged, escalations=tuple(escalations))

    return refined
