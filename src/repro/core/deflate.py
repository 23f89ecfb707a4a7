"""Low-mode deflation and subspace recycling for repeated solves.

Repeated solves against ONE bound gauge configuration — a propagator
request stream, an HMC force loop — all fight the same few low modes of
the normal operator ``A = Dhat^dag Dhat``: those modes dominate the
condition number and therefore every CG iteration count.  This module
computes a small deflation subspace once per gauge and removes it from
every subsequent solve, making the *stream* sublinear in total
iterations even though each individual solve is unchanged Krylov:

* :class:`DeflationBasis` — the subspace as a fixed-shape pytree
  ``(vectors, avectors, gram, mask)``: ``rank`` native-domain basis
  vectors ``W`` stacked on a leading axis (zero-padded past the fill
  count), the matching operator images ``A W`` (both builders compute
  them anyway — storing them makes the per-iteration projection free
  of extra operator applies), ``gram = W^H A W`` (identity in unused
  slots) and a slot mask.  Fixed shapes are the point: the basis is
  passed into the jitted solve as an ARGUMENT, so a basis that grows
  between solves updates values, never shapes — no retrace.
* :func:`lanczos_basis` — an m-step fully reorthogonalized Lanczos
  pass over ``A`` with Rayleigh-Ritz extraction of the lowest ``rank``
  modes; the reduction ``H = V^H (A V)`` rides the backend's batched
  native operator (one batched apply over the whole Krylov basis).
* :func:`galerkin_guess` — the Galerkin initial guess
  ``x0 = W (W^H A W)^{-1} W^H b``: solves the low-mode block before
  the Krylov loop starts.  An empty basis returns the zero guess
  bit-for-bit.
* :func:`make_projector` — the per-iteration half of deflation: new
  search directions are built from ``P r = r - W G^{-1} (A W)^H r``
  instead of ``r``, keeping every direction A-orthogonal to the
  subspace.  This is what makes deflation ROBUST in f32: the guess
  alone ("init-CG") only pays off with eigenvector accuracy near the
  solve tolerance, while the projected recurrence locks the low modes
  out of the Krylov space even when the basis spans them only
  approximately (harvested solutions, a modest Lanczos pass).  Cost
  per iteration: rank-sized dot products against the stored ``A W``
  — no operator applies.
* :func:`make_recycle_update` / :class:`DeflationState` — the
  recycling alternative to an up-front eigensolve: start empty and
  harvest converged solutions from the request stream itself
  (``x = A^{-1} b`` weights mode ``i`` by ``1/lambda_i`` — solutions
  are naturally low-mode rich), so per-solve iteration counts DROP as
  the stream proceeds; ``SolveSession.stats()`` exposes the drop.
* checkpointing — :class:`repro.resilience.BasisSnapshot` persists a
  basis (atomic staged saves) so a re-bound gauge restores it instead
  of re-paying the Lanczos pass or the recycle warm-up.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as _np

from . import solver as _sol
from .lattice import EXACT


class DeflationBasis(NamedTuple):
    """Fixed-shape deflation subspace (a pytree; see module docstring).

    ``vectors`` mirrors the native vector pytree with a leading
    ``rank`` axis per leaf; ``avectors`` holds the operator images
    ``A W`` in the same layout; slots past the fill count are zero.
    ``gram`` is ``W^H A W`` with identity rows/columns in unused slots
    (always solvable); ``mask`` flags filled slots.
    """
    vectors: jax.Array
    avectors: jax.Array
    gram: jax.Array
    mask: jax.Array

    @property
    def rank(self) -> int:
        return int(self.mask.shape[0])

    def count(self) -> int:
        return int(jnp.sum(self.mask))


def _gram_dtype(v_like):
    return _sol._vdot(v_like, v_like).dtype


def empty_basis(rank: int, v_like) -> DeflationBasis:
    """All-slots-empty basis shaped for ``rank`` vectors like ``v_like``
    (the recycle starting point, and the snapshot restore template)."""
    def stack_like(_):
        return jax.tree_util.tree_map(
            lambda leaf: jnp.zeros((rank,) + leaf.shape, leaf.dtype),
            v_like)

    gdtype = _gram_dtype(v_like)
    return DeflationBasis(stack_like(None), stack_like(None),
                          jnp.eye(rank, dtype=gdtype),
                          jnp.zeros((rank,), bool))


def _stack_dot(vecs, v, batched: bool):
    """Coefficients ``c[i] = <W_i, v>`` against a stacked basis —
    ``(rank,)``, or ``(rank, nrhs)`` for a batched ``v`` (f32-accumulated
    for sub-f32 leaves, like the solver reductions)."""
    out = None
    for w, x in zip(jax.tree_util.tree_leaves(vecs),
                    jax.tree_util.tree_leaves(v)):
        w, x = _sol._acc(w), _sol._acc(x)
        wf = jnp.conj(w).reshape(w.shape[0], -1)
        if batched:
            c = jnp.matmul(wf, x.reshape(x.shape[0], -1).T,
                           precision=EXACT)
        else:
            c = jnp.matmul(wf, x.reshape(-1), precision=EXACT)
        out = c if out is None else out + c
    return out


def _stack_comb(coef, vecs):
    """Linear combination ``sum_i coef[i] * W_i`` over the stacked
    basis; a ``(rank, nrhs)`` coefficient block yields the batched
    vector (leading nrhs axis).  The coefficient is cast down to the
    leaf dtype (see ``solver._apply_scalar``)."""
    def leaf(w):
        c = _sol._apply_scalar(coef, w)
        return jnp.tensordot(c, w, axes=((0,), (0,)), precision=EXACT)
    return jax.tree_util.tree_map(leaf, vecs)


def _mix(coef, stacked):
    """Re-stack a basis through a ``(k, m)`` coefficient matrix:
    ``out_i = sum_j coef[i, j] * V_j``."""
    def leaf(v):
        c = _sol._apply_scalar(coef, v)
        return jnp.tensordot(c, v, axes=((1,), (0,)), precision=EXACT)
    return jax.tree_util.tree_map(leaf, stacked)


def _masked_gram(gram, mask):
    """``W^H A W`` restricted to filled slots, identity elsewhere —
    always solvable, and empty slots contribute exactly zero."""
    gdtype = gram.dtype
    rank = mask.shape[0]
    mf = mask.astype(gdtype)
    return (mf[:, None] * mf[None, :]) * gram \
        + (1.0 - mf) * jnp.eye(rank, dtype=gdtype)


def galerkin_guess(basis: DeflationBasis, bn, *, batched: bool = False):
    """Galerkin (init-CG) deflation guess ``W (W^H A W)^{-1} W^H bn``.

    ``bn`` is the normal-equations RHS ``Dhat^dag rhs`` the solver
    iterates on.  Empty slots are masked to identity rows/zero RHS, so
    an EMPTY basis returns the zero vector — bit-for-bit the undeflated
    start (what makes a growing recycle basis safe to pass from solve
    zero onward).
    """
    mask = basis.mask
    mf = mask.astype(basis.gram.dtype)
    c = _stack_dot(basis.vectors, bn, batched)
    c = c * (mf[:, None] if batched else mf)
    gm = _masked_gram(basis.gram, mask)
    return _stack_comb(jnp.linalg.solve(gm, c), basis.vectors)


def make_projector(basis: DeflationBasis, *, batched: bool = False):
    """A-orthogonal deflation projector ``P r = r - W G^{-1} (A W)^H r``.

    The returned closure is handed to the solver's ``project`` hook:
    every new search direction is projected so ``W^H A p = 0``, which
    keeps the Krylov space out of the (approximately) deflated low
    modes for the whole solve — see the module docstring for why the
    initial guess alone is not enough in f32.  Uses the stored ``A W``
    (no operator applies).  With an EMPTY basis the correction term is
    exactly zero, so the projector is the identity and the solve
    matches the undeflated recurrence.
    """
    mask = basis.mask
    mf = mask.astype(basis.gram.dtype)
    gm = _masked_gram(basis.gram, mask)

    def project(r):
        c = _stack_dot(basis.avectors, r, batched)
        c = c * (mf[:, None] if batched else mf)
        y = jnp.linalg.solve(gm, c)
        corr = _stack_comb(y, basis.vectors)
        return jax.tree_util.tree_map(lambda a, d: a - d, r, corr)

    return project


def lanczos_basis(op: Callable, v0, rank: int, *,
                  iters: Optional[int] = None,
                  op_batched: Optional[Callable] = None
                  ) -> DeflationBasis:
    """Lowest-``rank`` Ritz pairs of the HPD ``op`` via Lanczos.

    Runs ``iters`` (default ``max(3*rank, rank+16)``, clamped to the
    space dimension — the projected recurrence tolerates approximate
    Ritz vectors, but more steps still buy fewer iterations per
    deflated solve) Lanczos steps from ``v0`` with full
    reorthogonalization (modified Gram-Schmidt against every stored
    vector — m is small, orthogonality is what makes the low Ritz pairs
    trustworthy), then extracts Ritz vectors from the explicit
    Rayleigh quotient ``H = V^H (A V)``.  ``op_batched`` (the backend's
    batched native operator) computes ``A V`` as ONE batched apply over
    the whole stacked basis; without it the column-wise fallback is
    used.  Eager Python loop by design: once per bound gauge, with a
    data-dependent early exit on Krylov-space exhaustion.
    """
    dim = sum(leaf.size
              for leaf in jax.tree_util.tree_leaves(v0))
    m = int(iters) if iters else max(3 * rank, rank + 16)
    m = max(1, min(m, dim))
    nrm2 = _sol._norm2(v0)
    tiny = _sol._tiny(nrm2.dtype)
    v = _sol._scale(1.0 / jnp.sqrt(jnp.maximum(nrm2, tiny)), v0)
    basis_vecs = [v]
    for _ in range(m - 1):
        w = op(basis_vecs[-1])
        pre2 = _sol._norm2(w)
        # Full reorthogonalization (MGS) — also subsumes the three-term
        # recurrence's alpha/beta subtraction.
        for u in basis_vecs:
            w = _sol._axpy(-_sol._vdot(u, w), u, w)
        w2 = _sol._norm2(w)
        # RELATIVE breakdown test: once the Krylov space saturates (a
        # well-conditioned operator exhausts it in a few dozen steps),
        # what survives orthogonalization is pure roundoff — normalizing
        # it would stack a numerically dependent direction into V and
        # seed Rayleigh-Ritz with spurious near-null eigenvalues.
        if float(w2) <= max(float(tiny), 1e-10 * float(pre2)):
            break                      # Krylov space exhausted
        basis_vecs.append(_sol._scale(1.0 / jnp.sqrt(w2), w))
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *basis_vecs)
    if op_batched is not None:
        av = op_batched(stacked)
    else:
        av = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[op(u) for u in basis_vecs])
    h = _sol._bgram(stacked, av)
    h = 0.5 * (h + jnp.conj(h).T)
    vals, y = jnp.linalg.eigh(h)       # ascending: low modes first
    # Spurious-mode filter: residual f32 rank loss in V shows up as
    # Ritz values at roundoff scale (~eps^2 of the spectrum top) that
    # correspond to no eigenvalue of the HPD operator; deflating one
    # would project against garbage.  Genuine deflatable low modes of
    # an f32-solvable system sit far above eps * lambda_max.
    vals = _np.asarray(vals.real)
    eps_h = float(jnp.finfo(jnp.zeros((), h.dtype).real.dtype).eps)
    cutoff = max(vals[-1], 0.0) * eps_h * 16
    genuine = [int(i) for i in range(vals.shape[0]) if vals[i] > cutoff]
    keep = min(rank, len(genuine))
    yk = y[:, _np.asarray(genuine[:keep], dtype=_np.int64)].T
    w_ritz = _mix(yk, stacked)
    aw = _mix(yk, av)
    # Quality filter: the projector divides by the Ritz value, so an
    # UNCONVERGED pair (Ritz residual |A w - theta w| comparable to
    # theta itself) would amplify its eigenvector error by 1/theta and
    # poison every deflated solve.  Keep only pairs whose residual is
    # safely below their value; dropping a marginal mode merely forgoes
    # its iteration savings.
    theta = vals[_np.asarray(genuine[:keep], dtype=_np.int64)]
    resid = jax.tree_util.tree_map(
        lambda a_, w_: a_ - _sol._bb(
            _sol._apply_scalar(jnp.asarray(theta), w_), w_) * w_,
        aw, w_ritz)
    rres = _np.sqrt(_np.asarray(jax.device_get(_sol._bnorm2(resid))))
    ok = [i for i in range(keep)
          if rres[i] <= RITZ_QUALITY * theta[i]]
    if len(ok) < keep:
        sel = _np.asarray(ok, dtype=_np.int64)
        w_ritz = jax.tree_util.tree_map(lambda l: l[sel], w_ritz)
        aw = jax.tree_util.tree_map(lambda l: l[sel], aw)
        keep = len(ok)
    gram = _sol._bgram(w_ritz, aw)
    gram = 0.5 * (gram + jnp.conj(gram).T)
    out = empty_basis(rank, v0)

    def fill(z, w):
        return jax.tree_util.tree_map(
            lambda zl, wl: zl.at[:keep].set(wl.astype(zl.dtype)), z, w)

    return DeflationBasis(
        fill(out.vectors, w_ritz), fill(out.avectors, aw),
        out.gram.at[:keep, :keep].set(gram.astype(out.gram.dtype)),
        out.mask.at[:keep].set(True))


# Ritz-pair acceptance: a pair only deflates when its eigenvector
# residual |A w - theta w| is below this fraction of theta — the
# projector divides by theta, so a sloppier pair amplifies its own
# error by 1/theta into every deflated iteration.
RITZ_QUALITY = 0.5
# Recycle refinement accepts at a laxer, band-level gate: harvested
# spans resolve the low CLUSTER collectively before any individual
# pair converges (intra-band mixing inflates per-pair residuals while
# the span already deflates the band — measured: a stream that stays
# flat gated at 0.5, and mildly *degrades* gated at 2 when only part
# of a cluster activates, drops ~30% gated at 5).  Genuinely dangerous
# pairs — near-null values carrying roundoff garbage — have residual
# ratios orders of magnitude above this and stay rejected.
RECYCLE_QUALITY = 5.0


def make_ritz_refine(quality: float = RITZ_QUALITY):
    """Jitted ``raw span -> deflation basis`` Rayleigh-Ritz refinement.

    Deflating with RAW harvested solutions is numerically fragile: a
    solution ``x = A^{-1} b`` mixes every mode, so its image ``A x``
    is large relative to its tiny Rayleigh quotient, and the
    projection identity ``P^H r = r`` that CG's step length relies on
    degrades by that ratio in f32 — measured as harvests *slowing the
    stream down*.  This refinement rotates the harvested span to its
    Ritz pairs (Rayleigh-Ritz on the stored ``W^H A W``) and ACCEPTS —
    via the basis mask — only pairs passing the :data:`RITZ_QUALITY`
    eigenvector-residual test, i.e. the directions the span already
    resolves as approximate eigenvectors.  Early in the stream nothing
    may qualify (the projector stays the identity — no harm); as
    harvests accumulate the low cluster converges, pairs activate, and
    per-solve iterations drop.

    The empty-slot handling rides the exact block structure of the
    masked gram: masked entries are zero, so filled and empty blocks
    cannot mix in ``eigh``; empty diagonals get a sentinel only a few
    times the spectrum scale (an f32 ``eigh``'s backward error is
    ``eps * |gm|`` — a huge sentinel would destroy the small Ritz
    values), and empty-block eigenpairs are identified by their
    eigenvector weight, not their value.
    """
    def refine(raw: DeflationBasis) -> DeflationBasis:
        vecs, avecs, gram, mask = raw
        rank = mask.shape[0]
        gdtype = gram.dtype
        rdtype = jnp.zeros((), gdtype).real.dtype
        mf = mask.astype(gdtype)
        diag = jnp.abs(jnp.diag(gram).real) * mf.real
        sentinel = 4.0 * jnp.maximum(jnp.max(diag), 1.0)
        gm = (mf[:, None] * mf[None, :]) * gram \
            + ((1.0 - mf) * sentinel.astype(gdtype)) \
            * jnp.eye(rank, dtype=gdtype)
        vals, y = jnp.linalg.eigh(gm)          # ascending
        theta = vals.real.astype(rdtype)
        # out_i = sum_j y[j, i] V_j  ->  coefficient matrix y.T
        w = _mix(y.T, vecs)
        aw = _mix(y.T, avecs)
        resid = jax.tree_util.tree_map(
            lambda a_, w_: a_ - _sol._bb(
                _sol._apply_scalar(theta, w_), w_) * w_, aw, w)
        r2 = _sol._bnorm2(resid)
        # weight of each eigenvector on EMPTY slots: exactly 1 for the
        # sentinel block's pairs, exactly 0 for genuine ones.
        wempty = jnp.matmul((1.0 - mf.real)[None, :], jnp.abs(y) ** 2,
                            precision=EXACT).ravel()
        accept = jnp.logical_and(
            jnp.logical_and(wempty < 0.5, theta > 0.0),
            r2 <= (quality * theta) ** 2)
        gnew = jnp.diag(jnp.where(accept, theta, 1.0).astype(gdtype))
        zero = jnp.zeros((), rdtype)
        wm = jax.tree_util.tree_map(
            lambda l: l * _sol._bb(_sol._apply_scalar(
                jnp.where(accept, zero + 1.0, zero), l), l), w)
        awm = jax.tree_util.tree_map(
            lambda l: l * _sol._bb(_sol._apply_scalar(
                jnp.where(accept, zero + 1.0, zero), l), l), aw)
        return DeflationBasis(wm, awm, gnew, accept)

    return jax.jit(refine)


def estimate_lambda_max(op: Callable, v0, iters: int = 12) -> float:
    """Power-iteration estimate of the top eigenvalue of the HPD
    ``op`` — scales the recycle harvest filter (see
    :func:`make_recycle_update`).  A dozen applies, once per basis."""
    n2 = _sol._norm2(v0)
    tiny = _sol._tiny(n2.dtype)
    v = _sol._scale(1.0 / jnp.sqrt(jnp.maximum(n2, tiny)), v0)
    lam = 0.0
    for _ in range(max(1, int(iters))):
        w = op(v)
        lam = float(_sol._vdot(v, w).real)
        w2 = _sol._norm2(w)
        v = _sol._scale(1.0 / jnp.sqrt(jnp.maximum(w2, tiny)), w)
    return lam


def make_recycle_update(op: Callable, *, lam_max: Optional[float] = None,
                        filter_steps: int = 8, lo_frac: float = 0.05):
    """Jitted ``(basis, v) -> basis`` appending one harvested solution.

    ``v`` is orthogonalized against the filled slots, normalized, and
    written into the first free slot; the Gram matrix — and the stored
    ``A W`` image — come from ONE ``op`` apply.  Fixed shapes throughout
    (where-selects, clipped scatter index), so every update reuses one
    executable.  The update is rejected — basis returned unchanged —
    when the basis is full, the new component is non-finite, or ``v``
    is numerically inside the span already (a dependent direction would
    make the Gram solve ill-posed for zero deflation gain).

    ``lam_max`` (with ``filter_steps > 0``) arms the Chebyshev harvest
    filter.  A raw solution is only ``1/sigma``-weighted in the normal
    operator's eigenbasis (``x = Dhat^{-1} rhs``) — too weak for the
    harvested span to ever resolve the low cluster the projector needs
    (its Ritz pairs stall an order of magnitude above the true low
    modes).  ``filter_steps`` three-term Chebyshev steps on
    ``[lo_frac * lam_max, lam_max]`` suppress every mode inside that
    interval to ``|T_k| <= 1`` while amplifying the modes BELOW it
    exponentially in ``k``, so each harvest enters the span low-mode
    dominated and the stream becomes a filtered subspace iteration —
    at ``filter_steps`` operator applies per harvest, a fraction of
    one solve.
    """
    def update(basis: DeflationBasis, v) -> DeflationBasis:
        vecs, avecs, gram, mask = basis
        if lam_max is not None and filter_steps > 0:
            b_hi = float(lam_max)
            a_lo = float(lo_frac) * b_hi
            half = 0.5 * (b_hi - a_lo)
            mid = 0.5 * (b_hi + a_lo)

            def smap(u):
                # affine map of A onto [-1, 1] over [a_lo, b_hi]
                return jax.tree_util.tree_map(
                    lambda p, q: (p - mid * q) / half, op(u), u)

            t0, t1 = v, smap(v)
            for _ in range(int(filter_steps) - 1):
                t2 = jax.tree_util.tree_map(
                    lambda s, p: 2.0 * s - p, smap(t1), t0)
                t0, t1 = t1, t2
            v = t1
        rank = mask.shape[0]
        gdtype = gram.dtype
        mf = mask.astype(gdtype)
        c = _stack_dot(vecs, v, batched=False) * mf
        d = jax.tree_util.tree_map(
            lambda x, u: x - u, v, _stack_comb(c, vecs))
        d2 = _sol._norm2(d)
        v2 = _sol._norm2(v)
        tiny = _sol._tiny(d2.dtype)
        idx = jnp.sum(mask).astype(jnp.int32)
        good = jnp.logical_and(
            jnp.logical_and(idx < rank, jnp.isfinite(d2)),
            d2 > v2 * 1e-8)
        w = _sol._scale(1.0 / jnp.sqrt(jnp.maximum(d2, tiny)), d)
        aw = op(w)
        col = _stack_dot(vecs, aw, batched=False) * mf
        diag = _sol._vdot(w, aw)
        # Hermitian extension: gram[idx, j] = <w, A W_j> = conj(col_j).
        g1 = gram.at[idx, :].set(jnp.conj(col))
        g1 = g1.at[:, idx].set(col)
        g1 = g1.at[idx, idx].set(diag.astype(gdtype))
        vecs1 = jax.tree_util.tree_map(
            lambda z, wl: z.at[idx].set(wl.astype(z.dtype)), vecs, w)
        avecs1 = jax.tree_util.tree_map(
            lambda z, wl: z.at[idx].set(wl.astype(z.dtype)), avecs, aw)
        return DeflationBasis(
            _sol._swhere(good, vecs1, vecs),
            _sol._swhere(good, avecs1, avecs),
            jnp.where(good, g1, gram),
            jnp.where(good, mask.at[idx].set(True), mask))

    return jax.jit(update)


class DeflationState:
    """Per-(matrix, spec) deflation holder the session drives.

    Owns the current :class:`DeflationBasis` (passed into each jitted
    solve as an argument), the jitted recycle updater, and the optional
    :class:`repro.resilience.BasisSnapshot` persisting the basis across
    process lifetimes.  ``mode``: ``"lanczos"`` pays an up-front
    eigensolve and stays fixed; ``"recycle"`` starts empty and grows
    from the stream via :meth:`harvest_column`.
    """

    def __init__(self, basis: DeflationBasis, mode: str,
                 update_fn=None, snapshot=None, refine_fn=None,
                 raw: Optional[DeflationBasis] = None):
        self.basis = basis        # what solves project against
        self.raw = raw            # recycle: harvested span behind it
        self.mode = mode
        self.harvested = 0
        self._update = update_fn
        self._refine = refine_fn
        self._snapshot = snapshot

    @property
    def rank(self) -> int:
        return self.basis.rank

    @property
    def count(self) -> int:
        """Filled slots — of the raw harvested span in recycle mode
        (what gates further harvesting), of the basis otherwise."""
        if self.raw is not None:
            return self.raw.count()
        return self.basis.count()

    @property
    def active(self) -> int:
        """Basis slots the projector actually uses (recycle: Ritz pairs
        passing the quality filter — at most ``count``)."""
        return self.basis.count()

    def harvest_column(self, v) -> bool:
        """Offer one CONVERGED solution vector to a recycle basis.

        The raw span grows by the (orthogonalized) solution, then the
        EXPOSED basis is re-derived by Rayleigh-Ritz refinement — only
        quality-passing Ritz pairs deflate (see
        :func:`make_ritz_refine`), so the caller's next solve sees the
        grown basis as changed values, never changed shapes.
        Lanczos-mode and full bases decline.  The grown raw span is
        snapshotted immediately when persistence is on, so a restarted
        process resumes with the learned subspace.
        """
        if self.mode != "recycle" or self._update is None:
            return False
        before = self.count
        if before >= self.rank:
            return False
        raw1 = DeflationBasis(*self._update(self.raw, v))
        after = raw1.count()
        if after == before:
            return False
        self.raw = raw1
        self.basis = (DeflationBasis(*self._refine(raw1))
                      if self._refine is not None else raw1)
        self.harvested += 1
        if self._snapshot is not None:
            self._snapshot.save(after, raw1)
        return True
