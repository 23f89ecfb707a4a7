"""Even-odd (red-black) layout and hopping blocks.

The even/odd arrays are compacted in the x-direction (paper Fig. 4):

``even[t, z, y, xh] = full[t, z, y, 2*xh + (t+z+y) % 2]``
``odd [t, z, y, xh] = full[t, z, y, 2*xh + (t+z+y+1) % 2]``

so both have shape ``(T, Z, Y, Xh, ...)`` with ``Xh = X // 2``.  The price
is the parity-dependent x-shift of Fig. 5: the +-x neighbor of a site sits
at the *same* ``xh`` in the opposite-parity array for half the rows and at
``xh +- 1`` for the other half, with the row parity ``(t+z+y) % 2`` as the
predicate.  :func:`eo_shift` implements exactly the paper's ``sel`` +
``tbl`` sequence as a masked roll.

``hop_oe`` (even -> odd) and ``hop_eo`` (odd -> even) are the two hopping
blocks; ``D_eo = -kappa * hop_eo`` etc.  The even-odd preconditioned
operator of Eq. (4) is ``Dhat = 1 - kappa^2 * H_eo H_oe``.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from . import gamma, wilson
from .lattice import (AXIS_OF_MU, EXACT, MU_X, MU_Y, MU_Z, NDIM,
                      row_parity)

EVEN, ODD = 0, 1


def _row_par(shape: Tuple[int, ...], trailing: int) -> jnp.ndarray:
    return row_parity(shape, trailing_dims=trailing)


def pack(field: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split a full-lattice field ``(T, Z, Y, X, ...)`` into (even, odd)."""
    T, Z, Y, X = field.shape[:4]
    rest = field.shape[4:]
    v = field.reshape(T, Z, Y, X // 2, 2, *rest)
    off = _row_par((T, Z, Y), trailing=len(rest))  # (T,Z,Y,1,1...)
    v0, v1 = v[:, :, :, :, 0], v[:, :, :, :, 1]
    even = jnp.where(off == 0, v0, v1)
    odd = jnp.where(off == 0, v1, v0)
    return even, odd


def unpack(even: jnp.ndarray, odd: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack`."""
    T, Z, Y, Xh = even.shape[:4]
    rest = even.shape[4:]
    off = _row_par((T, Z, Y), trailing=len(rest))
    v0 = jnp.where(off == 0, even, odd)
    v1 = jnp.where(off == 0, odd, even)
    v = jnp.stack([v0, v1], axis=4)
    return v.reshape(T, Z, Y, 2 * Xh, *rest)


def pack_gauge(U: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack a gauge field ``(4, T, Z, Y, X, 3, 3)`` into even/odd halves."""
    pairs = [pack(U[mu]) for mu in range(NDIM)]
    return (jnp.stack([p[0] for p in pairs]), jnp.stack([p[1] for p in pairs]))


def eo_shift(src: jnp.ndarray, mu: int, direction: int, out_parity: int,
             parity_offset: int = 0) -> jnp.ndarray:
    """Neighbor fetch inside the even-odd layout.

    Returns ``src(x + direction * mu_hat)`` evaluated at the sites of
    ``out_parity``, where ``src`` is the compacted array of the *opposite*
    parity.  For mu in {y, z, t} this is a plain periodic roll; for mu = x
    it is the paper's parity-masked shift (``sel`` on a rolled copy).

    ``parity_offset`` is ``(t0 + z0 + y0) % 2`` of the local shard origin,
    so distributed shards with an odd origin use the flipped mask.
    """
    axis = AXIS_OF_MU[mu]
    if mu != MU_X:
        return jnp.roll(src, -direction, axis=axis)
    T, Z, Y, Xh = src.shape[:4]
    trailing = src.ndim - 4
    par = _row_par((T, Z, Y), trailing=trailing)
    m = (out_parity + (1 if direction > 0 else 0) + parity_offset) % 2
    rolled = jnp.roll(src, -direction, axis=3)
    return jnp.where(par == m, rolled, src)


def hop_block(U_e: jnp.ndarray, U_o: jnp.ndarray, src: jnp.ndarray,
              out_parity: int, parity_offset: int = 0) -> jnp.ndarray:
    """One hopping block: ``H_oe`` if ``out_parity == ODD`` else ``H_eo``.

    ``U_e, U_o``: ``(4, T, Z, Y, Xh, 3, 3)``; ``src``: spinor of the
    opposite parity, ``(T, Z, Y, Xh, 4, 3)``.
    """
    U_out = U_o if out_parity == ODD else U_e   # U_mu(x) at output sites
    U_in = U_e if out_parity == ODD else U_o    # U_mu at source-parity sites
    out = jnp.zeros_like(src)
    for mu in range(NDIM):
        # Forward: (1 - g_mu) U_mu(x) src(x + mu).
        fwd = eo_shift(src, mu, +1, out_parity, parity_offset)
        h = gamma.project(fwd, mu, s=-1)
        uh = jnp.einsum("...ab,...hb->...ha", U_out[mu], h, precision=EXACT)
        out = out + gamma.reconstruct(uh, mu, s=-1)
        # Backward: (1 + g_mu) U_mu^dag(x - mu) src(x - mu).
        bwd = eo_shift(src, mu, -1, out_parity, parity_offset)
        u_bwd = eo_shift(U_in[mu], mu, -1, out_parity, parity_offset)
        h = gamma.project(bwd, mu, s=+1)
        uh = jnp.einsum("...ba,...hb->...ha", u_bwd.conj(), h, precision=EXACT)
        out = out + gamma.reconstruct(uh, mu, s=+1)
    return out


def hop_oe(U_e, U_o, psi_e):
    """even -> odd hopping block."""
    return hop_block(U_e, U_o, psi_e, ODD)


def hop_eo(U_e, U_o, psi_o):
    """odd -> even hopping block."""
    return hop_block(U_e, U_o, psi_o, EVEN)


def apply_dhat(U_e, U_o, psi_e, kappa, hop_oe_fn=None, hop_eo_fn=None):
    """Even-odd preconditioned operator ``(1 - kappa^2 H_eo H_oe) psi_e``.

    ``hop_*_fn`` may be swapped for the Pallas-backed implementations.
    """
    hop_oe_fn = hop_oe_fn or hop_oe
    hop_eo_fn = hop_eo_fn or hop_eo
    tmp = hop_oe_fn(U_e, U_o, psi_e)
    return psi_e - (kappa * kappa) * hop_eo_fn(U_e, U_o, tmp)


def apply_dhat_dagger(U_e, U_o, psi_e, kappa, hop_oe_fn=None, hop_eo_fn=None):
    """``Dhat^dag`` via gamma5-hermiticity (g5 Dhat g5 = Dhat^dag)."""
    g5 = jnp.asarray(gamma.GAMMA5)
    g5psi = jnp.einsum("ij,...jc->...ic", g5, psi_e, precision=EXACT)
    out = apply_dhat(U_e, U_o, g5psi, kappa, hop_oe_fn, hop_eo_fn)
    return jnp.einsum("ij,...jc->...ic", g5, out, precision=EXACT)


def apply_wilson_eo(U_e, U_o, psi_e, psi_o, kappa):
    """Full D_W in even-odd form: returns (D psi)_e, (D psi)_o."""
    return (psi_e - kappa * hop_eo(U_e, U_o, psi_o),
            psi_o - kappa * hop_oe(U_e, U_o, psi_e))


def _masked_roll_x(arr: jnp.ndarray, direction: int, out_parity: int,
                   parity_offset) -> jnp.ndarray:
    """Parity-masked x-roll on a ``(T, Z, Y, Xh, ...)`` array (sel + tbl)."""
    T, Z, Y = arr.shape[:3]
    trailing = arr.ndim - 4
    par = _row_par((T, Z, Y), trailing=trailing)
    m = (out_parity + (1 if direction > 0 else 0) + parity_offset) % 2
    rolled = jnp.roll(arr, -direction, axis=3)
    return jnp.where(par == m, rolled, arr)


def hop_block_ext(U_out: jnp.ndarray, U_in_ext: jnp.ndarray,
                  src_ext: jnp.ndarray, out_parity: int,
                  parity_offset=0) -> jnp.ndarray:
    """Hopping block on halo-extended arrays (the distributed local step).

    ``src_ext``: ``(Tl+2, Zl+2, Y, Xh, 4, 3)`` with t/z halos;
    ``U_in_ext``: ``(4, Tl+2, Zl+2, Y, Xh, 3, 3)``;
    ``U_out``: unextended ``(4, Tl, Zl, Y, Xh, 3, 3)``.
    ``parity_offset`` is the (possibly traced) global ``(t0+z0) % 2`` of
    the local block origin.

    z/t neighbors are static slices of the extended arrays; x/y shifts are
    in-plane (periodic is exact there because x/y are never sharded).
    """
    c = src_ext[1:-1, 1:-1]
    out = jnp.zeros_like(c)

    def fwd_bwd(mu):
        if mu == MU_X:
            fwd = _masked_roll_x(c, +1, out_parity, parity_offset)
            bwd = _masked_roll_x(c, -1, out_parity, parity_offset)
            u_bwd = _masked_roll_x(U_in_ext[0, 1:-1, 1:-1], -1, out_parity,
                                   parity_offset)
        elif mu == MU_Y:
            fwd = jnp.roll(c, -1, axis=2)
            bwd = jnp.roll(c, +1, axis=2)
            u_bwd = jnp.roll(U_in_ext[1, 1:-1, 1:-1], +1, axis=2)
        elif mu == MU_Z:
            fwd = src_ext[1:-1, 2:]
            bwd = src_ext[1:-1, :-2]
            u_bwd = U_in_ext[2, 1:-1, :-2]
        else:
            fwd = src_ext[2:, 1:-1]
            bwd = src_ext[:-2, 1:-1]
            u_bwd = U_in_ext[3, :-2, 1:-1]
        return fwd, bwd, u_bwd

    for mu in range(NDIM):
        fwd, bwd, u_bwd = fwd_bwd(mu)
        h = gamma.project(fwd, mu, s=-1)
        uh = jnp.einsum("...ab,...hb->...ha", U_out[mu], h, precision=EXACT)
        out = out + gamma.reconstruct(uh, mu, s=-1)
        h = gamma.project(bwd, mu, s=+1)
        uh = jnp.einsum("...ba,...hb->...ha", u_bwd.conj(), h, precision=EXACT)
        out = out + gamma.reconstruct(uh, mu, s=+1)
    return out


# ---------------------------------------------------------------------------
# Oracles via the full lattice (slow, for tests).
# ---------------------------------------------------------------------------

def hop_block_oracle(U: jnp.ndarray, src: jnp.ndarray, out_parity: int) -> jnp.ndarray:
    """Same contraction through the full-lattice reference operator."""
    zeros = jnp.zeros_like(src)
    full = unpack(src, zeros) if out_parity == ODD else unpack(zeros, src)
    hopped = wilson.hop(U, full)
    even, odd = pack(hopped)
    return odd if out_parity == ODD else even
