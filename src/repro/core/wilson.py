"""Full-lattice Wilson fermion matrix (textbook reference).

``D_W psi = psi - kappa * H psi`` with the hopping term

``H(x,y) = sum_mu [ (1 - g_mu) U_mu(x) d_{x+mu,y}
                  + (1 + g_mu) U_mu^dag(x - mu) d_{x-mu,y} ]``

This module is the slowest, clearest implementation; everything else
(even-odd packing, planar float layout, the Pallas kernel) is validated
against it, directly or transitively.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import gamma
from .lattice import EXACT, NDIM, shift

# Flop count per site of one hopping application, QXS convention (paper
# Sec. 2): 8 hops x (project 12 + SU(3) x half-spinor 132 + reconstruct 12)
# + 7 x 24 accumulate adds + 24 x 2 for the 1 - kappa*H axpy = 1368.
HOP_FLOPS_PER_SITE = 1320
DW_FLOPS_PER_SITE = 1368


def hop(U: jnp.ndarray, psi: jnp.ndarray) -> jnp.ndarray:
    """Apply the hopping term ``H psi`` on the full lattice.

    ``U``: ``(4, T, Z, Y, X, 3, 3)``; ``psi``: ``(T, Z, Y, X, 4, 3)``.
    """
    out = jnp.zeros_like(psi)
    for mu in range(NDIM):
        # Forward: (1 - g_mu) U_mu(x) psi(x + mu).
        fwd = shift(psi, mu, +1)
        h = gamma.project(fwd, mu, s=-1)
        uh = jnp.einsum("...ab,...hb->...ha", U[mu], h, precision=EXACT)
        out = out + gamma.reconstruct(uh, mu, s=-1)
        # Backward: (1 + g_mu) U_mu^dag(x - mu) psi(x - mu).
        bwd = shift(psi, mu, -1)
        u_bwd = shift(U[mu], mu, -1)  # U_mu(x - mu)
        h = gamma.project(bwd, mu, s=+1)
        uh = jnp.einsum("...ba,...hb->...ha", u_bwd.conj(), h, precision=EXACT)
        out = out + gamma.reconstruct(uh, mu, s=+1)
    return out


def apply_wilson(U: jnp.ndarray, psi: jnp.ndarray, kappa: float) -> jnp.ndarray:
    """``D_W psi = psi - kappa * H psi``."""
    return psi - kappa * hop(U, psi)


def apply_wilson_dagger(U: jnp.ndarray, psi: jnp.ndarray, kappa: float) -> jnp.ndarray:
    """``D_W^dag psi`` via gamma5-hermiticity: ``D^dag = g5 D g5``."""
    g5 = jnp.asarray(gamma.GAMMA5)
    g5psi = jnp.einsum("ij,...jc->...ic", g5, psi, precision=EXACT)
    return jnp.einsum("ij,...jc->...ic", g5, apply_wilson(U, g5psi, kappa),
                      precision=EXACT)
