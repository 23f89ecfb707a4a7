"""The plain reference: the full-lattice Wilson operator and the true
residual of a returned solution, in complex128 on the host CPU.

It imports nothing of the program under test.  The conventions are the
ones the program documents for its users:

* sites ``[t, z, y, x]``; spinors ``(T, Z, Y, X, 4, 3)``; links
  ``U[mu, t, z, y, x]`` of shape ``(3, 3)`` from ``x`` to ``x + mu``,
  with ``mu`` = 0 (x), 1 (y), 2 (z), 3 (t); periodic in every direction;
* ``D_W psi = psi - kappa * H psi`` with
  ``H(x,y) = sum_mu [(1 - g_mu) U_mu(x) d_{x+mu,y}
  + (1 + g_mu) U_mu(x-mu)^dag d_{x-mu,y}]`` in the DeGrand-Rossi basis;
* even/odd halves compacted in x (see :mod:`chipbench.sources`).

``true_residuals`` gives ``|eta - D_W xi| / |eta|`` per column, the
number each returned solution is held to.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

AXIS_OF_MU = {0: 3, 1: 2, 2: 1, 3: 0}


def _project(p, mu, s):
    """Half spinor of ``(1 + s g_mu) p``: ``(..., 4, 3) -> (..., 2, 3)``."""
    p0, p1, p2, p3 = (p[..., i, :] for i in range(4))
    si = s * 1j
    if mu == 0:
        h0, h1 = p0 + si * p3, p1 + si * p2
    elif mu == 1:
        h0, h1 = p0 - s * p3, p1 + s * p2
    elif mu == 2:
        h0, h1 = p0 + si * p2, p1 - si * p3
    else:
        h0, h1 = p0 + s * p2, p1 + s * p3
    return jnp.stack([h0, h1], axis=-2)


def _reconstruct(h, mu, s):
    """The 4-spinor ``(1 + s g_mu) p`` from its half spinor."""
    h0, h1 = h[..., 0, :], h[..., 1, :]
    si = s * 1j
    if mu == 0:
        r2, r3 = -si * h1, -si * h0
    elif mu == 1:
        r2, r3 = s * h1, -s * h0
    elif mu == 2:
        r2, r3 = -si * h0, si * h1
    else:
        r2, r3 = s * h0, s * h1
    return jnp.stack([h0, h1, r2, r3], axis=-2)


def _times(h, m):
    """``out[..., i, a] = sum_b m[..., a, b] h[..., i, b]``, written as
    element-wise products so that no backend rounds it."""
    return sum(h[..., :, b, None] * m[..., None, :, b] for b in range(3))


def hop(U, psi):
    """``H psi`` on the full lattice."""
    out = jnp.zeros_like(psi)
    for mu in range(4):
        ax = AXIS_OF_MU[mu]
        fwd = _project(jnp.roll(psi, -1, axis=ax), mu, -1)
        out = out + _reconstruct(_times(fwd, U[mu]), mu, -1)
        u_back = jnp.swapaxes(jnp.roll(U[mu], 1, axis=ax), -1, -2).conj()
        bwd = _project(jnp.roll(psi, 1, axis=ax), mu, +1)
        out = out + _reconstruct(_times(bwd, u_back), mu, +1)
    return out


def apply_wilson(U, psi, kappa):
    """``D_W psi = psi - kappa H psi``."""
    return psi - kappa * hop(U, psi)


def _row_parity(shape):
    T, Z, Y = shape[:3]
    t, z, y = np.ogrid[:T, :Z, :Y]
    return ((t + z + y) % 2)[..., None, None, None]


def unpack(even, odd):
    """Full-lattice field from its even/odd halves ``(T, Z, Y, Xh, 4, 3)``."""
    par = _row_parity(even.shape)
    x0 = jnp.where(par == 0, even, odd)
    x1 = jnp.where(par == 0, odd, even)
    T, Z, Y, Xh = even.shape[:4]
    return jnp.stack([x0, x1], axis=4).reshape(T, Z, Y, 2 * Xh, 4, 3)


def pack(full):
    """Even/odd halves of a full-lattice field ``(T, Z, Y, X, ...)``."""
    T, Z, Y, X = full.shape[:4]
    v = full.reshape(T, Z, Y, X // 2, 2, *full.shape[4:])
    par = _row_parity(full.shape)[..., 0, 0]
    par = par.reshape(par.shape + (1,) * (full.ndim - 4))
    v0, v1 = v[:, :, :, :, 0], v[:, :, :, :, 1]
    return jnp.where(par == 0, v0, v1), jnp.where(par == 0, v1, v0)


def _residual(U, kappa, eta_e, eta_o, xi_e, xi_o):
    c = jnp.complex128
    eta = unpack(eta_e.astype(c), eta_o.astype(c))
    r = eta - apply_wilson(U, unpack(xi_e.astype(c), xi_o.astype(c)), kappa)
    return jnp.sqrt(jnp.sum(jnp.abs(r) ** 2) / jnp.sum(jnp.abs(eta) ** 2))


class Reference:
    """The reference operator on one gauge field, on the host CPU.

    ``U`` is the full gauge field ``(4, T, Z, Y, X, 3, 3)`` the benchmark
    made; it is held here in complex128.
    """

    def __init__(self, U, kappa: float):
        self.cpu = jax.devices("cpu")[0]
        self.kappa = float(kappa)
        with jax.enable_x64(True), jax.default_device(self.cpu):
            self.U = jax.device_put(
                jnp.asarray(jax.device_put(U, self.cpu), jnp.complex128),
                self.cpu)
            self._fn = jax.jit(_residual)

    def true_residuals(self, eta_e, eta_o, xi_e, xi_o) -> np.ndarray:
        """``|eta - D_W xi| / |eta|`` per column (float64); the arrays
        carry a leading column axis when they are 7-d."""
        eta_e, eta_o = np.asarray(eta_e), np.asarray(eta_o)
        xi_e, xi_o = np.asarray(xi_e), np.asarray(xi_o)
        if eta_e.ndim == 6:
            eta_e, eta_o, xi_e, xi_o = (a[None] for a in
                                        (eta_e, eta_o, xi_e, xi_o))
        out = []
        with jax.enable_x64(True), jax.default_device(self.cpu):
            for j in range(eta_e.shape[0]):
                args = jax.device_put(
                    (eta_e[j], eta_o[j], xi_e[j], xi_o[j]), self.cpu)
                out.append(float(self._fn(self.U, self.kappa, *args)))
        return np.asarray(out)
