"""SU(3) gauge-field utilities."""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from .lattice import EXACT, NDIM, shift


def random_su3(key: jax.Array, shape: Sequence[int], dtype=jnp.complex64) -> jnp.ndarray:
    """Haar-ish random SU(3) matrices of shape ``(*shape, 3, 3)``.

    Gram-Schmidt (QR) on a random complex matrix, with the determinant phase
    divided out so ``det U = 1`` exactly (up to fp rounding).
    """
    kr, ki = jax.random.split(key)
    m = (jax.random.normal(kr, (*shape, 3, 3))
         + 1j * jax.random.normal(ki, (*shape, 3, 3))).astype(dtype)
    q, r = jnp.linalg.qr(m)
    # Fix the U(1) phases left free by QR: make diag(r) real-positive.
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    q = q * (d / jnp.abs(d))[..., None, :]
    det = jnp.linalg.det(q)
    return q * (det[..., None, None] ** (-1.0 / 3.0))


def random_gauge(key: jax.Array, lat_shape: Sequence[int], dtype=jnp.complex64) -> jnp.ndarray:
    """Random gauge field ``(4, T, Z, Y, X, 3, 3)``."""
    return random_su3(key, (NDIM, *lat_shape), dtype=dtype)


def unit_gauge(lat_shape: Sequence[int], dtype=jnp.complex64) -> jnp.ndarray:
    """Free-field (identity) gauge configuration."""
    eye = jnp.eye(3, dtype=dtype)
    return jnp.broadcast_to(eye, (NDIM, *lat_shape, 3, 3))


def weak_gauge(key: jax.Array, lat_shape: Sequence[int],
               eps: float = 0.2, dtype=jnp.complex64) -> jnp.ndarray:
    """Weak-field (smooth) gauge configuration ``U = exp(i eps H)``
    with ``H`` random Hermitian traceless — exactly SU(3), a small
    fluctuation around the free field.

    The physics that makes this the deflation test bed: a smooth
    configuration keeps the free operator's momentum-mode structure, so
    the low end of ``Dhat^dag Dhat`` is a few ISOLATED (and degenerate
    — 12-fold at p=0: 4 spinor x 3 color) clusters that a small
    deflation basis can actually remove, whereas a Haar-random ("hot")
    gauge smears the low spectrum into a quasi-continuum no small basis
    helps with.
    """
    kr, ki = jax.random.split(key)
    shape = (NDIM, *lat_shape, 3, 3)
    a = (jax.random.normal(kr, shape)
         + 1j * jax.random.normal(ki, shape)).astype(jnp.complex64)
    h = 0.5 * (a + jnp.conj(jnp.swapaxes(a, -1, -2)))
    tr = jnp.trace(h, axis1=-2, axis2=-1) / 3.0
    h = h - tr[..., None, None] * jnp.eye(3, dtype=h.dtype)
    return jax.scipy.linalg.expm(1j * eps * h).astype(dtype)


def compress_two_row(U: jnp.ndarray) -> jnp.ndarray:
    """Keep the first two rows: ``(..., 3, 3)`` -> ``(..., 2, 3)``.

    12 real numbers per link instead of 18. Exact for any SU(3) matrix:
    the third row is ``conj(a x b)`` (see :func:`reconstruct_two_row`).
    """
    return U[..., :2, :]


def reconstruct_two_row(W: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`compress_two_row`: ``(..., 2, 3)`` -> ``(..., 3, 3)``."""
    a, b = W[..., 0, :], W[..., 1, :]
    c = jnp.cross(a, b).conj()
    return jnp.stack([a, b, c], axis=-2)


def compress_minimal(U: jnp.ndarray) -> jnp.ndarray:
    """8-real compression: ``(..., 3, 3)`` complex -> ``(..., 8)`` real.

    Stores ``a2, a3, b1`` (re/im) and the phases of ``a1`` and ``c1``;
    unitarity fixes the rest. Singular when ``|a2|^2 + |a3|^2 == 0``
    (e.g. the unit gauge) — intended for interacting gauge fields. More
    sensitive to rounding than ``two_row`` (a 1/D division), so expect
    ~1e-4 round-trip error in f32 instead of ~1e-6.
    """
    real = jnp.float64 if U.dtype == jnp.complex128 else jnp.float32
    a2, a3, b1 = U[..., 0, 1], U[..., 0, 2], U[..., 1, 0]
    th_a = jnp.angle(U[..., 0, 0]).astype(real)
    th_c = jnp.angle(U[..., 2, 0]).astype(real)
    return jnp.stack(
        [a2.real.astype(real), a2.imag.astype(real),
         a3.real.astype(real), a3.imag.astype(real),
         b1.real.astype(real), b1.imag.astype(real), th_a, th_c], axis=-1)


def reconstruct_minimal(W: jnp.ndarray, dtype=jnp.complex64) -> jnp.ndarray:
    """Inverse of :func:`compress_minimal`: ``(..., 8)`` -> ``(..., 3, 3)``."""
    a2 = (W[..., 0] + 1j * W[..., 1]).astype(dtype)
    a3 = (W[..., 2] + 1j * W[..., 3]).astype(dtype)
    b1 = (W[..., 4] + 1j * W[..., 5]).astype(dtype)
    th_a, th_c = W[..., 6], W[..., 7]
    d = (jnp.abs(a2) ** 2 + jnp.abs(a3) ** 2).real
    a1 = (jnp.sqrt(jnp.maximum(1.0 - d, 0.0))
          * jnp.exp(1j * th_a)).astype(dtype)
    c1 = (jnp.sqrt(jnp.maximum(d - jnp.abs(b1) ** 2, 0.0))
          * jnp.exp(1j * th_c)).astype(dtype)
    dinv = (1.0 / jnp.maximum(d, 1e-30)).astype(dtype)
    s = -a1.conj() * b1
    b2 = (a2 * s - a3.conj() * c1.conj()) * dinv
    b3 = (a3 * s + a2.conj() * c1.conj()) * dinv
    c2 = (a3 * b1 - a1 * b3).conj()
    c3 = (a1 * b2 - a2 * b1).conj()
    row_a = jnp.stack([a1, a2, a3], axis=-1)
    row_b = jnp.stack([b1, b2, b3], axis=-1)
    row_c = jnp.stack([c1, c2, c3], axis=-1)
    return jnp.stack([row_a, row_b, row_c], axis=-2)


def unitarity_defect(U: jnp.ndarray) -> jnp.ndarray:
    """max |U U^dag - 1| over the field; ~1e-6 for healthy f32 SU(3)."""
    eye = jnp.eye(3, dtype=U.dtype)
    uud = jnp.einsum("...ab,...cb->...ac", U, U.conj(), precision=EXACT)
    return jnp.max(jnp.abs(uud - eye))


def project_su3(U: jnp.ndarray) -> jnp.ndarray:
    """Project ``(..., 3, 3)`` complex matrices onto SU(3).

    The unitary polar factor ``W V^dag`` of the SVD (the nearest unitary
    in Frobenius norm), with the residual determinant phase divided out
    — the repair half of a gauge-integrity audit
    (:func:`repro.resilience.repair_gauge`).  Links must be finite and
    non-singular; replace corrupted links first.
    """
    w, _, vh = jnp.linalg.svd(U)
    q = jnp.einsum("...ab,...bc->...ac", w, vh, precision=EXACT)
    det = jnp.linalg.det(q)
    return q * (det[..., None, None] ** (-1.0 / 3.0))


def plaquette(U: jnp.ndarray) -> jnp.ndarray:
    """Average plaquette ``Re tr P / 3`` over all sites and planes.

    ``P_{mu,nu}(x) = U_mu(x) U_nu(x+mu) U_mu(x+nu)^dag U_nu(x)^dag``;
    gauge invariant, equals 1 for the unit gauge.
    """
    total = 0.0
    count = 0
    for mu in range(NDIM):
        for nu in range(mu + 1, NDIM):
            u_mu, u_nu = U[mu], U[nu]
            u_nu_xmu = shift(u_nu, mu, +1)  # U_nu(x+mu)
            u_mu_xnu = shift(u_mu, nu, +1)  # U_mu(x+nu)
            p = jnp.einsum("...ab,...bc,...dc,...ed->...ae",
                           u_mu, u_nu_xmu, u_mu_xnu.conj(), u_nu.conj(),
                           precision=EXACT)
            tr = jnp.trace(p, axis1=-2, axis2=-1)
            total = total + jnp.mean(tr.real)
            count += 1
    return total / (3.0 * count)
