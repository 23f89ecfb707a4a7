"""Lattice geometry helpers.

Conventions (used throughout the package):

* Site arrays are indexed ``[t, z, y, x]`` (t slowest, x fastest).
* A spinor field on the full lattice has shape ``(T, Z, Y, X, 4, 3)``
  (spin, color) and complex dtype.
* A gauge field has shape ``(4, T, Z, Y, X, 3, 3)`` with direction index
  ``mu``: 0 = x, 1 = y, 2 = z, 3 = t.  ``U[mu, t, z, y, x]`` lives on the
  link from site ``x`` to ``x + mu_hat``.
* Site parity is ``(t + z + y + x) % 2``; parity 0 is "even".

The x-direction is the SIMD-packed direction of the paper; the even/odd
arrays are compacted in x (``Xh = X // 2``), see :mod:`repro.core.evenodd`.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Precision of every matrix product in the lattice numerics.  The TPU's
# default rounds f32 matmul operands to bf16 (about three significant
# digits); the operators, the residual checks and the Krylov reductions
# need exact f32 products there, as they get on the CPU.
EXACT = jax.lax.Precision.HIGHEST

# Direction indices.
MU_X, MU_Y, MU_Z, MU_T = 0, 1, 2, 3
# Array axis carrying each direction for a ``(T, Z, Y, X, ...)`` field.
AXIS_OF_MU = {MU_X: 3, MU_Y: 2, MU_Z: 1, MU_T: 0}
NDIM = 4


@dataclasses.dataclass(frozen=True)
class LatticeGeometry:
    """Global lattice geometry (sizes are in sites, full lattice)."""

    shape: Tuple[int, int, int, int]  # (T, Z, Y, X)

    def __post_init__(self):
        T, Z, Y, X = self.shape
        if X % 2:
            raise ValueError(f"X extent must be even for even-odd layout, got {X}")

    @property
    def T(self) -> int:
        return self.shape[0]

    @property
    def Z(self) -> int:
        return self.shape[1]

    @property
    def Y(self) -> int:
        return self.shape[2]

    @property
    def X(self) -> int:
        return self.shape[3]

    @property
    def Xh(self) -> int:
        return self.shape[3] // 2

    @property
    def n_sites(self) -> int:
        return int(np.prod(self.shape))

    def spinor_shape(self, even_odd: bool = False) -> Tuple[int, ...]:
        if even_odd:
            return (self.T, self.Z, self.Y, self.Xh, 4, 3)
        return (self.T, self.Z, self.Y, self.X, 4, 3)

    def gauge_shape(self, even_odd: bool = False) -> Tuple[int, ...]:
        if even_odd:
            return (NDIM, self.T, self.Z, self.Y, self.Xh, 3, 3)
        return (NDIM, self.T, self.Z, self.Y, self.X, 3, 3)


def site_parity(shape: Sequence[int]) -> jnp.ndarray:
    """(T, Z, Y, X) int32 array of site parities (0 = even)."""
    T, Z, Y, X = shape
    t = jnp.arange(T).reshape(T, 1, 1, 1)
    z = jnp.arange(Z).reshape(1, Z, 1, 1)
    y = jnp.arange(Y).reshape(1, 1, Y, 1)
    x = jnp.arange(X).reshape(1, 1, 1, X)
    return (t + z + y + x) % 2


def row_parity(shape: Sequence[int], trailing_dims: int = 0) -> jnp.ndarray:
    """``(t + z + y) % 2`` per x-row, shaped ``(T, Z, Y, 1, *1s)``.

    This is the parity that decides the even-odd x-shift pattern (the
    predicate of the paper's ``sel`` instruction, Fig. 5).  The result
    broadcasts against an even/odd array ``(T, Z, Y, Xh, ...)`` when
    ``trailing_dims`` extra singleton axes are appended.
    """
    T, Z, Y = shape[0], shape[1], shape[2]
    t = jnp.arange(T).reshape(T, 1, 1)
    z = jnp.arange(Z).reshape(1, Z, 1)
    y = jnp.arange(Y).reshape(1, 1, Y)
    par = (t + z + y) % 2
    par = par[..., None]  # x axis
    for _ in range(trailing_dims):
        par = par[..., None]
    return par


def shift(field: jnp.ndarray, mu: int, direction: int) -> jnp.ndarray:
    """Periodic shift of a full-lattice field.

    ``direction=+1`` returns ``field(x + mu_hat)`` (forward neighbor),
    ``direction=-1`` returns ``field(x - mu_hat)``.
    """
    axis = AXIS_OF_MU[mu]
    return jnp.roll(field, -direction, axis=axis)
