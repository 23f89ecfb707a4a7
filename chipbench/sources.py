"""Sources of the benchmark's traffic, made with numpy from ``--seed``.

A source is a pair of even/odd halves ``(eta_e, eta_o)`` of shape
``(T, Z, Y, X/2, 4, 3)``, complex64, with a leading column axis when it
has several columns.  The halves follow the public even-odd convention
of the solve API: ``even[t, z, y, xh] = full[t, z, y, 2*xh + (t+z+y) % 2]``
and ``odd[t, z, y, xh] = full[t, z, y, 2*xh + (t+z+y+1) % 2]``.
"""
from __future__ import annotations

import numpy as np

# Independent random streams drawn from one seed.
GAUGE, GAUSSIAN, POINT, SAMPLE = 0, 1, 2, 4


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``stream`` of ``seed`` (any whole number)."""
    return np.random.default_rng([int(seed) % 2**64, *stream])


def half_shape(extents) -> tuple:
    T, Z, Y, X = (int(e) for e in extents)
    return (T, Z, Y, X // 2, 4, 3)


def gaussian(seed: int, i: int, extents, columns: int = 1):
    """Source ``i``: complex Gaussian noise on every site, ``columns``
    columns (no column axis when ``columns`` is 1)."""
    lead = () if columns == 1 else (int(columns),)
    z = rng(seed, GAUSSIAN, i).standard_normal(
        (2,) + lead + half_shape(extents) + (2,), dtype=np.float32)
    eta = (z[..., 0] + 1j * z[..., 1]).astype(np.complex64)
    return eta[0], eta[1]


def point_site(seed: int, i: int, extents) -> tuple:
    g = rng(seed, POINT, i)
    return tuple(int(g.integers(e)) for e in extents)


def point(seed: int, i: int, extents):
    """Source ``i``: a 12-column point source (4 spins x 3 colours) at
    one site drawn from the seed; column ``3 * spin + colour``."""
    t, z, y, x = point_site(seed, i, extents)
    halves = [np.zeros((12,) + half_shape(extents), np.complex64)
              for _ in range(2)]
    parity = (t + z + y + x) % 2
    for spin in range(4):
        for colour in range(3):
            halves[parity][3 * spin + colour, t, z, y, x // 2,
                           spin, colour] = 1.0
    return halves[0], halves[1]


def make(kind: str, seed: int, i: int, extents, columns: int):
    """Source ``i`` of ``kind`` for a cell whose solves have ``columns``
    columns."""
    if kind == "point":
        if columns != 12:
            raise ValueError(f"a point source has 12 columns, not {columns}")
        return point(seed, i, extents)
    if kind == "gaussian":
        return gaussian(seed, i, extents, columns)
    raise ValueError(f"unknown source kind {kind!r}; known: gaussian, point")

