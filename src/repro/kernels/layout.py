"""Planar (re/im-separated) field layout for the TPU kernel.

The A64FX implementation keeps real and imaginary parts in *separate* SIMD
vectors and packs an x-y tile of sites into each vector (paper Sec. 3.2).
The TPU analogue puts the ``(Y, Xh)`` site plane in the two trailing array
dims — sublanes x lanes of the VPU — and splits complex numbers into a
re/im component axis:

* spinor: ``(T, Z, Y, Xh, 4, 3)`` complex  <->  ``(T, Z, 24, Y, Xh)`` real
  with component index ``c = (spin * 3 + color) * 2 + reim``;
* gauge:  ``(4, T, Z, Y, Xh, 3, 3)`` complex <-> ``(4, T, Z, 18, Y, Xh)``
  real with ``c = (row * 3 + col) * 2 + reim``.

This is the AoSoA layout of Eq. (6)/(7) with the SIMD vector grown to a
whole VMEM-resident plane.

Multi-RHS batching: the spinor conversions accept arbitrary *leading*
batch dims, so a block of right-hand sides ``(nrhs, T, Z, Y, Xh, 4, 3)``
maps to the batched planar layout ``(nrhs, T, Z, 24, Y, Xh)`` — the
layout the batched kernels eat while loading each gauge plane once for
the whole block.

Lane-dense spinors: the bytes of a complex64 ``(..., T, Z, Y, Xh, 4, 3)``
array already hold the interleaved reals ``(..., T, Z, Y, Xh, 24)`` in
planar component order, so its float32 view, read as ``(rows, 128)``,
is the planar vector up to one transpose.  :func:`spinor_lanes` takes
that view on the host without a pass over the data;
:func:`planar_from_lanes` and :func:`lanes_from_planar` are the
transposes, traceable, for the device side.  A ``(rows, 128)`` float32
array whose size fills whole ``(8, 128)`` tiles is stored unpadded on a
TPU, where a complex ``(..., 4, 3)`` one is not.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

SPINOR_COMPS = 24  # 4 spin x 3 color x re/im
GAUGE_COMPS = 18   # 3 x 3 x re/im
GAUGE_COMPS_TWO_ROW = 12   # rows a, b; c = conj(a x b) rebuilt in-register
GAUGE_COMPS_MINIMAL = 8    # a2, a3, b1 + phases of a1, c1

#: compression mode -> planar component-plane count
GAUGE_COMPRESSIONS = {
    "none": GAUGE_COMPS,
    "two_row": GAUGE_COMPS_TWO_ROW,
    "minimal": GAUGE_COMPS_MINIMAL,
}


def _real_dtype_of(complex_dtype):
    return (jnp.float64 if jnp.dtype(complex_dtype) == jnp.dtype(jnp.complex128)
            else jnp.float32)


def spinor_to_planar(psi: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """``(..., T, Z, Y, Xh, 4, 3)`` complex -> ``(..., T, Z, 24, Y, Xh)``.

    Leading batch dims (the multi-RHS axis) pass through unchanged.
    """
    *batch, T, Z, Y, Xh = psi.shape[:-2]
    arr = jnp.stack([psi.real, psi.imag], axis=-1)    # (...,T,Z,Y,Xh,4,3,2)
    # (Y, Xh) to the trailing (sublane, lane) position.
    arr = jnp.moveaxis(arr, (-5, -4), (-2, -1))       # (...,T,Z,4,3,2,Y,Xh)
    return arr.reshape(*batch, T, Z, SPINOR_COMPS, Y, Xh).astype(dtype)


def spinor_from_planar(p: jnp.ndarray, dtype=jnp.complex64) -> jnp.ndarray:
    """Inverse of :func:`spinor_to_planar` (batch dims pass through)."""
    *batch, T, Z, _, Y, Xh = p.shape
    arr = p.astype(_real_dtype_of(dtype)).reshape(*batch, T, Z, 4, 3, 2, Y, Xh)
    arr = jnp.moveaxis(arr, (-2, -1), (-5, -4))       # (...,T,Z,Y,Xh,4,3,2)
    return (arr[..., 0] + 1j * arr[..., 1]).astype(dtype)


LANES = 128
#: float32 elements in one (8, 128) TPU tile
LANE_TILE = 8 * LANES


def lanes_fit(shape, dtype) -> bool:
    """Whether a spinor of ``shape`` and ``dtype`` has a lane-dense view:
    complex64, with its float32 elements filling whole ``(8, 128)``
    tiles."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.complex64)
            and (2 * math.prod(shape)) % LANE_TILE == 0)


def spinor_lanes(psi) -> np.ndarray:
    """The ``(rows, 128)`` float32 view of a host complex64 spinor's own
    bytes (no copy; see :func:`lanes_fit`)."""
    return np.asarray(psi).view(np.float32).reshape(-1, LANES)


def spinor_from_lanes(lanes, shape) -> np.ndarray:
    """Inverse of :func:`spinor_lanes`: the complex64 spinor of ``shape``
    whose bytes are the host array ``lanes`` (no copy)."""
    return np.asarray(lanes).view(np.complex64).reshape(shape)


def planar_from_lanes(lanes, shape, dtype=jnp.float32):
    """Lane-dense view of a complex spinor of ``shape`` -> its planar
    vector: :func:`spinor_to_planar` of that spinor, traceable."""
    *batch, T, Z, Y, Xh = shape[:-2]
    arr = lanes.reshape(*batch, T, Z, Y, Xh, SPINOR_COMPS)
    return jnp.moveaxis(arr, (-3, -2), (-2, -1)).astype(dtype)


def lanes_from_planar(p):
    """Planar vector -> the lane-dense view of
    :func:`spinor_from_planar`'s complex64 spinor, traceable."""
    arr = jnp.moveaxis(p.astype(jnp.float32), (-2, -1), (-3, -2))
    return arr.reshape(-1, LANES)


def gamma5_planar(p: jnp.ndarray) -> jnp.ndarray:
    """``gamma5 psi`` directly on a planar spinor ``(..., 24, Y, Xh)``.

    ``gamma5 = diag(1, 1, -1, -1)`` in this basis, and the planar
    component index is ``(spin * 3 + color) * 2 + reim``, so it simply
    negates component planes 12..23 — no complex round-trip needed.
    """
    sign = jnp.concatenate([jnp.ones((12,), p.dtype),
                            -jnp.ones((12,), p.dtype)])
    return p * sign.reshape(SPINOR_COMPS, 1, 1)


def gauge_to_planar(u: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """``(4, T, Z, Y, Xh, 3, 3)`` complex -> ``(4, T, Z, 18, Y, Xh)`` real."""
    _, T, Z, Y, Xh = u.shape[:5]
    arr = jnp.stack([u.real, u.imag], axis=-1)           # (4,T,Z,Y,Xh,3,3,2)
    arr = arr.transpose(0, 1, 2, 5, 6, 7, 3, 4)          # (4,T,Z,3,3,2,Y,Xh)
    return arr.reshape(4, T, Z, GAUGE_COMPS, Y, Xh).astype(dtype)


def gauge_from_planar(p: jnp.ndarray, dtype=jnp.complex64) -> jnp.ndarray:
    """Inverse of :func:`gauge_to_planar`.

    Accepts compressed planar gauge fields too (12 or 8 component
    planes): they are expanded to the full 18 planes first, so every
    caller that round-trips through the complex form sees reconstructed
    full SU(3) links regardless of the storage representation.
    """
    if p.shape[-3] != GAUGE_COMPS:
        p = gauge_expand_planar(p)
    _, T, Z, _, Y, Xh = p.shape
    arr = p.astype(_real_dtype_of(dtype)).reshape(4, T, Z, 3, 3, 2, Y, Xh)
    arr = arr.transpose(0, 1, 2, 6, 7, 3, 4, 5)          # (4,T,Z,Y,Xh,3,3,2)
    return (arr[..., 0] + 1j * arr[..., 1]).astype(dtype)


# --- SU(3) link compression (planar form) ----------------------------
#
# Component-plane index is c = (row * 3 + col) * 2 + reim, i.e. planes
#   a1=(0,1)  a2=(2,3)  a3=(4,5)     row a = U[0,:]
#   b1=(6,7)  b2=(8,9)  b3=(10,11)   row b = U[1,:]
#   c1=(12,13) c2=(14,15) c3=(16,17) row c = U[2,:]
#
# two_row (12 real): keep rows a and b — a contiguous plane slice — and
# rebuild c = conj(a x b) in-register (~42 extra flops per link).
#
# minimal (8 real): keep a2, a3, b1 and the *phases* of a1 and c1.
# Unitarity fixes the moduli: with D = |a2|^2 + |a3|^2,
#   |a1| = sqrt(1 - D),   |c1| = sqrt(D - |b1|^2),
# and the pair (b2, b3) solves the 2x2 linear system given by
#   a2 b3 - a3 b2 = conj(c1)   (c = conj(a x b))
#   conj(a2) b2 + conj(a3) b3 = -conj(a1) b1   (row orthogonality)
# whose determinant is -D, so reconstruction divides by D once
# (~150 extra flops per link, incl. sin/cos). Degenerate caveat: at
# D = 0 (e.g. the unit gauge, |a1| = 1) the system is singular and the
# stored 8 numbers no longer determine the link — "minimal" is for
# *interacting* (random/thermalized) gauge fields; the 1/D division is
# clamped so free-field links degrade gracefully instead of NaN-ing.


def _cmul(ar, ai, br, bi):
    """(ar + i ai)(br + i bi) on split re/im planes."""
    return ar * br - ai * bi, ar * bi + ai * br


def expand_links_planes(u):
    """Expand one direction's planar link planes ``(gc, ...)`` to 18.

    ``u`` has the component axis *leading* (the orientation the hopping
    kernels index); trailing dims are arbitrary. For ``gc == 18`` the
    input is returned unchanged — an expanded call site adds nothing to
    the jaxpr. Otherwise a list of the 18 component planes is returned
    (callers index it exactly like an array's leading axis).

    Reconstruction is element-wise, so lane/sublane rolls and boundary
    masks commute with it — kernels shift the *compressed* planes and
    expand after, which is cheaper.
    """
    gc = u.shape[0]
    if gc == GAUGE_COMPS:
        return u
    if gc == GAUGE_COMPS_TWO_ROW:
        (a1r, a1i, a2r, a2i, a3r, a3i,
         b1r, b1i, b2r, b2i, b3r, b3i) = (u[i] for i in range(12))
        # c1 = conj(a2 b3 - a3 b2)
        t1r, t1i = _cmul(a2r, a2i, b3r, b3i)
        t2r, t2i = _cmul(a3r, a3i, b2r, b2i)
        c1r, c1i = t1r - t2r, t2i - t1i
    elif gc == GAUGE_COMPS_MINIMAL:
        a2r, a2i, a3r, a3i, b1r, b1i, tha, thc = (u[i] for i in range(8))
        d = a2r * a2r + a2i * a2i + a3r * a3r + a3i * a3i
        a1m = jnp.sqrt(jnp.maximum(1.0 - d, 0.0))
        a1r, a1i = a1m * jnp.cos(tha), a1m * jnp.sin(tha)
        c1m = jnp.sqrt(jnp.maximum(d - (b1r * b1r + b1i * b1i), 0.0))
        c1r, c1i = c1m * jnp.cos(thc), c1m * jnp.sin(thc)
        dinv = 1.0 / jnp.maximum(d, 1e-30)
        # s = -conj(a1) b1
        sr, si = _cmul(a1r, -a1i, b1r, b1i)
        sr, si = -sr, -si
        # b2 = (a2 s - conj(a3) conj(c1)) / D
        t1r, t1i = _cmul(a2r, a2i, sr, si)
        t2r, t2i = _cmul(a3r, -a3i, c1r, -c1i)
        b2r, b2i = (t1r - t2r) * dinv, (t1i - t2i) * dinv
        # b3 = (a3 s + conj(a2) conj(c1)) / D
        t3r, t3i = _cmul(a3r, a3i, sr, si)
        t4r, t4i = _cmul(a2r, -a2i, c1r, -c1i)
        b3r, b3i = (t3r + t4r) * dinv, (t3i + t4i) * dinv
    else:
        raise ValueError(
            f"planar gauge block has {gc} component planes; expected one "
            f"of {sorted(GAUGE_COMPRESSIONS.values())}")
    # c2 = conj(a3 b1 - a1 b3), c3 = conj(a1 b2 - a2 b1)
    t1r, t1i = _cmul(a3r, a3i, b1r, b1i)
    t2r, t2i = _cmul(a1r, a1i, b3r, b3i)
    c2r, c2i = t1r - t2r, t2i - t1i
    t1r, t1i = _cmul(a1r, a1i, b2r, b2i)
    t2r, t2i = _cmul(a2r, a2i, b1r, b1i)
    c3r, c3i = t1r - t2r, t2i - t1i
    return [a1r, a1i, a2r, a2i, a3r, a3i,
            b1r, b1i, b2r, b2i, b3r, b3i,
            c1r, c1i, c2r, c2i, c3r, c3i]


def gauge_compress_planar(p: jnp.ndarray, mode: str) -> jnp.ndarray:
    """Compress a full planar gauge field ``(4, T, Z, 18, Y, Xh)``.

    ``mode`` is one of :data:`GAUGE_COMPRESSIONS`; ``"none"`` returns
    the input unchanged. The compressed array keeps the same axis order
    with a smaller component-plane axis (12 or 8).
    """
    if mode in (None, "none"):
        return p
    if p.shape[-3] != GAUGE_COMPS:
        raise ValueError(
            f"can only compress a full 18-plane gauge field, got "
            f"{p.shape[-3]} planes")
    if mode == "two_row":
        return p[..., :GAUGE_COMPS_TWO_ROW, :, :]
    if mode == "minimal":
        u = jnp.moveaxis(p, -3, 0)
        f32 = jnp.float32 if p.dtype != jnp.float64 else jnp.float64
        tha = jnp.arctan2(u[1].astype(f32), u[0].astype(f32)).astype(p.dtype)
        thc = jnp.arctan2(u[13].astype(f32), u[12].astype(f32)).astype(p.dtype)
        planes = [u[2], u[3], u[4], u[5], u[6], u[7], tha, thc]
        return jnp.moveaxis(jnp.stack(planes), 0, -3)
    raise ValueError(f"unknown gauge compression mode {mode!r}")


def gauge_expand_planar(p: jnp.ndarray) -> jnp.ndarray:
    """Expand a compressed planar gauge field back to 18 planes."""
    if p.shape[-3] == GAUGE_COMPS:
        return p
    planes = expand_links_planes(jnp.moveaxis(p, -3, 0))
    return jnp.moveaxis(jnp.stack(planes), 0, -3)
