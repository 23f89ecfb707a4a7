"""Pallas TPU kernel for the even-odd Wilson hopping blocks.

Maps the paper's A64FX SIMD strategy onto the TPU memory hierarchy:

* grid over ``(T, Z)``; each grid step owns one x-y site plane — the 2-D
  SIMD tile of the paper grown to a VMEM block (``BlockSpec`` below);
* the x-shift of the even-odd compacted layout (paper Fig. 5, ``sel`` +
  ``tbl``) is a lane-roll of the plane masked by the row parity
  ``(t+z+y) % 2``;
* the y-shift (Fig. 6, ``ext``) is a sublane-roll;
* z/t neighbors arrive as extra pipelined operands of the *same* array
  with shifted ``index_map`` (modular wrap for the periodic single-shard
  case, or offset-by-one into halo-extended arrays for the distributed
  case) — no gather/scatter anywhere, exactly the paper's rule;
* complex arithmetic is planar: separate re/im component planes, pure f32
  mul/add on the VPU (the A64FX argument against ``fcmla`` becomes a hard
  constraint on TPU);
* SU(3) x half-spinor products are fully unrolled element-wise FMAs over
  the plane: color=3 contractions are far below MXU size, so the VPU is
  the right unit — the systolic array is *not* used, by design.

All 8 hop directions are computed and accumulated in VMEM registers per
plane; the plane is written once.  Optionally the kernel fuses the
``psi0 + coeff * hop`` axpy of the even-odd preconditioned operator so the
accumulator never round-trips through HBM (beyond-paper fusion; QWS does
the analogous fusion on A64FX).

Multi-RHS batching (Duerr-style right-hand-side parallelism): a batched
planar source ``(nrhs, T, Z, 24, Y, Xh)`` runs through the SAME grid —
each (t, z) step loads the gauge planes ONCE and applies the unrolled
SU(3) x half-spinor math to the whole RHS block via broadcasting, so the
flops-per-gauge-byte ratio grows ~nrhs x (the kernel is memory-bound on
the gauge stream at nrhs=1).  :func:`hop_traffic_model` is the
amortization model the benchmarks report next to measured numbers.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from .layout import (GAUGE_COMPS, GAUGE_COMPS_MINIMAL, GAUGE_COMPS_TWO_ROW,
                     SPINOR_COMPS, expand_links_planes)

# Flops per lattice site of one hopping block application, QXS convention.
HOP_FLOPS_PER_SITE = 1320

# Extra in-register flops to rebuild one full SU(3) link from its
# compressed planes (see layout.expand_links_planes): two_row rebuilds
# row c = conj(a x b) (6 complex mul + 3 sub), minimal additionally
# solves the 2x2 system for (b2, b3) and evaluates sqrt/sin/cos for the
# phase-encoded a1/c1.  The hopping block expands 8 links per site.
RECON_FLOPS_PER_LINK = {
    GAUGE_COMPS: 0,
    GAUGE_COMPS_TWO_ROW: 42,
    GAUGE_COMPS_MINIMAL: 150,
}
LINKS_EXPANDED_PER_SITE = 8


def _c(p: jnp.ndarray, s: int, a: int):
    """(re, im) planes of spinor component (spin s, color a)."""
    i = (s * 3 + a) * 2
    return p[i], p[i + 1]


def _u(u: jnp.ndarray, a: int, b: int):
    """(re, im) planes of gauge element (row a, col b)."""
    i = (a * 3 + b) * 2
    return u[i], u[i + 1]


def _sgn(s: int, v):
    return v if s > 0 else -v


def _proj(p: jnp.ndarray, mu: int, s: int):
    """Half-spinor projection of ``(1 + s*gamma_mu)``; returns h[2][3] pairs."""
    h = [[None] * 3 for _ in range(2)]
    for a in range(3):
        p0r, p0i = _c(p, 0, a)
        p1r, p1i = _c(p, 1, a)
        p2r, p2i = _c(p, 2, a)
        p3r, p3i = _c(p, 3, a)
        if mu == 0:    # x: h0 = p0 + s*i*p3, h1 = p1 + s*i*p2
            h[0][a] = (p0r - _sgn(s, p3i), p0i + _sgn(s, p3r))
            h[1][a] = (p1r - _sgn(s, p2i), p1i + _sgn(s, p2r))
        elif mu == 1:  # y: h0 = p0 - s*p3,  h1 = p1 + s*p2
            h[0][a] = (p0r - _sgn(s, p3r), p0i - _sgn(s, p3i))
            h[1][a] = (p1r + _sgn(s, p2r), p1i + _sgn(s, p2i))
        elif mu == 2:  # z: h0 = p0 + s*i*p2, h1 = p1 - s*i*p3
            h[0][a] = (p0r - _sgn(s, p2i), p0i + _sgn(s, p2r))
            h[1][a] = (p1r + _sgn(s, p3i), p1i - _sgn(s, p3r))
        else:          # t: h0 = p0 + s*p2,  h1 = p1 + s*p3
            h[0][a] = (p0r + _sgn(s, p2r), p0i + _sgn(s, p2i))
            h[1][a] = (p1r + _sgn(s, p3r), p1i + _sgn(s, p3i))
    return h


def _su3_mul(u: jnp.ndarray, h, dagger: bool):
    """uh[s][a] = sum_b U[a,b] h[s][b] (or U^dag for ``dagger``).

    The gauge planes ``(Y, Xh)`` broadcast against half-spinor planes that
    may carry a leading RHS-batch axis ``(nrhs, Y, Xh)`` — one gauge load
    serves the whole batch.
    """
    out = [[None] * 3 for _ in range(2)]
    for sp in range(2):
        for a in range(3):
            rr = ri = None
            for b in range(3):
                ur, ui = _u(u, b, a) if dagger else _u(u, a, b)
                hr, hi = h[sp][b]
                if dagger:  # conj(u): (ur - i ui)(hr + i hi)
                    tr = ur * hr + ui * hi
                    ti = ur * hi - ui * hr
                else:
                    tr = ur * hr - ui * hi
                    ti = ur * hi + ui * hr
                rr = tr if rr is None else rr + tr
                ri = ti if ri is None else ri + ti
            out[sp][a] = (rr, ri)
    return out


def _recon_acc(acc, uh, mu: int, s: int):
    """Reconstruct the 4-spinor of ``(1 + s*gamma_mu)`` and accumulate."""

    def add(sp, a, vr, vi):
        i = (sp * 3 + a) * 2
        acc[i] = vr if acc[i] is None else acc[i] + vr
        acc[i + 1] = vi if acc[i + 1] is None else acc[i + 1] + vi

    for a in range(3):
        h0r, h0i = uh[0][a]
        h1r, h1i = uh[1][a]
        add(0, a, h0r, h0i)
        add(1, a, h1r, h1i)
        if mu == 0:    # r2 = -s*i*h1, r3 = -s*i*h0
            add(2, a, _sgn(s, h1i), -_sgn(s, h1r))
            add(3, a, _sgn(s, h0i), -_sgn(s, h0r))
        elif mu == 1:  # r2 = s*h1, r3 = -s*h0
            add(2, a, _sgn(s, h1r), _sgn(s, h1i))
            add(3, a, -_sgn(s, h0r), -_sgn(s, h0i))
        elif mu == 2:  # r2 = -s*i*h0, r3 = s*i*h1
            add(2, a, _sgn(s, h0i), -_sgn(s, h0r))
            add(3, a, -_sgn(s, h1i), _sgn(s, h1r))
        else:          # r2 = s*h0, r3 = s*h1
            add(2, a, _sgn(s, h0r), _sgn(s, h0i))
            add(3, a, _sgn(s, h1r), _sgn(s, h1i))


def _hop_plane(p, pzp, pzm, ptp, ptm, u_out, ux, uy, uz, ut,
               tz_par, out_parity: int):
    """One hopping block on a single (Y, Xh) site plane; returns the 24
    accumulator planes.

    ``p`` is the center source plane ``(24, Y, Xh)`` — or, batched,
    ``(24, nrhs, Y, Xh)`` with the RHS axis right behind the component
    axis; ``pzp/pzm/ptp/ptm`` the z/t neighbor planes; ``u_out`` the
    output-parity gauge ``(4, gc, Y, Xh)``; ``ux/uy/uz/ut`` the
    source-parity gauge planes the backward hops read (``uz/ut`` already
    shifted to z-1 / t-1).  Gauge planes never carry the RHS axis: they
    broadcast, so they are loaded once per plane regardless of the batch.
    x/y neighbors are in-register rolls of the center plane (the paper's
    sel/tbl/ext sequence), so no operands are needed for them.

    ``gc`` may be 18 (full links), 12 (two_row) or 8 (minimal): the
    compressed planes are rolled/masked *first* (reconstruction is
    element-wise, so shifts commute with it and move fewer planes) and
    expanded to the 18 component planes in-register per hop direction —
    the HBM gauge stream shrinks 33%/55% for some extra VPU flops.
    """
    Y, Xh = p.shape[-2], p.shape[-1]

    # Row parity (t+z+y) % 2 — the predicate of the paper's `sel`.
    row = (jax.lax.broadcasted_iota(jnp.int32, (Y, Xh), 0) + tz_par) % 2
    mask_f = row == (out_parity + 1) % 2   # rows whose +x neighbor is at xh+1
    mask_b = row == out_parity % 2         # rows whose -x neighbor is at xh-1

    # In-register stencil shifts (sel/tbl/ext analogues).
    psi_xf = jnp.where(mask_f, pltpu_roll(p, -1, -1), p)
    psi_xb = jnp.where(mask_b, pltpu_roll(p, +1, -1), p)
    psi_yf = pltpu_roll(p, -1, -2)
    psi_yb = pltpu_roll(p, +1, -2)
    u_xb = jnp.where(mask_b, pltpu_roll(ux, +1, -1), ux)
    u_yb = pltpu_roll(uy, +1, -2)

    acc = [None] * SPINOR_COMPS
    hops = [(psi_xf, psi_xb, u_xb), (psi_yf, psi_yb, u_yb),
            (pzp, pzm, uz), (ptp, ptm, ut)]
    for mu, (pf, pb, ub) in enumerate(hops):
        # Forward: (1 - g_mu) U_mu(x) psi(x + mu).
        uh = _su3_mul(expand_links_planes(u_out[mu]), _proj(pf, mu, -1),
                      dagger=False)
        _recon_acc(acc, uh, mu, -1)
        # Backward: (1 + g_mu) U_mu^dag(x - mu) psi(x - mu).
        uh = _su3_mul(expand_links_planes(ub), _proj(pb, mu, +1),
                      dagger=True)
        _recon_acc(acc, uh, mu, +1)
    return acc


def _origin_parity(tz_offset) -> jnp.ndarray:
    """``(t0 + z0) % 2`` of the shard origin as the kernels' SMEM operand.

    Each kernel adds its grid ``(t, z)`` to this scalar to get the row
    parity of the plane it works on.  The operand is a ``(1,)`` array held
    whole in SMEM (:func:`_origin_parity_spec`): a per-step ``(1, 1)``
    block of a ``(T, Z)`` parity table is refused by the TPU lowering,
    whose blocks must be (8, 128)-aligned or span the array.
    ``tz_offset`` may be traced (the distributed shard origin).
    """
    t0, z0 = tz_offset
    return jnp.reshape(jnp.asarray((t0 + z0) % 2, jnp.int32), (1,))


def _origin_parity_spec() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` means: the Pallas interpreter off the TPU, the
    compiled kernel on it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _plane(ref, batched: bool):
    """Component-leading view of one pipelined spinor block.

    Unbatched block ``(1, 1, 24, Y, Xh)`` -> ``(24, Y, Xh)``; batched
    block ``(nrhs, 1, 1, 24, Y, Xh)`` -> ``(24, nrhs, Y, Xh)`` (component
    axis first so the unrolled plane math indexes it the same way).
    """
    return jnp.swapaxes(ref[:, 0, 0], 0, 1) if batched else ref[0, 0]


def _hop_kernel(*refs, out_parity: int, axpy_coeff: Optional[float],
                batched: bool):
    """Kernel body; operates on one (Y, Xh) plane of the lattice."""
    if axpy_coeff is not None:
        (par_ref, pc, pzp, pzm, ptp, ptm,
         uo, uix, uiy, uizm, uitm, psi0, out_ref) = refs
    else:
        (par_ref, pc, pzp, pzm, ptp, ptm,
         uo, uix, uiy, uizm, uitm, out_ref) = refs
        psi0 = None

    p = _plane(pc, batched)           # (24, [nrhs,] Y, Xh)
    compute_dtype = p.dtype
    tz_par = (pl.program_id(0) + pl.program_id(1) + par_ref[0]) % 2
    acc = _hop_plane(p, _plane(pzp, batched), _plane(pzm, batched),
                     _plane(ptp, batched), _plane(ptm, batched),
                     uo[:, 0, 0], uix[0, 0, 0], uiy[0, 0, 0],
                     uizm[0, 0, 0], uitm[0, 0, 0],
                     tz_par, out_parity)

    result = jnp.stack(acc).astype(compute_dtype)
    if axpy_coeff is not None:
        result = _plane(psi0, batched) + compute_dtype.type(axpy_coeff) * result
    if batched:
        out_ref[:, 0, 0] = jnp.swapaxes(result, 0, 1)
    else:
        out_ref[0, 0] = result


def pltpu_roll(x: jnp.ndarray, shift: int, axis: int) -> jnp.ndarray:
    """Static roll; lowers to lane/sublane rotates on TPU."""
    return jnp.roll(x, shift, axis=axis)


def hop_block_ext_planar_native(u_out_p: jnp.ndarray,
                                u_in_ext_p: jnp.ndarray,
                                src_ext_p: jnp.ndarray,
                                out_parity: int,
                                parity_offset=0) -> jnp.ndarray:
    """Planar-native jnp hopping block on halo-extended arrays.

    Identical math to the Pallas kernel (same _proj/_su3_mul/_recon_acc
    helpers, vectorized over (T, Z) instead of gridded), with NO
    complex<->planar layout conversions — the pure-XLA fast path used by
    the distributed jnp backend and the dry-run.  ``parity_offset`` may be
    traced ((t0+z0) % 2 of the shard origin).

    Accepts a batched source ``(nrhs, T+2, Z+2, 24, Y, Xh)`` (gauge never
    batched); the RHS axis rides right behind the component axis through
    the broadcasted SU(3) math — one gauge read per plane for the block.
    Compressed planar gauge fields (12/8 component planes) are expanded
    per hop direction, mirroring the in-register path of the kernel.
    """
    # Component axis to the front; an optional leading RHS axis lands
    # right behind it, so the trailing dims are (T, Z, Y, Xh) either way.
    src = jnp.moveaxis(src_ext_p, -3, 0)       # (24, [N,] T+2, Z+2, Y, Xh)
    u_in = jnp.moveaxis(u_in_ext_p, 3, 1)      # (4, gc, T+2, Z+2, Y, Xh)
    u_out = jnp.moveaxis(u_out_p, 3, 1)        # (4, gc, T, Z, Y, Xh)
    Tl, Zl = u_out_p.shape[1], u_out_p.shape[2]
    Y, Xh = src_ext_p.shape[-2], src_ext_p.shape[-1]

    c = src[..., 1:-1, 1:-1, :, :]             # (24, [N,] T, Z, Y, Xh)
    t = jnp.arange(Tl).reshape(Tl, 1, 1, 1)
    z = jnp.arange(Zl).reshape(1, Zl, 1, 1)
    y = jnp.arange(Y).reshape(1, 1, Y, 1)
    row = (t + z + y + parity_offset) % 2      # (T, Z, Y, 1)
    mask_f = row == (out_parity + 1) % 2
    mask_b = row == out_parity % 2

    psi_xf = jnp.where(mask_f, jnp.roll(c, -1, axis=-1), c)
    psi_xb = jnp.where(mask_b, jnp.roll(c, +1, axis=-1), c)
    psi_yf = jnp.roll(c, -1, axis=-2)
    psi_yb = jnp.roll(c, +1, axis=-2)
    psi_zf = src[..., 1:-1, 2:, :, :]
    psi_zb = src[..., 1:-1, :-2, :, :]
    psi_tf = src[..., 2:, 1:-1, :, :]
    psi_tb = src[..., :-2, 1:-1, :, :]

    ux = u_in[0, :, 1:-1, 1:-1]
    uy = u_in[1, :, 1:-1, 1:-1]
    uz = u_in[2, :, 1:-1, :-2]
    ut = u_in[3, :, :-2, 1:-1]
    u_xb = jnp.where(mask_b, jnp.roll(ux, +1, axis=-1), ux)
    u_yb = jnp.roll(uy, +1, axis=-2)

    acc = [None] * SPINOR_COMPS
    hops = [(psi_xf, psi_xb, u_xb), (psi_yf, psi_yb, u_yb),
            (psi_zf, psi_zb, uz), (psi_tf, psi_tb, ut)]
    for mu, (pf, pb, ub) in enumerate(hops):
        uh = _su3_mul(expand_links_planes(u_out[mu]), _proj(pf, mu, -1),
                      dagger=False)
        _recon_acc(acc, uh, mu, -1)
        uh = _su3_mul(expand_links_planes(ub), _proj(pb, mu, +1),
                      dagger=True)
        _recon_acc(acc, uh, mu, +1)
    out = jnp.stack(acc).astype(src_ext_p.dtype)
    return jnp.moveaxis(out, 0, -3)            # ([N,] T, Z, 24, Y, Xh)


def _build_specs(Tl: int, Zl: int, Y: int, Xh: int, halo: bool,
                 with_axpy: bool, rhs_block: Optional[int] = None,
                 gauge_comps: int = GAUGE_COMPS):
    """BlockSpecs for (origin parity, psi x5, U_out, U_in x4[, psi0]).

    With ``rhs_block`` the spinor blocks grow a leading RHS axis of that
    many columns, walked by a third, innermost grid axis ``r``.  The
    gauge index maps ignore ``r``, so consecutive steps of one (t, z)
    reuse the fetched gauge planes: each gauge plane is loaded once per
    (t, z) whatever the batch size.  ``gauge_comps`` sizes the gauge
    component-plane axis (18 full / 12 two_row / 8 minimal).
    """
    if rhs_block is None:
        sblk = (1, 1, SPINOR_COMPS, Y, Xh)
    else:
        sblk = (rhs_block, 1, 1, SPINOR_COMPS, Y, Xh)
    gblk1 = (1, 1, 1, gauge_comps, Y, Xh)

    def s(im):
        if rhs_block is None:
            return pl.BlockSpec(sblk, im)
        return pl.BlockSpec(sblk,
                            lambda t, z, r, _im=im: (r, *_im(t, z)))

    def g(im):
        return pl.BlockSpec(gblk1, lambda t, z, *_r, _im=im: _im(t, z))

    if halo:
        # Arrays are halo-extended to (T+2, Z+2) in t/z; +1 recenters.
        psi = [
            s(lambda t, z: (t + 1, z + 1, 0, 0, 0)),
            s(lambda t, z: (t + 1, z + 2, 0, 0, 0)),   # z+1
            s(lambda t, z: (t + 1, z, 0, 0, 0)),       # z-1
            s(lambda t, z: (t + 2, z + 1, 0, 0, 0)),   # t+1
            s(lambda t, z: (t, z + 1, 0, 0, 0)),       # t-1
        ]
        u_in = [
            g(lambda t, z: (0, t + 1, z + 1, 0, 0, 0)),  # x, center
            g(lambda t, z: (1, t + 1, z + 1, 0, 0, 0)),  # y, center
            g(lambda t, z: (2, t + 1, z, 0, 0, 0)),      # z, z-1
            g(lambda t, z: (3, t, z + 1, 0, 0, 0)),      # t, t-1
        ]
    else:
        psi = [
            s(lambda t, z: (t, z, 0, 0, 0)),
            s(lambda t, z: (t, (z + 1) % Zl, 0, 0, 0)),
            s(lambda t, z: (t, (z - 1) % Zl, 0, 0, 0)),
            s(lambda t, z: ((t + 1) % Tl, z, 0, 0, 0)),
            s(lambda t, z: ((t - 1) % Tl, z, 0, 0, 0)),
        ]
        u_in = [
            g(lambda t, z: (0, t, z, 0, 0, 0)),
            g(lambda t, z: (1, t, z, 0, 0, 0)),
            g(lambda t, z: (2, t, (z - 1) % Zl, 0, 0, 0)),
            g(lambda t, z: (3, (t - 1) % Tl, z, 0, 0, 0)),
        ]

    u_out = pl.BlockSpec((4, 1, 1, gauge_comps, Y, Xh),
                         lambda t, z, *_r: (0, t, z, 0, 0, 0))
    specs = [_origin_parity_spec()] + psi + [u_out] + u_in
    if with_axpy:
        specs.append(s(lambda t, z: (t, z, 0, 0, 0)))
    out = s(lambda t, z: (t, z, 0, 0, 0))
    return specs, out


# ---------------------------------------------------------------------------
# VMEM accounting: the bytes the TPU compiler allocates per kernel.
# ---------------------------------------------------------------------------

# The scoped-VMEM limit of one kernel on v5e (what the compiler's refusal
# quotes as "limit 16.00M").  Every kernel and policy below is held to it.
_VMEM_LIMIT_BYTES = 16 << 20

# Mosaic lays the minor (Y, Xh) dims of every VMEM buffer out in
# (sublane, lane) tiles: Xh rounds up to 128 lanes and Y to 8 sublanes of
# 32-bit words (16 rows for bf16).  A (16, 8) f32 plane thus takes
# 16 x 128 x 4 B = 8 KiB, not 512 B.
_LANES = 128

# VMEM the unrolled SU(3) x half-spinor body spills, in 32-bit planes per
# RHS column (the VPU computes bf16 in 32-bit registers).  Compiles for
# v5e at 16^4 and 32^3x64 planes, nrhs 1-4, needed up to ~130 such planes
# per column in f32 and ~170 in bf16 beyond the pipeline buffers; 192
# keeps a margin, so a kernel the model admits is one the compiler takes.
_BODY_SPILL_PLANES = 192

# Gauge plane-sets (of ``gauge_comps`` planes each) that one grid step
# keeps in flight, per kernel: the hop block's u_out (4 directions) and
# four u_in views; the resident fused kernel's u_out + z/t views for each
# parity; the streaming kernel's two u_out/u_x,y quartets and four
# shifted views.
GAUGE_PLANE_SETS = {"hop": 8, "hop_axpy": 8, "resident": 12, "stream": 20}

# Pipelined spinor blocks per grid step (inputs + output), per kernel.
_SPINOR_BLOCKS = {"hop": 6, "hop_axpy": 7, "resident": 6, "stream": 7}


def _itemsize(dtype) -> int:
    return dtype if isinstance(dtype, int) else jnp.dtype(dtype).itemsize


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def plane_vmem_bytes(Y: int, Xh: int, dtype=jnp.float32) -> int:
    """VMEM bytes of one ``(Y, Xh)`` plane as the TPU compiler tiles it.

    ``dtype`` may also be an int itemsize.
    """
    itemsize = _itemsize(dtype)
    sublanes = 8 * max(1, 4 // itemsize)
    return itemsize * _round_up(Y, sublanes) * _round_up(Xh, _LANES)


def _pipeline_vmem_bytes(kernel: str, nrhs: int, Y: int, Xh: int, dtype,
                         gauge_comps: int) -> int:
    """Double-buffered pipeline blocks plus body spills of one kernel."""
    planes = (_SPINOR_BLOCKS[kernel] * nrhs * SPINOR_COMPS
              + GAUGE_PLANE_SETS[kernel] * gauge_comps)
    spill = plane_vmem_bytes(Y, Xh, max(_itemsize(dtype), 4))
    return (2 * planes * plane_vmem_bytes(Y, Xh, dtype)
            + _BODY_SPILL_PLANES * nrhs * spill)


def hop_vmem_bytes(rhs_block: int, Y: int, Xh: int, dtype=jnp.float32, *,
                   gauge_comps: int = GAUGE_COMPS,
                   with_axpy: bool = False) -> int:
    """VMEM of one hopping-block kernel over ``rhs_block`` RHS columns."""
    return _pipeline_vmem_bytes("hop_axpy" if with_axpy else "hop",
                                rhs_block, Y, Xh, dtype, gauge_comps)


def hop_rhs_block(nrhs: int, Y: int, Xh: int, dtype=jnp.float32, *,
                  gauge_comps: int = GAUGE_COMPS,
                  with_axpy: bool = False) -> int:
    """RHS columns per hopping-block grid step: the largest divisor of
    ``nrhs`` whose :func:`hop_vmem_bytes` fits the VMEM limit (1 if none
    does — the kernel then refuses the shape)."""
    for b in range(nrhs, 0, -1):
        if nrhs % b == 0 and hop_vmem_bytes(
                b, Y, Xh, dtype, gauge_comps=gauge_comps,
                with_axpy=with_axpy) <= _VMEM_LIMIT_BYTES:
            return b
    return 1


def hop_traffic_model(Tl: int, Zl: int, Y: int, Xh: int, *,
                      nrhs: int = 1, itemsize: int = 4,
                      with_axpy: bool = False,
                      gauge_comps: int = GAUGE_COMPS) -> dict:
    """HBM-traffic / flops model of one (batched) hopping-block call.

    The gauge term is *independent of nrhs* — each (t, z) grid step loads
    its gauge planes once and reuses them across the whole RHS block —
    while spinor traffic and flops scale linearly, so the arithmetic
    intensity approaches ``HOP_FLOPS_PER_SITE / (4 * spinor bytes)`` as
    nrhs grows.  This is the model :mod:`benchmarks.bench_multirhs`
    prints next to measured numbers, and what the kernel's
    ``pl.CostEstimate`` is built from.

    ``gauge_comps`` scales the gauge stream for compressed links (12/8
    planes instead of 18) and adds the in-register reconstruction flops
    (:data:`RECON_FLOPS_PER_LINK` x 8 expanded links per site) — the
    bytes/flops trade a memory-bound stencil wants to make.
    """
    sites = Tl * Zl * Y * Xh
    bytes_spinor = itemsize * SPINOR_COMPS * sites * nrhs   # read + written
    bytes_gauge = 2 * itemsize * 4 * gauge_comps * sites    # both parities
    total = 2 * bytes_spinor + bytes_gauge + (bytes_spinor if with_axpy else 0)
    recon = (RECON_FLOPS_PER_LINK[gauge_comps]
             * LINKS_EXPANDED_PER_SITE * sites)
    flops = HOP_FLOPS_PER_SITE * sites * nrhs + recon
    return {
        "flops": flops,
        "flops_recon": recon,
        "bytes_spinor": bytes_spinor,
        "bytes_gauge": bytes_gauge,
        "bytes_total": total,
        "intensity_flops_per_byte": flops / total,
    }


def hop_block_planar(u_out_p: jnp.ndarray, u_in_p: jnp.ndarray,
                     src_p: jnp.ndarray, out_parity: int, *,
                     tz_offset: Tuple[int, int] = (0, 0),
                     halo: bool = False,
                     axpy: Optional[Tuple[float, jnp.ndarray]] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Apply one hopping block in the planar layout via the Pallas kernel.

    Args:
      u_out_p: planar gauge at output-parity sites ``(4, T, Z, gc, Y, Xh)``
        with gc in {18, 12, 8} — compressed links are expanded
        in-register (never halo-extended, never batched).
      u_in_p: planar gauge at source-parity sites; halo-extended to
        ``(4, T+2, Z+2, ...)`` iff ``halo``.
      src_p: planar source spinor ``(T, Z, 24, Y, Xh)`` — or batched
        ``(nrhs, T, Z, 24, Y, Xh)`` — halo-extended in (T, Z) iff ``halo``.
        Batched sources run ONE kernel over the same (T, Z) grid with the
        gauge planes loaded once per step for the whole block.
      out_parity: parity of the *output* (ODD for ``H_oe``).
      tz_offset: global (t0, z0) origin of this shard, for the parity mask.
      halo: neighbor planes come from halo-extended arrays instead of
        periodic wrap (the distributed path).
      axpy: optional ``(coeff, psi0_p)`` fusing ``psi0 + coeff * hop``
        (``psi0_p`` batched iff ``src_p`` is).
      interpret: force/disable interpret mode (default: auto off-TPU).

    A batched source runs in RHS blocks of :func:`hop_rhs_block` columns
    (the largest divisor of nrhs whose blocks fit VMEM), each gauge plane
    still fetched once per (t, z).
    """
    interpret = resolve_interpret(interpret)
    batched = src_p.ndim == 6
    nrhs = src_p.shape[0] if batched else None
    lead = 1 if batched else 0
    Tl, Zl = src_p.shape[lead], src_p.shape[lead + 1]
    if halo:
        Tl, Zl = Tl - 2, Zl - 2
    Y, Xh = src_p.shape[-2], src_p.shape[-1]

    with_axpy = axpy is not None
    gauge_comps = u_out_p.shape[3]
    rhs_block = None
    grid = (Tl, Zl)
    if batched:
        rhs_block = hop_rhs_block(nrhs, Y, Xh, src_p.dtype,
                                  gauge_comps=gauge_comps,
                                  with_axpy=with_axpy)
        grid = (Tl, Zl, nrhs // rhs_block)
    if not interpret and hop_vmem_bytes(
            rhs_block or 1, Y, Xh, src_p.dtype, gauge_comps=gauge_comps,
            with_axpy=with_axpy) > _VMEM_LIMIT_BYTES:
        raise ValueError(
            f"hopping block on ({Y}, {Xh}) planes needs more than the "
            f"{_VMEM_LIMIT_BYTES} B of VMEM a kernel may use, even one "
            "right-hand side at a time")
    in_specs, out_spec = _build_specs(Tl, Zl, Y, Xh, halo, with_axpy,
                                      rhs_block=rhs_block,
                                      gauge_comps=gauge_comps)
    coeff = float(axpy[0]) if with_axpy else None

    model = hop_traffic_model(Tl, Zl, Y, Xh, nrhs=nrhs or 1,
                              itemsize=src_p.dtype.itemsize,
                              with_axpy=with_axpy,
                              gauge_comps=gauge_comps)
    cost = pl.CostEstimate(flops=model["flops"],
                           bytes_accessed=model["bytes_total"],
                           transcendentals=0)

    kernel = functools.partial(_hop_kernel, out_parity=out_parity,
                               axpy_coeff=coeff, batched=batched)
    operands = [_origin_parity(tz_offset), src_p, src_p, src_p, src_p, src_p,
                u_out_p, u_in_p, u_in_p, u_in_p, u_in_p]
    if with_axpy:
        operands.append(axpy[1])

    out_shape = ((nrhs,) if batched else ()) + (Tl, Zl, SPINOR_COMPS, Y, Xh)
    fn = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, src_p.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        interpret=interpret,
        cost_estimate=cost,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",) * len(grid)),
        name=f"wilson_hop_{'oe' if out_parity else 'eo'}",
    )
    return fn(*operands)


# ---------------------------------------------------------------------------
# Fused even-odd preconditioned operator: Dhat in ONE pallas_call.
# ---------------------------------------------------------------------------

def _dhat_kernel(par_ref, pc, pzp, pzm, ptp, ptm,
                 ue_all, ue_zm, ue_tm, uo_all, uo_zm, uo_tm,
                 out_ref, tmp_ref, *, kappa2: float, Tl: int, Zl: int,
                 batched: bool):
    """Fused ``Dhat = 1 - kappa^2 H_eo H_oe`` over grid ``(2, T, Z)``.

    Pass 0 (``s == 0``) computes the odd-parity intermediate
    ``tmp = H_oe psi_e`` plane by plane into a full-lattice VMEM scratch;
    pass 1 re-walks the grid applying ``H_eo`` to the scratch (z/t
    neighbor planes are VMEM reads with periodic wrap) and writes the
    fused ``psi0 - kappa^2 * (...)`` epilogue.  The intermediate spinor
    never exists in HBM — the round-trip the two-call
    ``apply_dhat_planar`` pays is gone (QWS applies the same fusion on
    A64FX; cf. Kanamori & Matsufuru on keeping intermediates
    SIMD-resident).

    Batched blocks keep the scratch component-leading
    ``(T, Z, 24, nrhs, Y, Xh)`` so both passes read planes in the layout
    the unrolled math wants; the scratch grows nrhs x (see
    :func:`fused_dhat_fits`).
    """
    s = pl.program_id(0)
    t = pl.program_id(1)
    z = pl.program_id(2)
    tz_par = (t + z + par_ref[0]) % 2
    p = _plane(pc, batched)           # psi_e center plane (24, [N,] Y, Xh)
    compute_dtype = p.dtype

    @pl.when(s == 0)
    def _pass_hoe():
        acc = _hop_plane(p, _plane(pzp, batched), _plane(pzm, batched),
                         _plane(ptp, batched), _plane(ptm, batched),
                         uo_all[:, 0, 0],
                         ue_all[0, 0, 0], ue_all[1, 0, 0],
                         ue_zm[0, 0, 0], ue_tm[0, 0, 0],
                         tz_par, 1)
        tmp_ref[t, z] = jnp.stack(acc).astype(compute_dtype)

    @pl.when(s == 1)
    def _pass_heo_axpy():
        tc = tmp_ref[t, z]
        tzp = tmp_ref[t, (z + 1) % Zl]
        tzm = tmp_ref[t, (z - 1) % Zl]
        ttp = tmp_ref[(t + 1) % Tl, z]
        ttm = tmp_ref[(t - 1) % Tl, z]
        acc = _hop_plane(tc, tzp, tzm, ttp, ttm,
                         ue_all[:, 0, 0],
                         uo_all[0, 0, 0], uo_all[1, 0, 0],
                         uo_zm[0, 0, 0], uo_tm[0, 0, 0],
                         tz_par, 0)
        hop2 = jnp.stack(acc).astype(compute_dtype)
        result = p - compute_dtype.type(kappa2) * hop2
        if batched:
            out_ref[:, 0, 0] = jnp.swapaxes(result, 0, 1)
        else:
            out_ref[0, 0] = result


def dhat_planar_fused(u_e_p: jnp.ndarray, u_o_p: jnp.ndarray,
                      psi_e_p: jnp.ndarray, kappa: float, *,
                      tz_offset: Tuple[int, int] = (0, 0),
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """``(1 - kappa^2 H_eo H_oe) psi_e`` as a single Pallas kernel.

    Both hopping blocks and the axpy epilogue run inside one
    ``pallas_call``; the odd intermediate lives in a full-lattice VMEM
    scratch for the whole invocation, so versus the two-call
    ``apply_dhat_planar`` path one spinor HBM write + pipelined re-read
    (5 planes per grid step) is eliminated.  Periodic single-shard only
    (the distributed path keeps the two-call structure so halos can
    overlap).  Batched sources ``(nrhs, T, Z, 24, Y, Xh)`` are supported;
    the scratch then holds the whole batched intermediate.

    The scratch is the (batched) odd-parity spinor: ``nrhs * 24 *
    T*Z*Y*Xh`` elements.  On a real TPU that caps the local volume (~12
    MiB budget); callers should fall back to the unfused path above that
    — :func:`fused_dhat_fits` (itemsize derived from the actual dtype)
    tells you.
    """
    interpret = resolve_interpret(interpret)
    batched = psi_e_p.ndim == 6
    nrhs = psi_e_p.shape[0] if batched else None
    lead = 1 if batched else 0
    Tl, Zl = psi_e_p.shape[lead], psi_e_p.shape[lead + 1]
    Y, Xh = psi_e_p.shape[-2], psi_e_p.shape[-1]

    gauge_comps = u_e_p.shape[3]
    if not interpret and not fused_dhat_fits(psi_e_p.shape, psi_e_p.dtype,
                                             gauge_comps=gauge_comps):
        budget = fused_scratch_budget("resident", psi_e_p.shape,
                                      psi_e_p.dtype, gauge_comps=gauge_comps)
        raise ValueError(
            f"fused Dhat intermediate needs "
            f"{fused_scratch_bytes(psi_e_p.shape, psi_e_p.dtype)} B of "
            f"VMEM scratch (> {budget} budget at gauge_comps="
            f"{gauge_comps}); use the unfused apply_dhat_planar path for "
            "this local volume / nrhs")

    if batched:
        sblk = (nrhs, 1, 1, SPINOR_COMPS, Y, Xh)
    else:
        sblk = (1, 1, SPINOR_COMPS, Y, Xh)
    gblk1 = (1, 1, 1, gauge_comps, Y, Xh)

    def s(im):
        if not batched:
            return pl.BlockSpec(sblk, im)
        return pl.BlockSpec(sblk, lambda s_, t, z, _im=im: (0, *_im(s_, t, z)))

    def g(im):
        return pl.BlockSpec(gblk1, im)

    # Operands read by only one pass collapse to block (0, 0) in the
    # other pass (multiply the index by ``1 - s`` or ``s``): the block
    # index then stays constant across the dead pass's grid steps, so the
    # pipeliner fetches it once instead of streaming a full dead volume
    # from HBM — without this, pass 1 would re-fetch all four psi
    # neighbor planes it never reads and the fusion's HBM saving mostly
    # evaporates.
    psi_specs = [
        s(lambda _, t, z: (t, z, 0, 0, 0)),   # center: psi0 in pass 1
        s(lambda s_, t, z: (t * (1 - s_), ((z + 1) % Zl) * (1 - s_),
                            0, 0, 0)),
        s(lambda s_, t, z: (t * (1 - s_), ((z - 1) % Zl) * (1 - s_),
                            0, 0, 0)),
        s(lambda s_, t, z: (((t + 1) % Tl) * (1 - s_), z * (1 - s_),
                            0, 0, 0)),
        s(lambda s_, t, z: (((t - 1) % Tl) * (1 - s_), z * (1 - s_),
                            0, 0, 0)),
    ]

    def gauge_specs(live):
        # ``live(s)`` is 1 in the pass that reads the shifted planes.
        return [
            pl.BlockSpec((4, 1, 1, gauge_comps, Y, Xh),
                         lambda _, t, z: (0, t, z, 0, 0, 0)),
            g(lambda s_, t, z: (2, t * live(s_),
                                ((z - 1) % Zl) * live(s_), 0, 0, 0)),
            g(lambda s_, t, z: (3, ((t - 1) % Tl) * live(s_),
                                z * live(s_), 0, 0, 0)),
        ]

    in_specs = ([_origin_parity_spec()] + psi_specs
                + gauge_specs(lambda s_: 1 - s_)    # u_e shifts: pass 0
                + gauge_specs(lambda s_: s_))       # u_o shifts: pass 1
    out_spec = s(lambda _, t, z: (t, z, 0, 0, 0))

    # Two hopping blocks + axpy epilogue, but only one spinor read and
    # one write touch HBM (the intermediate is scratch-resident).
    n = nrhs or 1
    m = hop_traffic_model(Tl, Zl, Y, Xh, nrhs=n,
                          itemsize=psi_e_p.dtype.itemsize,
                          gauge_comps=gauge_comps)
    cost = pl.CostEstimate(
        flops=2 * m["flops"] + 2 * SPINOR_COMPS * Tl * Zl * Y * Xh * n,
        bytes_accessed=2 * m["bytes_spinor"] + 2 * m["bytes_gauge"],
        transcendentals=0)

    scratch_shape = ((Tl, Zl, SPINOR_COMPS)
                     + ((nrhs,) if batched else ()) + (Y, Xh))
    kernel = functools.partial(_dhat_kernel, kappa2=float(kappa) ** 2,
                               Tl=Tl, Zl=Zl, batched=batched)
    out_shape = ((nrhs,) if batched else ()) + (Tl, Zl, SPINOR_COMPS, Y, Xh)
    fn = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, psi_e_p.dtype),
        grid=(2, Tl, Zl),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM(scratch_shape, psi_e_p.dtype)],
        interpret=interpret,
        cost_estimate=cost,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="wilson_dhat_fused",
    )
    return fn(_origin_parity(tz_offset),
              psi_e_p, psi_e_p, psi_e_p, psi_e_p, psi_e_p,
              u_e_p, u_e_p, u_e_p, u_o_p, u_o_p, u_o_p)


def gauge_headroom_bytes(Y: int, Xh: int, itemsize: int,
                         gauge_comps: int = GAUGE_COMPS,
                         plane_sets: int = GAUGE_PLANE_SETS["resident"]
                         ) -> int:
    """VMEM freed by compressed gauge blocks in a fused kernel's pipeline.

    A fused kernel keeps ``plane_sets`` gauge plane-sets in flight per
    grid step (:data:`GAUGE_PLANE_SETS`), double-buffered by the
    pipeline.  Compression shrinks each from 18 to ``gauge_comps``
    tiled ``(Y, Xh)`` planes, and the scratch budget absorbs the
    difference.  Zero at ``gauge_comps == 18``.
    """
    return ((GAUGE_COMPS - gauge_comps) * plane_sets * 2
            * plane_vmem_bytes(Y, Xh, itemsize))


def fused_scratch_budget(kernel: str, psi_e_p_shape, dtype=jnp.float32, *,
                         gauge_comps: int = GAUGE_COMPS) -> int:
    """VMEM left for the scratch of fused ``kernel`` ("resident" or
    "stream"): the per-kernel limit less its pipeline blocks and body
    spills at full 18-plane links, plus the :func:`gauge_headroom_bytes`
    that compressed links free.  Negative when not even the pipeline
    fits."""
    nrhs = psi_e_p_shape[0] if len(psi_e_p_shape) == 6 else 1
    Y, Xh = psi_e_p_shape[-2], psi_e_p_shape[-1]
    return (_VMEM_LIMIT_BYTES
            - _pipeline_vmem_bytes(kernel, nrhs, Y, Xh, dtype, GAUGE_COMPS)
            + gauge_headroom_bytes(Y, Xh, _itemsize(dtype), gauge_comps,
                                   GAUGE_PLANE_SETS[kernel]))


def fused_scratch_bytes(psi_e_p_shape, dtype=jnp.float32) -> int:
    """VMEM bytes of the resident kernel's scratch: the whole (batched)
    odd intermediate, ``nrhs * T * Z * 24`` tiled ``(Y, Xh)`` planes."""
    return (math.prod(psi_e_p_shape[:-2])
            * plane_vmem_bytes(psi_e_p_shape[-2], psi_e_p_shape[-1], dtype))


def fused_dhat_fits(psi_e_p_shape, dtype=jnp.float32, *,
                    gauge_comps: int = GAUGE_COMPS) -> bool:
    """Whether the resident fused kernel's VMEM intermediate fits.

    ``psi_e_p_shape`` is the (possibly batched) planar spinor shape —
    ``(T, Z, 24, Y, Xh)`` or ``(nrhs, T, Z, 24, Y, Xh)``.  ``dtype``
    (or an int itemsize) sizes the tiles: f64 halves the admissible
    volume versus f32.  Compressed links (``gauge_comps`` < 18) free
    pipeline VMEM (:func:`gauge_headroom_bytes`), nudging the threshold
    up.
    """
    return fused_scratch_bytes(psi_e_p_shape, dtype) <= fused_scratch_budget(
        "resident", psi_e_p_shape, dtype, gauge_comps=gauge_comps)


# ---------------------------------------------------------------------------
# Streaming (plane-window) fused Dhat: the VMEM cap lifted.
# ---------------------------------------------------------------------------

# Ring rows of odd-intermediate t-planes held in VMEM by the streaming
# kernel: 3 live rows cover the +-t stencil reach of the second hopping
# block, +1 is the row being produced while the previous three are
# consumed (the double buffer).  This is the sliding working set of the
# KNL/AVX-512 predecessors (Kanamori & Matsufuru 1712.01505, 1811.00893)
# mapped onto the TPU pipeline.
STREAM_WINDOW_ROWS = 4


def stream_ring_bytes(psi_e_p_shape, dtype=jnp.float32,
                      window: int = STREAM_WINDOW_ROWS) -> int:
    """VMEM bytes of the streaming kernel's t-plane ring.

    The ring holds ``window`` t-rows of the (batched) odd intermediate —
    ``window * Z * 24 * nrhs`` tiled ``(Y, Xh)`` planes — so its size is
    derived from the actual ``dtype`` and the RHS batch but is
    *independent of T*: that is the cap-lift.  ``psi_e_p_shape`` as in
    :func:`fused_dhat_fits`.
    """
    lead = 1 if len(psi_e_p_shape) == 6 else 0
    return (window * fused_scratch_bytes(psi_e_p_shape, dtype)
            // psi_e_p_shape[lead])


def fused_dhat_stream_fits(psi_e_p_shape, dtype=jnp.float32, *,
                           gauge_comps: int = GAUGE_COMPS) -> bool:
    """Whether the streaming kernel's t-plane ring fits the VMEM budget."""
    return stream_ring_bytes(psi_e_p_shape, dtype) <= fused_scratch_budget(
        "stream", psi_e_p_shape, dtype, gauge_comps=gauge_comps)


def fused_dhat_policy(psi_e_p_shape, dtype=jnp.float32, *,
                      gauge_comps: int = GAUGE_COMPS) -> str:
    """Three-way fused-Dhat path selection for a planar spinor shape.

    ``"resident"`` — the whole (batched) odd intermediate fits the VMEM
    scratch budget: use :func:`dhat_planar_fused` (fewest HBM bytes).
    ``"stream"`` — it doesn't, but the :data:`STREAM_WINDOW_ROWS`-row
    plane window does: use :func:`dhat_planar_fused_stream` (same fusion,
    T-independent scratch, 2 recomputed boundary rows).
    ``"unfused"`` — even one window row ring is too large (enormous
    z-planes): fall back to the two-kernel ``apply_dhat_planar`` path,
    which needs no scratch at all.

    ``gauge_comps`` < 18 moves both thresholds up by the pipeline VMEM
    the compressed gauge blocks free (:func:`gauge_headroom_bytes`).

    Every count is of tiled VMEM (:func:`plane_vmem_bytes`) against the
    per-kernel limit, so the policy never picks a kernel the TPU
    compiler refuses.  With ``(Y, Xh)`` planes padded to whole tiles,
    no f32 Table-1 volume (16^4, 16^3x64, 32^3x64) fits either fused
    kernel; they run unfused.
    """
    if fused_dhat_fits(psi_e_p_shape, dtype, gauge_comps=gauge_comps):
        return "resident"
    if fused_dhat_stream_fits(psi_e_p_shape, dtype,
                              gauge_comps=gauge_comps):
        return "stream"
    return "unfused"


def dhat_stream_traffic_model(Tl: int, Zl: int, Y: int, Xh: int, *,
                              nrhs: int = 1, itemsize: int = 4,
                              window: int = STREAM_WINDOW_ROWS,
                              gauge_comps: int = GAUGE_COMPS) -> dict:
    """HBM-traffic / flops / scratch model of one streaming fused Dhat.

    Versus the resident fused kernel the streaming variant recomputes 2
    boundary t-rows of ``H_oe`` (rows T-1 and 0 are produced twice so the
    periodic wrap reads fresh ring slots) and re-fetches their operand
    planes — a ``(T+2)/T`` factor on the first hopping block — while its
    VMEM scratch shrinks from the full lattice to the ``window``-row
    ring.  The :mod:`benchmarks` print these numbers next to measured
    times, and the kernel's ``pl.CostEstimate`` is built from them.
    """
    m = hop_traffic_model(Tl, Zl, Y, Xh, nrhs=nrhs, itemsize=itemsize,
                          gauge_comps=gauge_comps)
    sites = Tl * Zl * Y * Xh
    produce_scale = (Tl + 2) / Tl
    flops = (int(m["flops"] * produce_scale)      # H_oe incl. recompute
             + m["flops"]                          # H_eo
             + 2 * SPINOR_COMPS * sites * nrhs)    # axpy epilogue
    spinor1 = itemsize * SPINOR_COMPS * sites * nrhs
    bytes_spinor = int(spinor1 * (produce_scale + 2))  # psi in, psi0, out
    bytes_gauge = int(m["bytes_gauge"] * (produce_scale + 1))
    shape = ((nrhs,) if nrhs > 1 else ()) + (Tl, Zl, SPINOR_COMPS, Y, Xh)
    return {
        "flops": flops,
        "bytes_spinor": bytes_spinor,
        "bytes_gauge": bytes_gauge,
        "bytes_total": bytes_spinor + bytes_gauge,
        "intensity_flops_per_byte": flops / (bytes_spinor + bytes_gauge),
        "recompute_rows": 2,
        "window_rows": window,
        "vmem_ring_bytes": stream_ring_bytes(shape, itemsize,
                                             window=window),
        "vmem_resident_bytes": fused_scratch_bytes(shape, itemsize),
    }


def _dhat_stream_kernel(par_ref, pc, pzp, pzm, ptp, ptm, psi0,
                        uo_src, ue_src, ue_zm, ue_tm,
                        ue_out, uo_out, uo_zm, uo_tm,
                        out_ref, ring_ref, *, kappa2: float, Tl: int,
                        Zl: int, window: int, batched: bool):
    """Streaming fused ``Dhat`` over grid ``(T + 3, Z)``.

    Step ``(s, z)`` runs two interleaved stages against a ``window``-row
    ring of odd-intermediate t-planes:

    * **produce** (``s <= T+1``): ``ring[s % window][z] = H_oe psi_e``
      for source row ``ts = (s-1) % T`` — the walk starts at row ``T-1``
      and ends by re-producing row ``0``, so both wrap neighbors of the
      consume stage read freshly-computed slots and the periodic
      boundary stays exact (2 recomputed rows total);
    * **consume** (``s >= 3``): output row ``to = (s-3) % T`` applies
      ``H_eo`` to ring rows ``to-1 / to / to+1`` (slots ``(s-3..s-1) %
      window`` — all complete, and all distinct from the slot being
      produced this step) and writes the fused ``psi0 - kappa^2 (...)``
      epilogue.

    The lag of 3 grid rows between produce and consume guarantees row
    ``to+1`` is complete across the whole z extent before any of its
    planes are read, so the ring never needs intra-step ordering.
    """
    s = pl.program_id(0)
    z = pl.program_id(1)
    compute_dtype = out_ref.dtype
    par_src = ((s - 1) % Tl + z + par_ref[0]) % 2    # row ts
    par_out = ((s - 3) % Tl + z + par_ref[0]) % 2    # row to

    @pl.when(s <= Tl + 1)
    def _produce():
        p = _plane(pc, batched)
        acc = _hop_plane(p, _plane(pzp, batched), _plane(pzm, batched),
                         _plane(ptp, batched), _plane(ptm, batched),
                         uo_src[:, 0, 0],
                         ue_src[0, 0, 0], ue_src[1, 0, 0],
                         ue_zm[0, 0, 0], ue_tm[0, 0, 0],
                         par_src, 1)
        ring_ref[s % window, z] = jnp.stack(acc).astype(compute_dtype)

    @pl.when(s >= 3)
    def _consume():
        tc = ring_ref[(s - 2) % window, z]
        tzp = ring_ref[(s - 2) % window, (z + 1) % Zl]
        tzm = ring_ref[(s - 2) % window, (z - 1) % Zl]
        ttp = ring_ref[(s - 1) % window, z]
        ttm = ring_ref[(s - 3) % window, z]
        acc = _hop_plane(tc, tzp, tzm, ttp, ttm,
                         ue_out[:, 0, 0],
                         uo_out[0, 0, 0], uo_out[1, 0, 0],
                         uo_zm[0, 0, 0], uo_tm[0, 0, 0],
                         par_out, 0)
        hop2 = jnp.stack(acc).astype(compute_dtype)
        result = _plane(psi0, batched) - compute_dtype.type(kappa2) * hop2
        if batched:
            out_ref[:, 0, 0] = jnp.swapaxes(result, 0, 1)
        else:
            out_ref[0, 0] = result


def dhat_planar_fused_stream(u_e_p: jnp.ndarray, u_o_p: jnp.ndarray,
                             psi_e_p: jnp.ndarray, kappa: float, *,
                             tz_offset: Tuple[int, int] = (0, 0),
                             window: int = STREAM_WINDOW_ROWS,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """``(1 - kappa^2 H_eo H_oe) psi_e`` as ONE kernel with a plane-window
    VMEM scratch — the cap-lifting variant of :func:`dhat_planar_fused`.

    Instead of the full-lattice odd intermediate, only a ``window``-row
    ring of t-planes lives in VMEM (``window * Z * 24 * nrhs * Y * Xh``
    elements — independent of T), double-buffered: each grid step
    produces ``H_oe`` of one t-row into the slot the consume stage is not
    reading while the fused ``H_eo`` + axpy epilogue consumes rows three
    steps behind.  The periodic t-wrap stays exact by producing the two
    boundary rows twice (rows ``T-1`` and ``0`` — see
    :func:`dhat_stream_traffic_model` for the accounted overhead).
    Periodic single-shard only, like the resident variant; batched
    sources ``(nrhs, T, Z, 24, Y, Xh)`` run one kernel with each gauge
    plane fetched once per grid step for the whole block.
    """
    interpret = resolve_interpret(interpret)
    if window < STREAM_WINDOW_ROWS:
        raise ValueError(
            f"stream window needs >= {STREAM_WINDOW_ROWS} rows (3 live "
            f"for the +-t stencil reach + 1 produce slot); got {window}")
    batched = psi_e_p.ndim == 6
    nrhs = psi_e_p.shape[0] if batched else None
    lead = 1 if batched else 0
    Tl, Zl = psi_e_p.shape[lead], psi_e_p.shape[lead + 1]
    Y, Xh = psi_e_p.shape[-2], psi_e_p.shape[-1]

    gauge_comps = u_e_p.shape[3]
    ring_bytes = stream_ring_bytes(psi_e_p.shape, psi_e_p.dtype,
                                   window=window)
    ring_limit = fused_scratch_budget("stream", psi_e_p.shape,
                                      psi_e_p.dtype, gauge_comps=gauge_comps)
    if not interpret and ring_bytes > ring_limit:
        raise ValueError(
            f"streaming Dhat ring needs {ring_bytes} B of VMEM "
            f"(> {ring_limit} budget at gauge_comps={gauge_comps}); this "
            "z-plane volume / nrhs needs the unfused apply_dhat_planar "
            "path")

    if batched:
        sblk = (nrhs, 1, 1, SPINOR_COMPS, Y, Xh)
    else:
        sblk = (1, 1, SPINOR_COMPS, Y, Xh)
    gblk1 = (1, 1, 1, gauge_comps, Y, Xh)
    gblk4 = (4, 1, 1, gauge_comps, Y, Xh)

    def spec(im):
        if not batched:
            return pl.BlockSpec(sblk, im)
        return pl.BlockSpec(sblk, lambda s, z, _im=im: (0, *_im(s, z)))

    def g(im):
        return pl.BlockSpec(gblk1, im)

    def g4(im):
        return pl.BlockSpec(gblk4, im)

    # Produce stage reads source row ts = (s-1) % T; consume stage reads
    # output row to = (s-3) % T.  All wraps are modular block indices, so
    # the two out-of-range lead-in/lead-out rows of each stage fetch
    # valid (revisited) blocks and are simply gated off in the kernel.
    in_specs = [
        _origin_parity_spec(),
        spec(lambda s, z: ((s - 1) % Tl, z, 0, 0, 0)),       # psi center
        spec(lambda s, z: ((s - 1) % Tl, (z + 1) % Zl, 0, 0, 0)),
        spec(lambda s, z: ((s - 1) % Tl, (z - 1) % Zl, 0, 0, 0)),
        spec(lambda s, z: (s % Tl, z, 0, 0, 0)),             # t+1 of ts
        spec(lambda s, z: ((s - 2) % Tl, z, 0, 0, 0)),       # t-1 of ts
        spec(lambda s, z: ((s - 3) % Tl, z, 0, 0, 0)),       # psi0 @ to
        g4(lambda s, z: (0, (s - 1) % Tl, z, 0, 0, 0)),      # u_o all @ ts
        g4(lambda s, z: (0, (s - 1) % Tl, z, 0, 0, 0)),      # u_e x/y @ ts
        g(lambda s, z: (2, (s - 1) % Tl, (z - 1) % Zl, 0, 0, 0)),
        g(lambda s, z: (3, (s - 2) % Tl, z, 0, 0, 0)),
        g4(lambda s, z: (0, (s - 3) % Tl, z, 0, 0, 0)),      # u_e all @ to
        g4(lambda s, z: (0, (s - 3) % Tl, z, 0, 0, 0)),      # u_o x/y @ to
        g(lambda s, z: (2, (s - 3) % Tl, (z - 1) % Zl, 0, 0, 0)),
        g(lambda s, z: (3, (s - 4) % Tl, z, 0, 0, 0)),
    ]
    out_spec = spec(lambda s, z: ((s - 3) % Tl, z, 0, 0, 0))

    n = nrhs or 1
    model = dhat_stream_traffic_model(Tl, Zl, Y, Xh, nrhs=n,
                                      itemsize=psi_e_p.dtype.itemsize,
                                      window=window,
                                      gauge_comps=gauge_comps)
    cost = pl.CostEstimate(flops=model["flops"],
                           bytes_accessed=model["bytes_total"],
                           transcendentals=0)

    ring_shape = ((window, Zl, SPINOR_COMPS)
                  + ((nrhs,) if batched else ()) + (Y, Xh))
    kernel = functools.partial(_dhat_stream_kernel,
                               kappa2=float(kappa) ** 2, Tl=Tl, Zl=Zl,
                               window=window, batched=batched)
    out_shape = ((nrhs,) if batched else ()) + (Tl, Zl, SPINOR_COMPS, Y, Xh)
    fn = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, psi_e_p.dtype),
        grid=(Tl + 3, Zl),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM(ring_shape, psi_e_p.dtype)],
        interpret=interpret,
        cost_estimate=cost,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="wilson_dhat_fused_stream",
    )
    return fn(_origin_parity(tz_offset),
              psi_e_p, psi_e_p, psi_e_p, psi_e_p, psi_e_p, psi_e_p,
              u_o_p, u_e_p, u_e_p, u_e_p,
              u_e_p, u_o_p, u_o_p, u_o_p)
