"""Streaming plane-window fused Dhat: the three-way VMEM policy at its
exact byte boundaries, the cap-lift, and silent-correct fallback.

The resident fused kernel's scratch is the whole (batched) odd
intermediate — ``nrhs * 24 * T * Z`` planes of ``(Y, Xh)``, each padded to
whole (sublane, 128-lane) VMEM tiles — against what the per-kernel VMEM
limit leaves after the pipeline blocks.  The streaming kernel replaces it
with a 4-row t-plane ring whose size is independent of T.  These tests
pin the tiled counts, the selection policy (resident -> stream ->
unfused) at the last shape that fits and one plane over, for
f32/f64/bf16 and nrhs in {1, 2}, and that every path computes the same
operator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends
from repro.core import evenodd, su3
from repro.kernels import layout, ops
from repro.kernels import wilson_stencil as ws
from repro.kernels.wilson_stencil import (
    STREAM_WINDOW_ROWS, dhat_planar_fused_stream, dhat_stream_traffic_model,
    fused_dhat_fits, fused_dhat_policy, fused_dhat_stream_fits,
    stream_ring_bytes)

KAPPA = 0.13

DTYPE = {"f32": jnp.float32, "f64": jnp.float64, "bf16": jnp.bfloat16}
# VMEM bytes of one (8, 4) plane: a whole (8, 128) tile of 32-bit words
# for f32, (16, 128) for bf16, (8, 128) of 64-bit words for f64.
Y, XH = 8, 4
PLANE = {"f32": 4096, "bf16": 4096, "f64": 8192}


def _batched(shape, nrhs):
    return (nrhs, *shape) if nrhs > 1 else shape


def _resident_boundary_shape(dt, nrhs):
    """Planar shape with the most t-rows whose resident scratch fits.

    One z-plane per t-row keeps a row small enough that the 4-row ring
    still fits where the resident scratch stops, in every dtype."""
    probe = _batched((1, 1, 24, Y, XH), nrhs)
    budget = ws.fused_scratch_budget("resident", probe, DTYPE[dt])
    T = budget // (24 * nrhs * PLANE[dt])
    return _batched((T, 1, 24, Y, XH), nrhs)


def _stream_boundary_shape(dt, nrhs):
    """Planar shape with the most z-planes whose 4-row ring fits (T far
    too large for the resident scratch)."""
    probe = _batched((64, 1, 24, Y, XH), nrhs)
    budget = ws.fused_scratch_budget("stream", probe, DTYPE[dt])
    Z = budget // (STREAM_WINDOW_ROWS * 24 * nrhs * PLANE[dt])
    return _batched((64, Z, 24, Y, XH), nrhs)


def _bump(shape, axis_from_t):
    """Same shape with the (batched-aware) T or Z extent + 1 plane."""
    lead = 1 if len(shape) == 6 else 0
    i = lead + axis_from_t
    return shape[:i] + (shape[i] + 1,) + shape[i + 1:]


@pytest.mark.parametrize("nrhs", [1, 2])
@pytest.mark.parametrize("dt", ["f32", "f64", "bf16"])
def test_resident_boundary_exact_and_one_plane_over(dt, nrhs):
    """At the last t-row that fits: resident.  One t-plane over: the
    resident scratch no longer fits but the ring does -> stream."""
    shape = _resident_boundary_shape(dt, nrhs)
    T = shape[-5]
    assert T > STREAM_WINDOW_ROWS
    assert ws.plane_vmem_bytes(Y, XH, DTYPE[dt]) == PLANE[dt]
    assert ws.fused_scratch_bytes(shape, DTYPE[dt]) == (
        T * 24 * nrhs * PLANE[dt])
    assert fused_dhat_fits(shape, DTYPE[dt])
    assert fused_dhat_policy(shape, DTYPE[dt]) == "resident"

    over = _bump(shape, 0)                      # one extra t-plane row
    assert not fused_dhat_fits(over, DTYPE[dt])
    assert fused_dhat_stream_fits(over, DTYPE[dt])
    assert fused_dhat_policy(over, DTYPE[dt]) == "stream"


@pytest.mark.parametrize("nrhs", [1, 2])
@pytest.mark.parametrize("dt", ["f32", "f64", "bf16"])
def test_stream_boundary_exact_and_one_plane_over(dt, nrhs):
    """At the last z-plane that fits the ring -> stream; one z-plane
    over it cannot (the ring holds full z-rows) -> the silent two-kernel
    fallback."""
    shape = _stream_boundary_shape(dt, nrhs)
    Z = shape[-4]
    assert Z >= 1
    assert stream_ring_bytes(shape, DTYPE[dt]) == (
        STREAM_WINDOW_ROWS * Z * 24 * nrhs * PLANE[dt])
    assert not fused_dhat_fits(shape, DTYPE[dt])
    assert fused_dhat_policy(shape, DTYPE[dt]) == "stream"

    over = _bump(shape, 1)                      # one extra z-plane
    assert not fused_dhat_stream_fits(over, DTYPE[dt])
    assert fused_dhat_policy(over, DTYPE[dt]) == "unfused"


def test_ring_bytes_independent_of_t():
    """The cap-lift itself: growing T leaves the ring untouched while the
    resident scratch grows linearly."""
    base = (8, 4, 24, 4, 4)
    tall = (512, 4, 24, 4, 4)
    assert stream_ring_bytes(base) == stream_ring_bytes(tall)
    assert ws.fused_scratch_bytes(tall) == 64 * ws.fused_scratch_bytes(base)
    # Batched shapes scale the ring by nrhs, like the resident scratch.
    assert stream_ring_bytes((8, *base)) == 8 * stream_ring_bytes(base)


def test_acceptance_lattice_runs_streaming_policy():
    """16x16x16x32: its (16, 16) planes take (16, 128) tiles, 8 KiB in
    f32 and 4 KiB in bf16.  One bf16 right-hand side streams (6 MiB
    ring); in f32 the 12 MiB ring plus the pipeline passes the 16 MiB
    limit, and at nrhs=8 the ring alone is 96 MiB -> unfused."""
    shape = (16, 16, 24, 16, 16)                # planar
    assert stream_ring_bytes(shape, jnp.float32) == 12 << 20
    assert stream_ring_bytes(shape, jnp.bfloat16) == 6 << 20
    assert fused_dhat_policy(shape, jnp.bfloat16) == "stream"
    assert fused_dhat_policy(shape, jnp.float32) == "unfused"
    batched = (8, *shape)
    assert not fused_dhat_fits(batched, jnp.float32)
    assert stream_ring_bytes(batched, jnp.float32) == 96 << 20
    assert fused_dhat_policy(batched, jnp.float32) == "unfused"
    assert fused_dhat_policy(batched, jnp.float64) == "unfused"


def test_stream_traffic_model_accounts_overhead():
    m = dhat_stream_traffic_model(16, 8, 8, 8, nrhs=2)
    r = ws.hop_traffic_model(16, 8, 8, 8, nrhs=2)
    assert m["recompute_rows"] == 2
    assert m["window_rows"] == STREAM_WINDOW_ROWS
    # Flops: two hopping blocks + 2 recomputed rows of the first + axpy.
    assert m["flops"] > 2 * r["flops"]
    assert m["flops"] < 2.2 * r["flops"]
    # The ring is window/T of the resident scratch.
    assert m["vmem_ring_bytes"] * 16 == m["vmem_resident_bytes"] * 4


def _rand_planar(shape, seed=0, nrhs=None):
    U = su3.random_gauge(jax.random.PRNGKey(seed), shape)
    k = jax.random.PRNGKey(seed + 1)
    bshape = (() if nrhs is None else (nrhs,)) + (*shape, 4, 3)
    psi = (jax.random.normal(k, bshape)
           + 1j * jax.random.normal(jax.random.fold_in(k, 1), bshape)
           ).astype(jnp.complex64)
    Ue, Uo = evenodd.pack_gauge(U)
    if nrhs is None:
        e, _ = evenodd.pack(psi)
    else:
        e, _ = jax.vmap(evenodd.pack)(psi)
    return Ue, Uo, e


def test_stream_kernel_matches_resident_and_unfused(small_eo):
    """All three fused paths compute the same operator (forced
    selection, planar in/out)."""
    Ue, Uo, e, _, kappa = small_eo
    Uep, Uop = ops.make_planar_fields(Ue, Uo)
    ep = layout.spinor_to_planar(e)
    outs = {f: ops.apply_dhat_planar_any(Uep, Uop, ep, kappa, fused=f,
                                         interpret=True)
            for f in ("resident", "stream", "unfused")}
    np.testing.assert_allclose(np.asarray(outs["stream"]),
                               np.asarray(outs["unfused"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs["resident"]),
                               np.asarray(outs["unfused"]), atol=1e-5)
    # Booleans keep their legacy meaning.
    np.testing.assert_array_equal(
        np.asarray(ops.apply_dhat_planar_any(Uep, Uop, ep, kappa,
                                             fused=True, interpret=True)),
        np.asarray(outs["resident"]))
    with pytest.raises(ValueError, match="fused="):
        ops.apply_dhat_planar_any(Uep, Uop, ep, kappa, fused="bogus",
                                  interpret=True)


def test_auto_policy_routes_over_budget_lattice_to_stream(monkeypatch):
    """A lattice that fails ``fused_dhat_fits`` must run the STREAMING
    kernel under the auto policy (not the two-kernel fallback), and still
    match the jnp reference — the cap-lift acceptance shape in miniature
    (the VMEM limit is set so the ring just fits instead of the lattice
    grown; the policy reads the live module constant).  T=16 is four
    times the 4-row window, so the ring saves more than the streaming
    kernel's larger pipeline costs."""
    Ue, Uo, e = _rand_planar((16, 2, 2, 4), seed=23)
    kappa = KAPPA
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    Uep, Uop = ops.make_planar_fields(Ue, Uo)
    ep = layout.spinor_to_planar(e)
    # Limit at which the ring fits exactly; the resident scratch not.
    ring = stream_ring_bytes(ep.shape)
    assert ring < ws.fused_scratch_bytes(ep.shape)
    monkeypatch.setattr(ws, "_VMEM_LIMIT_BYTES", ws._VMEM_LIMIT_BYTES + ring
                        - ws.fused_scratch_budget("stream", ep.shape))
    assert not fused_dhat_fits(ep.shape, ep.dtype)
    assert fused_dhat_policy(ep.shape, ep.dtype) == "stream"

    jaxpr = str(jax.make_jaxpr(
        lambda v: ops.apply_dhat_planar_any(Uep, Uop, v, kappa,
                                            interpret=True))(ep))
    assert "wilson_dhat_fused_stream" in jaxpr
    assert jaxpr.count("pallas_call") == 1

    got = layout.spinor_from_planar(
        ops.apply_dhat_planar_any(Uep, Uop, ep, kappa, interpret=True))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.apply_dhat(e, kappa)),
                               atol=1e-5)


def test_auto_policy_unfused_fallback_is_silent_correct(monkeypatch,
                                                        small_eo):
    """Below even the ring budget the auto policy must silently produce
    the correct operator through the two-kernel path."""
    Ue, Uo, e, _, kappa = small_eo
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    Uep, Uop = ops.make_planar_fields(Ue, Uo)
    ep = layout.spinor_to_planar(e)
    monkeypatch.setattr(ws, "_VMEM_LIMIT_BYTES", 1)
    assert fused_dhat_policy(ep.shape, ep.dtype) == "unfused"
    got = layout.spinor_from_planar(
        ops.apply_dhat_planar_any(Uep, Uop, ep, kappa, interpret=True))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.apply_dhat(e, kappa)),
                               atol=1e-5)


def test_stream_kernel_rejects_over_budget_ring_off_interpret():
    """On real hardware an over-budget ring must fail loudly, before any
    lowering (mirrors the resident kernel's guard)."""
    Z, Y, Xh = 17, 32, 64                       # ring 4*Z*24*16 KiB > 16MiB
    ep = jnp.zeros((8, Z, 24, Y, Xh), jnp.float32)
    u = jnp.zeros((4, 8, Z, 18, Y, Xh), jnp.float32)
    assert not fused_dhat_stream_fits(ep.shape)
    with pytest.raises(ValueError, match="streaming Dhat ring"):
        dhat_planar_fused_stream(u, u, ep, KAPPA, interpret=False)


def test_stream_kernel_rejects_too_small_window(small_eo):
    Ue, Uo, e, _, _ = small_eo
    Uep, Uop = ops.make_planar_fields(Ue, Uo)
    ep = layout.spinor_to_planar(e)
    with pytest.raises(ValueError, match="stream window"):
        dhat_planar_fused_stream(Uep, Uop, ep, KAPPA, window=3,
                                 interpret=True)


def test_stream_backend_registered_and_batched_single_kernel():
    """pallas_fused_stream registers like any other backend; its batched
    Dhat lowers to ONE pallas_call for the whole RHS block."""
    assert "pallas_fused_stream" in backends.available_backends()
    Ue, Uo, e = _rand_planar((4, 4, 4, 8), seed=3, nrhs=4)
    bops = backends.make_wilson_ops("pallas_fused_stream", Ue, Uo,
                                    interpret=True)
    assert bops.domain == "planar"
    v = bops.to_domain_batched(e)
    txt = str(jax.make_jaxpr(
        lambda w: bops.apply_dhat_native_batched(w, KAPPA))(v))
    assert txt.count("pallas_call") == 1
    assert "wilson_dhat_fused_stream" in txt
