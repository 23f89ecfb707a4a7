"""SU(3) gauge-link compression codecs.

Locks down the two compressed link representations against the full
18-real storage, at both the complex matrix level (``repro.core.su3``)
and the planar kernel-layout level (``repro.kernels.layout``):

* ``two_row``  — 12 reals: drop the third row, reconstruct it as the
  complex-conjugate cross product of the first two (exact up to
  rounding: one fused cross product per link);
* ``minimal``  — 8 reals: additionally collapse the first row and the
  reconstructed-row anchor to two phases plus magnitudes recovered from
  unitarity (amplifies rounding through sqrt/atan2 — looser f32 bound).

Also pins the byte/flop accounting the bandwidth story quotes: the
VMEM headroom compressed links free up, the policy boundary shift it
causes, and the halo/HBM traffic-model scaling.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import su3
from repro.kernels import layout, wilson_stencil as ws
from repro.distributed import halo

SHAPE = (3, 4, 2, 3)        # small (T, Z, Y, Xh) worth of links


def _links(n=64, seed=0, dtype=jnp.complex64):
    # Generate at the target precision: reconstruction relies on the
    # links being *unitary at that precision*, so upcasting f32 links
    # to f64 would cap the round-trip accuracy at the f32 defect.
    U = su3.random_gauge(jax.random.PRNGKey(seed), (1, 1, 2, 2 * n),
                         dtype=dtype)
    return U.reshape(-1, 3, 3)[:n]


def _x64():
    from repro.compat import enable_x64
    return enable_x64()


# --- complex-matrix codecs -------------------------------------------


def test_two_row_roundtrip_f32():
    U = _links()
    W = su3.compress_two_row(U)
    assert W.shape == U.shape[:-2] + (2, 3)
    err = float(jnp.max(jnp.abs(su3.reconstruct_two_row(W) - U)))
    assert err <= 1e-6, err


def test_two_row_roundtrip_f64():
    with _x64():
        U = _links(dtype=jnp.complex128)
        W = su3.compress_two_row(U)
        R = su3.reconstruct_two_row(W)
        assert R.dtype == jnp.complex128
        err = float(jnp.max(jnp.abs(R - U)))
        assert err <= 1e-12, err


def test_minimal_roundtrip_f32():
    U = _links()
    W = su3.compress_minimal(U)
    assert W.shape == U.shape[:-2] + (8,)
    assert not jnp.iscomplexobj(W)
    err = float(jnp.max(jnp.abs(su3.reconstruct_minimal(W) - U)))
    assert err <= 1e-4, err


def test_minimal_roundtrip_f64():
    with _x64():
        U = _links(dtype=jnp.complex128)
        W = su3.compress_minimal(U)
        R = su3.reconstruct_minimal(W, dtype=jnp.complex128)
        err = float(jnp.max(jnp.abs(R - U)))
        assert err <= 1e-9, err


def test_reconstructed_links_stay_unitary():
    U = _links(seed=3)
    for R in (su3.reconstruct_two_row(su3.compress_two_row(U)),
              su3.reconstruct_minimal(su3.compress_minimal(U))):
        eye = jnp.eye(3, dtype=R.dtype)
        defect = float(jnp.max(jnp.abs(
            jnp.einsum("...ij,...kj->...ik", R, R.conj()) - eye)))
        assert defect <= 5e-5, defect


# --- planar-layout codecs --------------------------------------------


@pytest.mark.parametrize("mode,comps", [("none", 18), ("two_row", 12),
                                        ("minimal", 8)])
def test_planar_codec_shapes(mode, comps):
    U = su3.random_gauge(jax.random.PRNGKey(1), SHAPE)
    c = layout.gauge_compress_planar(layout.gauge_to_planar(U), mode)
    assert c.shape[-3] == comps
    x = layout.gauge_expand_planar(c) if mode != "none" else c
    assert x.shape[-3] == layout.GAUGE_COMPS


@pytest.mark.parametrize("mode,atol32,atol64", [
    ("two_row", 1e-6, 1e-12),
    ("minimal", 1e-4, 1e-9),
])
def test_planar_codec_roundtrip(mode, atol32, atol64):
    U = su3.random_gauge(jax.random.PRNGKey(2), SHAPE)
    p32 = layout.gauge_to_planar(U, jnp.float32)
    got = layout.gauge_expand_planar(
        layout.gauge_compress_planar(p32, mode))
    np.testing.assert_allclose(np.asarray(got), np.asarray(p32),
                               atol=atol32)
    with _x64():
        U64 = su3.random_gauge(jax.random.PRNGKey(2), SHAPE,
                               dtype=jnp.complex128)
        p64 = layout.gauge_to_planar(U64, jnp.float64)
        got = layout.gauge_expand_planar(
            layout.gauge_compress_planar(p64, mode))
        np.testing.assert_allclose(np.asarray(got), np.asarray(p64),
                                   atol=atol64)


def test_planar_codec_bf16_loose():
    """bf16 planar links survive the codec within bf16 resolution (the
    compressed representation must not blow up at 8-bit mantissas)."""
    U = su3.random_gauge(jax.random.PRNGKey(4), SHAPE)
    p = layout.gauge_to_planar(U, jnp.bfloat16)
    for mode in ("two_row", "minimal"):
        got = layout.gauge_expand_planar(
            layout.gauge_compress_planar(p, mode))
        assert got.dtype == jnp.bfloat16
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - p.astype(jnp.float32))))
        assert err <= 0.1, (mode, err)


def test_gauge_from_planar_auto_expands():
    """The planar->complex decoder accepts compressed planes directly —
    the round trip through compression lands on the same gauge field."""
    U = su3.random_gauge(jax.random.PRNGKey(5), SHAPE)
    p = layout.gauge_to_planar(U, jnp.float32)
    want = layout.gauge_from_planar(p)
    for mode, atol in (("two_row", 1e-6), ("minimal", 1e-4)):
        got = layout.gauge_from_planar(
            layout.gauge_compress_planar(p, mode))
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=atol)


def test_compress_planar_rejects_unknown_mode():
    p = jnp.zeros((4, 2, 2, 18, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="gauge compression"):
        layout.gauge_compress_planar(p, "three_row")


def test_expand_links_planes_noop_at_full_width():
    u = jnp.ones((18, 4, 4), jnp.float32)
    assert layout.expand_links_planes(u) is u


# --- byte/flop accounting --------------------------------------------


def test_gauge_headroom_and_fits_boundary():
    """Compressed links extend the fused-kernel VMEM budget by exactly
    the bytes they free in the double-buffered gauge window; gc=18 is a
    strict no-op on every policy boundary."""
    Y, Xh, itemsize = 4, 4, 4
    plane = 4096                  # a (4, 4) f32 plane fills an (8, 128) tile
    assert ws.gauge_headroom_bytes(Y, Xh, itemsize, gauge_comps=18) == 0
    for gc in (12, 8):
        head = ws.gauge_headroom_bytes(Y, Xh, itemsize, gauge_comps=gc)
        assert head == (18 - gc) * 12 * 2 * plane
        limit = ws.fused_scratch_budget("resident", (1, 4, 24, Y, Xh))
        row = 4 * 24 * plane
        T_plain = limit // row
        T_gc = (limit + head) // row
        assert T_gc > T_plain     # the cap actually moved
        shape = (T_gc, 4, 24, Y, Xh)
        assert ws.fused_dhat_fits(shape, jnp.float32, gauge_comps=gc)
        assert not ws.fused_dhat_fits(shape, jnp.float32)
        assert ws.fused_dhat_policy(shape, jnp.float32,
                                    gauge_comps=gc) == "resident"


def test_hop_traffic_model_scales_with_compression():
    m18 = ws.hop_traffic_model(8, 8, 8, 4)
    m12 = ws.hop_traffic_model(8, 8, 8, 4, gauge_comps=12)
    m8 = ws.hop_traffic_model(8, 8, 8, 4, gauge_comps=8)
    assert m12["bytes_gauge"] * 18 == m18["bytes_gauge"] * 12
    assert m8["bytes_gauge"] * 18 == m18["bytes_gauge"] * 8
    # Spinor traffic is untouched; reconstruction flops are accounted.
    assert m12["bytes_spinor"] == m18["bytes_spinor"]
    assert m18["flops_recon"] == 0
    assert m12["flops_recon"] == 42 * 8 * 8 * 8 * 8 * 4
    assert m8["flops_recon"] > m12["flops_recon"]
    assert m12["flops"] == m18["flops"] + m12["flops_recon"]


def test_halo_traffic_model_scales_with_compression():
    m18 = halo.halo_traffic_model(4, 4, 4, 4)
    m12 = halo.halo_traffic_model(4, 4, 4, 4, gauge_comps=12)
    m8 = halo.halo_traffic_model(4, 4, 4, 4, gauge_comps=8)
    assert m12["bytes_gauge_exchange"] * 3 == m18["bytes_gauge_exchange"] * 2
    assert m8["bytes_gauge_exchange"] * 9 == m18["bytes_gauge_exchange"] * 4
    assert m12["bytes_spinor_exchange"] == m18["bytes_spinor_exchange"]
    for m in (m18, m12, m8):
        assert m["bytes_dhat_exchange"] == 2 * m["bytes_hop_exchange"]
