"""The plain reference and the work counts, on the CPU."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import problem, reference, sources, work

EXTENTS = (4, 4, 4, 8)


@pytest.fixture(scope="module")
def gauge():
    key = problem.gauge_key(2**40 + 3)
    U, Ue, Uo = jax.jit(problem._haar_halves, static_argnums=1)(key, EXTENTS)
    return U, Ue, Uo


def test_reference_matches_the_program_operator(gauge):
    from repro.core import wilson
    U, _, _ = gauge
    eta_e, eta_o = sources.gaussian(7, 0, EXTENTS)
    psi = reference.unpack(jnp.asarray(eta_e), jnp.asarray(eta_o))
    ours = reference.apply_wilson(U, psi, 0.13)
    theirs = wilson.apply_wilson(U, psi, 0.13)
    err = float(jnp.max(jnp.abs(ours - theirs)) / jnp.max(jnp.abs(theirs)))
    assert err < 1e-5


def test_even_odd_convention_is_the_programs():
    from repro.core import evenodd
    eta_e, eta_o = sources.gaussian(8, 1, EXTENTS)
    full = reference.unpack(jnp.asarray(eta_e), jnp.asarray(eta_o))
    e, o = evenodd.pack(full)
    assert np.array_equal(np.asarray(e), eta_e)
    assert np.array_equal(np.asarray(o), eta_o)
    e2, o2 = reference.pack(full)
    assert np.array_equal(np.asarray(e2), eta_e)


def test_point_source_sits_at_its_site():
    eta_e, eta_o = sources.point(9, 2, EXTENTS)
    t, z, y, x = sources.point_site(9, 2, EXTENTS)
    for col in range(12):
        full = np.asarray(reference.unpack(jnp.asarray(eta_e[col]),
                                           jnp.asarray(eta_o[col])))
        assert full.sum() == 1
        assert full[t, z, y, x, col // 3, col % 3] == 1


def test_haar_links_are_special_unitary(gauge):
    U = np.asarray(gauge[0], np.complex128)
    eye = np.einsum("...ab,...cb->...ac", U, U.conj())
    assert np.abs(eye - np.eye(3)).max() < 1e-5
    assert np.abs(np.linalg.det(U) - 1).max() < 1e-5


def test_seeds_beyond_32_bits_differ():
    a = np.asarray(jax.random.key_data(problem.gauge_key(5)))
    b = np.asarray(jax.random.key_data(problem.gauge_key(5 + 2**32)))
    assert not np.array_equal(a, b)
    assert not np.array_equal(sources.gaussian(5, 0, EXTENTS)[0],
                              sources.gaussian(5 + 2**32, 0, EXTENTS)[0])


def test_true_residual_of_an_exact_solution(gauge):
    """eta := D_W xi for a known xi: the residual is rounding alone, and
    a perturbed xi reads as wrong."""
    U, _, _ = gauge
    xi_e, xi_o = sources.gaussian(3, 0, EXTENTS)
    with jax.enable_x64(True):
        full = reference.unpack(jnp.asarray(xi_e, jnp.complex128),
                                jnp.asarray(xi_o, jnp.complex128))
        eta = reference.apply_wilson(jnp.asarray(U, jnp.complex128), full,
                                     0.13)
        eta_e, eta_o = (np.asarray(h) for h in reference.pack(eta))
    ref = reference.Reference(U, 0.13)
    assert ref.true_residuals(eta_e, eta_o, xi_e, xi_o)[0] < 1e-12
    r = ref.true_residuals(eta_e, eta_o, xi_e * 1.0001, xi_o)[0]
    assert r > 1e-5


def test_hop_block_bytes_by_hand():
    # V = 512 sites: links 4 * 512 * 18 reals, spinors 12 columns * 512 *
    # 24 reals (half the sites read, half written), 4 bytes each.
    assert work.hop_block_bytes(EXTENTS, 12, 4) == 4 * (36864 + 147456)
    assert work.hop_block_bytes(EXTENTS, 1, 4) == 4 * (36864 + 12288)
    # Bandwidth-bound at any nrhs: 1.7 flop/B at 1, under 7 as nrhs grows.
    i1 = work.hop_block_flops(EXTENTS, 1) / work.hop_block_bytes(EXTENTS, 1, 4)
    ibig = (work.hop_block_flops(EXTENTS, 10**6)
            / work.hop_block_bytes(EXTENTS, 10**6, 4))
    assert 1.7 < i1 < 1.72 and ibig < 7


def test_unknown_kernel_and_device_are_errors():
    with pytest.raises(KeyError):
        work.kernel_hop_blocks("wilson_hop_xx")
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    assert work.kernel_hop_blocks("wilson_hop_eo") == 1
    assert work.kernel_hop_blocks("wilson_dhat_fused") == 2
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _roofline_rec(kernels):
    return {"trace": {"kernels": kernels},
            "cell": {"config": {"extents": list(EXTENTS)}},
            "device_kind": "TPU v5 lite", "root": work.HERE}


def test_dhat_roofline_by_hand():
    from chipbench.bench import Bench
    read = Bench().reader("dhat_roofline")
    column = 256 * 24
    calls = [{"name": "wilson_hop_eo", "dtype": "f32", "elems": 12 * column,
              "s": 1e-3, "calls": 10}]
    want = 100 * 10 * 4 * (36864 + 147456) / 819e9 / 1e-3
    assert read(_roofline_rec(calls)) == pytest.approx(want)
    with pytest.raises(KeyError):
        read(_roofline_rec([{**calls[0], "name": "mystery_kernel"}]))
    assert read(_roofline_rec([])) is None


def test_benchmark_names_resolve():
    """Every cell's configuration, traffic, driver and metric readers
    exist; every per-layer metric names an end-to-end metric."""
    from chipbench.bench import ROOT, Bench
    b = Bench()
    e2e = {m["name"] for m in b.spec["end_to_end"]}
    for w in b.spec["workloads"]:
        cell = b.cell(w["name"])
        b.driver(cell["traffic"]["driver"])
        for m in b.per_layer(w["name"]):
            assert m["moves"] in e2e
            b.reader(m["name"])
        names = {m["name"] for m in b.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
    for c in b.spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
