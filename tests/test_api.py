"""Public API (`repro.api`): spec validation, the bind-once
WilsonMatrix pytree (flatten/unflatten, jit-argument no-retrace,
rebuild-from-leaves), and SolveSession compiled-solve caching (exactly
one trace for N same-shape solves, per backend)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, backends
from repro.core import evenodd, solver, su3

KAPPA = 0.13
SHAPE = (4, 4, 4, 8)


def _interpret(name):
    return (True if name.startswith("pallas")
            and jax.default_backend() != "tpu" else None)


def _bind_matrix(name, Ue, Uo, kappa=KAPPA):
    return api.WilsonMatrix.bind(
        Ue, Uo, kappa, backend=api.BackendSpec(name,
                                               interpret=_interpret(name)))


def make_eo(shape=SHAPE, seed=0, nrhs=None):
    U = su3.random_gauge(jax.random.PRNGKey(seed), shape)
    k = jax.random.PRNGKey(seed + 1)
    bshape = (() if nrhs is None else (nrhs,)) + (*shape, 4, 3)
    psi = (jax.random.normal(k, bshape)
           + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                    bshape)).astype(jnp.complex64)
    Ue, Uo = evenodd.pack_gauge(U)
    if nrhs is None:
        e, o = evenodd.pack(psi)
    else:
        e, o = jax.vmap(evenodd.pack)(psi)
    return Ue, Uo, e, o


# --- specs ------------------------------------------------------------


def test_lattice_spec_validation():
    lat = api.LatticeSpec((4, 4, 4, 8))
    assert (lat.T, lat.Z, lat.Y, lat.X, lat.Xh) == (4, 4, 4, 8, 4)
    assert lat.spinor_eo_shape() == (4, 4, 4, 4, 4, 3)
    assert lat.spinor_eo_shape(nrhs=3) == (3, 4, 4, 4, 4, 4, 3)
    with pytest.raises(ValueError, match="4 positive ints"):
        api.LatticeSpec((4, 4, 8))
    with pytest.raises(ValueError, match="must be even"):
        api.LatticeSpec((4, 4, 4, 7))
    Ue, _, _, _ = make_eo()
    assert api.LatticeSpec.from_eo_gauge(Ue) == api.LatticeSpec(SHAPE)


def test_solve_spec_method_choices_derived_from_solver():
    # The satellite contract: the choice list is derived, not duplicated.
    assert api.SolveSpec.METHODS is solver.KRYLOV_METHODS
    assert "cg" in api.SolveSpec.METHODS
    with pytest.raises(ValueError, match="'cg', 'cgnr', 'bicgstab'"):
        api.SolveSpec(method="sor")


def test_solve_spec_validation():
    with pytest.raises(ValueError, match="tol"):
        api.SolveSpec(tol=0.0)
    with pytest.raises(ValueError, match="nrhs"):
        api.SolveSpec(nrhs=0)
    with pytest.raises(ValueError, match="inner_dtype"):
        api.SolveSpec(inner_dtype="f8")
    with pytest.raises(ValueError, match="recompute_every"):
        api.SolveSpec(recompute_every=-1)
    # frozen + hashable: usable as a cache key
    assert hash(api.SolveSpec()) == hash(api.SolveSpec())


def test_backend_spec_validation_against_capabilities():
    with pytest.raises(ValueError, match="unknown backend 'nope'"):
        api.BackendSpec("nope").validated()
    # jnp declares no dtype / interpret knobs
    with pytest.raises(ValueError, match="no compute dtype"):
        api.BackendSpec("jnp", dtype="f32").validated()
    with pytest.raises(ValueError, match="no interpret mode"):
        api.BackendSpec("jnp", interpret=True).validated()
    with pytest.raises(ValueError, match="unknown compute dtype"):
        api.BackendSpec("pallas", dtype="f8")
    ok = api.BackendSpec("pallas_fused", dtype="bfloat16",
                         interpret=True).validated()
    assert ok.dtype == "bf16"      # normalized spelling
    assert ok.factory_opts() == {"dtype": jnp.bfloat16, "interpret": True}
    # "auto" resolves to a concrete registered name
    assert api.BackendSpec("auto").validated().name in \
        backends.available_backends()


@pytest.mark.parametrize("name", ["pallas", "pallas_fused",
                                  "pallas_fused_stream"])
def test_backend_spec_rejects_compiled_f64_pallas(name):
    """The TPU compiler builds no f64 Pallas kernel: asking for one with
    the interpreter off fails at spec time instead of degrading."""
    with pytest.raises(ValueError, match="no compiled f64 kernel"):
        api.BackendSpec(name, dtype="f64", interpret=False).validated()
    assert api.BackendSpec(name, dtype="f64",
                           interpret=True).validated().dtype == "f64"
    assert api.BackendSpec(name, dtype="f32",
                           interpret=False).validated().dtype == "f32"


def test_escalation_f64_rung_of_compiled_pallas_runs_jnp():
    """The refinement ladder's f64 rung never asks a compiled Pallas
    backend for an f64 kernel: it drops to the jnp reference, while the
    interpreter (off-TPU default) keeps the Pallas backend."""
    Ue, Uo, _, _ = make_eo(seed=3)
    for interpret, want in ((False, "jnp"), (True, "pallas")):
        D = api.WilsonMatrix.bind(
            Ue, Uo, KAPPA,
            backend=api.BackendSpec("pallas", interpret=interpret))
        rung_ops = api.SolveSession(D)._escalation_factory()("f64")
        assert rung_ops.backend == want, interpret


def test_available_backends_sorted_and_backend_info():
    names = backends.available_backends()
    assert names == sorted(names)
    for name in names:
        caps = backends.backend_info(name)
        assert caps.name == name
        assert caps.domain in ("complex", "planar", "planar_sharded")
    assert backends.backend_info("pallas_fused").batched_kernels
    assert "auto" in backends.backend_info("pallas_fused").policies
    assert not backends.backend_info("jnp").batched_kernels
    with pytest.raises(ValueError, match="backend_info"):
        backends.backend_info("nope")


# --- WilsonMatrix -----------------------------------------------------


def test_wilson_matrix_applies_match_reference():
    Ue, Uo, e, _ = make_eo(seed=2)
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    D = _bind_matrix("pallas_fused", Ue, Uo)
    np.testing.assert_allclose(
        np.asarray(D(e)), np.asarray(ref.apply_dhat(e, KAPPA)),
        atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(D.dagger(e)),
        np.asarray(ref.apply_dhat_dagger(e, KAPPA)), atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(D.normal(e)),
        np.asarray(ref.apply_dhat_dagger(ref.apply_dhat(e, KAPPA),
                                         KAPPA)), atol=5e-5)


def test_wilson_matrix_batched_apply():
    Ue, Uo, e, _ = make_eo(seed=3, nrhs=2)
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    D = _bind_matrix("pallas_fused", Ue, Uo)
    want = jnp.stack([ref.apply_dhat(e[n], KAPPA) for n in range(2)])
    np.testing.assert_allclose(np.asarray(D(e)), np.asarray(want),
                               atol=5e-5)


def test_wilson_matrix_pytree_flatten_unflatten():
    Ue, Uo, e, _ = make_eo(seed=4)
    D = _bind_matrix("pallas_fused", Ue, Uo)
    leaves, treedef = jax.tree_util.tree_flatten(D)
    assert len(leaves) == 2          # planar gauge halves are the leaves
    assert all(l.dtype == jnp.float32 for l in leaves)
    D2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(np.asarray(D(e)), np.asarray(D2(e)))
    # aux data (specs) survive
    assert D2.backend == D.backend and D2.lattice == D.lattice
    assert D2.kappa == D.kappa


def test_wilson_matrix_rebuilds_ops_from_mapped_leaves():
    """tree_map produces a matrix whose operators see the NEW leaves:
    zeroed gauge turns Dhat into the identity."""
    Ue, Uo, e, _ = make_eo(seed=5)
    D = _bind_matrix("jnp", Ue, Uo)
    D0 = jax.tree_util.tree_map(jnp.zeros_like, D)
    np.testing.assert_allclose(np.asarray(D0(e)), np.asarray(e),
                               atol=1e-6)


def test_wilson_matrix_jit_argument_no_retrace():
    """Two same-shape matrices share one jit cache entry, and the
    compiled fn reads the gauge from the argument (not a baked
    constant)."""
    Ue, Uo, e, _ = make_eo(seed=6)
    U2e, U2o, _, _ = make_eo(seed=16)
    D1 = _bind_matrix("jnp", Ue, Uo)
    D2 = _bind_matrix("jnp", U2e, U2o)
    traces = []

    @jax.jit
    def f(m, psi):
        traces.append(1)
        return m(psi)

    out1 = f(D1, e)
    out2 = f(D2, e)
    assert len(traces) == 1, f"retraced: {len(traces)}"
    ref2 = backends.make_wilson_ops("jnp", U2e, U2o)
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(ref2.apply_dhat(e, KAPPA)),
        atol=1e-5)
    # and the two results differ (different gauges really were used)
    assert float(jnp.max(jnp.abs(out1 - out2))) > 1e-3


def test_wilson_matrix_composes_under_vmap():
    Ue, Uo, e, _ = make_eo(seed=7, nrhs=3)
    D = _bind_matrix("jnp", Ue, Uo)
    got = jax.vmap(lambda p: D(p))(e)
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    want = jnp.stack([ref.apply_dhat(e[n], KAPPA) for n in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


def test_wilson_matrix_from_ops_wraps_bound_backend():
    Ue, Uo, e, _ = make_eo(seed=8)
    bops = backends.make_wilson_ops("jnp", Ue, Uo)
    D = api.WilsonMatrix.from_ops(bops, KAPPA, gauge=(Ue, Uo))
    np.testing.assert_array_equal(
        np.asarray(D(e)), np.asarray(bops.apply_dhat(e, KAPPA)))
    # gauge round-trip for refined solves (c128 needs x64 enabled)
    from repro.compat import enable_x64
    with enable_x64():
        U64e, _ = D.gauge_complex()
        assert U64e.dtype == jnp.complex128


def test_wilson_matrix_gauge_complex_from_planar_leaves():
    Ue, Uo, _, _ = make_eo(seed=9)
    D = _bind_matrix("pallas_fused", Ue, Uo)
    U64e, U64o = D.gauge_complex()
    # planar leaves are f32: reconstruction is exact at f32 precision
    np.testing.assert_allclose(np.asarray(U64e),
                               np.asarray(Ue.astype(jnp.complex128)),
                               atol=1e-7)
    # an unflattened matrix loses the exact-gauge ref and reconstructs
    leaves, treedef = jax.tree_util.tree_flatten(D)
    D2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_allclose(np.asarray(D2.gauge_complex()[0]),
                               np.asarray(U64e), atol=1e-7)


def test_wilson_matrix_gauge_complex_exact_despite_bf16_leaves():
    """Refined solves must target the TRUE gauge: a bf16-bound matrix
    keeps the exact complex gauge for gauge_complex() even though its
    planar leaves are rounded to ~3 significant digits."""
    Ue, Uo, _, _ = make_eo(seed=9)
    D = api.WilsonMatrix.bind(
        Ue, Uo, KAPPA, backend=api.BackendSpec(
            "pallas_fused", dtype="bf16", interpret=_interpret("pallas")))
    leaves = jax.tree_util.tree_flatten(D)[0]
    assert leaves[0].dtype == jnp.bfloat16
    U64e, _ = D.gauge_complex()
    np.testing.assert_array_equal(
        np.asarray(U64e.astype(jnp.complex64)), np.asarray(Ue))


# --- SolveSession caching --------------------------------------------


@pytest.mark.parametrize("name", backends.available_backends())
def test_session_compiles_once_per_backend(name):
    """The acceptance criterion: N same-shape solves through one
    session trigger exactly ONE trace (per backend, interpret mode
    off-TPU)."""
    Ue, Uo, _, _ = make_eo(seed=10)
    session = api.SolveSession(
        _bind_matrix(name, Ue, Uo),
        api.SolveSpec(method="bicgstab", tol=1e-3, max_iters=25))
    n = 3
    for i in range(n):
        _, _, e, o = make_eo(seed=20 + i)
        xe, xo, res = session.solve(e, o)
        assert bool(jnp.all(jnp.isfinite(jnp.abs(xe))))
    st = session.stats()
    assert st["solves"] == n
    assert st["traces"] == 1, st
    assert st["cache_hits"] == n - 1 and st["cache_misses"] == 1, st
    (krow,) = st["keys"].values()
    assert krow["solves"] == n and krow["kind"] == "plain"
    assert krow["first_solve_s"] > 0
    assert krow["steady_state_s"] > 0


def test_session_new_key_per_shape_and_spec():
    Ue, Uo, e, o = make_eo(seed=11)
    _, _, eb, ob = make_eo(seed=11, nrhs=2)
    session = api.SolveSession(_bind_matrix("jnp", Ue, Uo))
    spec = api.SolveSpec(method="bicgstab", tol=1e-3, max_iters=25)
    session.solve(e, o, spec)
    session.solve(eb, ob, spec)                      # new shape (nrhs=2)
    session.solve(e, o, dataclasses.replace(spec, tol=1e-2))  # new spec
    session.solve(e, o, spec)                        # hit
    st = session.stats()
    assert st["cache_misses"] == 3 and st["cache_hits"] == 1, st
    assert st["traces"] == 3, st
    assert len(st["keys"]) == 3


def test_session_solution_correct():
    Ue, Uo, e, o = make_eo(seed=12)
    session = api.SolveSession(
        _bind_matrix("pallas_fused", Ue, Uo),
        api.SolveSpec(method="bicgstab", tol=1e-5))
    xe, xo, res = session.solve(e, o)
    assert bool(res.converged)
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    rhs = e + KAPPA * ref.hop_eo(o)
    rel = float(jnp.linalg.norm(rhs - ref.apply_dhat(xe, KAPPA))
                / jnp.linalg.norm(rhs))
    assert rel < 1e-4, rel
    # odd reconstruction: xi_o = eta_o + kappa H_oe xi_e
    np.testing.assert_allclose(
        np.asarray(xo), np.asarray(o + KAPPA * ref.hop_oe(xe)),
        atol=5e-5)


def test_session_cg_method_solves_normal_equations():
    Ue, Uo, e, o = make_eo(seed=13)
    session = api.SolveSession(
        _bind_matrix("jnp", Ue, Uo),
        api.SolveSpec(method="cg", tol=1e-6))
    xe, _, res = session.solve(e, o)
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    rhs = e + KAPPA * ref.hop_eo(o)
    rel = float(jnp.linalg.norm(rhs - ref.apply_dhat(xe, KAPPA))
                / jnp.linalg.norm(rhs))
    assert rel < 1e-4, rel


def test_session_shape_validation():
    Ue, Uo, e, o = make_eo(seed=14)
    session = api.SolveSession(_bind_matrix("jnp", Ue, Uo))
    with pytest.raises(ValueError, match="does not match lattice"):
        session.solve(e[:2], o[:2])
    with pytest.raises(ValueError, match="sources disagree"):
        session.solve(e, o[:2])
    with pytest.raises(ValueError, match="nrhs"):
        session.solve(e, o, api.SolveSpec(nrhs=4))


def test_session_requires_matrix():
    Ue, Uo, _, _ = make_eo(seed=15)
    bops = backends.make_wilson_ops("jnp", Ue, Uo)
    with pytest.raises(TypeError, match="WilsonMatrix"):
        api.SolveSession(bops)


def test_session_refined_solve_cached():
    """Mixed-precision refinement through the session: RefinedResult
    contract, correct to the f64 tolerance, one cache entry reused."""
    from repro.compat import enable_x64

    with enable_x64():
        Ue, Uo, e, o = make_eo(seed=17)
        e, o = e.astype(jnp.complex128), o.astype(jnp.complex128)
        session = api.SolveSession(
            _bind_matrix("jnp", Ue, Uo),
            api.SolveSpec(method="cgnr", tol=1e-8, inner_dtype="f32"))
        xe, xo, res = session.solve(e, o)
        xe2, _, res2 = session.solve(e, o)
        assert bool(res.converged) and bool(res2.converged)
        assert res.f64_applies < 2 * int(jnp.max(res.iterations)) + 2
        U64e = Ue.astype(jnp.complex128)
        U64o = Uo.astype(jnp.complex128)
        ref = backends.make_wilson_ops("jnp", U64e, U64o)
        rhs = e + KAPPA * ref.hop_eo(o)
        rel = float(jnp.linalg.norm(rhs - ref.apply_dhat(xe, KAPPA))
                    / jnp.linalg.norm(rhs))
        assert rel <= 1e-8, rel
    st = session.stats()
    assert st["cache_hits"] == 1 and st["cache_misses"] == 1, st
    (krow,) = st["keys"].values()
    assert krow["kind"] == "refined" and krow["solves"] == 2


# --- one-shot convenience --------------------------------------------


def test_api_one_shot_solve():
    Ue, Uo, e, o = make_eo(seed=18)
    xe, xo, res = api.solve(
        Ue, Uo, e, o, KAPPA, backend="jnp",
        spec=api.SolveSpec(method="bicgstab", tol=1e-5))
    assert bool(res.converged)


def test_solve_wilson_eo_shim_is_gone():
    """The deprecated kwarg-sprawl entry point reached its removal
    horizon (PR 7): the symbol must not exist anywhere — ``api.solve``
    / SolveSession is the one-shot surface now (lint rule R3 enforces
    the same repo-wide)."""
    import repro.core as core

    assert not hasattr(solver, "solve_wilson_eo")
    assert not hasattr(core, "solve_wilson_eo")


# --- host-resident problems (repro.launch.problem) --------------------

_HOST_SOURCES_BODY = """
import jax, numpy as np
from jax.sharding import Mesh
from repro import api
from repro.launch import problem

devs = jax.devices()
host, chip = devs[4], devs[0]
problem.host_device = lambda: host          # 'host' = device 4
prob = problem.HostProblem((4, 4, 4, 8), 0.13, seed=3)
eta, ee, eo = prob.source(0)
assert ee.sharding.device_set == {host}
spec = api.SolveSpec(method="cgnr", tol=1e-5)

# Planar operator: only the native vector crosses; Dhat comes back home.
m = prob.bind(api.BackendSpec("pallas_fused", interpret=True),
              fallback=True)
assert m.devices() == {chip}, m.devices()
assert m.foreign_home(ee) == host
assert m.encode(ee).sharding.device_set == {chip}
ce, co = jax.device_put((ee, eo), chip)
assert m.foreign_home(ce) is None
d = m(ee)
assert d.sharding.device_set == {host}
np.testing.assert_array_equal(np.asarray(d), np.asarray(m(ce)))
# A fallback rebinds from the host-resident exact gauge, and serves
# from the device the failed matrix was on.
nxt = m.fallback_next("injected")
assert nxt.devices() == {chip} and nxt.degraded, nxt.devices()

# Session: solved on the chip, decoded on the host; the same solve with
# the sources already on the chip gives the same solution.
j = prob.bind(api.BackendSpec("jnp"))
assert j.devices() == {chip}
session = api.SolveSession(j, spec)
xe, xo, res = session.solve(ee, eo)
assert xe.sharding.device_set == {host}, xe.sharding
assert prob.true_residual(eta, xe, xo) <= 1e-5
ye, yo, _ = session.solve(ce, co)
assert ye.sharding.device_set == {chip}
np.testing.assert_array_equal(np.asarray(xe), np.asarray(ye))
assert session.stats()["traces"] == 1

# A four-device mesh that leaves the host out.
mesh = Mesh(np.array(devs[:4]).reshape(4, 1), ("data", "model"))
dist = prob.bind(api.BackendSpec("distributed"), mesh=mesh)
assert dist.devices() == set(devs[:4])
dd = dist(ee)
assert dd.sharding.device_set == {host}
np.testing.assert_allclose(np.asarray(dd), np.asarray(d), atol=1e-5)
print("OK")
"""


def test_host_problem_sources_cross_as_native_vectors():
    """Sources committed to the host are encoded and decoded there; only
    native vectors reach the operator's device (one device, a fallback
    rebind, and a four-device mesh that leaves the host out)."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=5 "
                        + env.get("XLA_FLAGS", ""))
    out = subprocess.run([sys.executable, "-c", _HOST_SOURCES_BODY],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("OK")
