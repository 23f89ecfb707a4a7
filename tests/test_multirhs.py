"""Multi-RHS batched kernels/solves and mixed-precision refinement.

Covers the acceptance criteria of the multi-RHS PR: batched kernels load
each gauge block once per grid step regardless of nrhs (structural
jaxpr + traffic-model assertions), batched solves agree column-by-column
with independent single-RHS solves on every builtin backend, per-column
convergence masks freeze correctly, BiCGStab breakdown is detected
instead of NaN-poisoning the batch, and mixed-precision refinement
reaches the f64 tolerance the pure-f64 solve reaches with fewer f64
operator applications.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, backends
from repro.core import evenodd, solver, su3
from repro.kernels import layout
from repro.kernels.wilson_stencil import (fused_dhat_fits,
                                          hop_traffic_model)

BUILTIN_BACKENDS = ("jnp", "pallas", "pallas_fused", "distributed")
NRHS = 2


def _bind(name, Ue, Uo, **extra):
    opts = ({"interpret": True} if name.startswith("pallas")
            and jax.default_backend() != "tpu" else {})
    opts.update(extra)
    return backends.make_wilson_ops(name, Ue, Uo, **opts)


def make_batched_eo(shape, nrhs, seed=0):
    U = su3.random_gauge(jax.random.PRNGKey(seed), shape)
    k = jax.random.PRNGKey(seed + 1)
    psi = (jax.random.normal(k, (nrhs, *shape, 4, 3))
           + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                    (nrhs, *shape, 4, 3))
           ).astype(jnp.complex64)
    e, o = jax.vmap(evenodd.pack)(psi)
    Ue, Uo = evenodd.pack_gauge(U)
    return Ue, Uo, e, o


def test_batched_layout_roundtrip():
    """Planar codecs pass leading batch dims through losslessly and match
    the unbatched conversion column by column."""
    k = jax.random.PRNGKey(3)
    psi = (jax.random.normal(k, (3, 2, 2, 4, 2, 4, 3))
           + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                    (3, 2, 2, 4, 2, 4, 3))
           ).astype(jnp.complex64)
    p = layout.spinor_to_planar(psi)
    assert p.shape == (3, 2, 2, 24, 4, 2)
    np.testing.assert_array_equal(
        np.asarray(layout.spinor_from_planar(p)), np.asarray(psi))
    for n in range(3):
        np.testing.assert_array_equal(
            np.asarray(p[n]), np.asarray(layout.spinor_to_planar(psi[n])))


@pytest.mark.parametrize("name", BUILTIN_BACKENDS)
def test_batched_native_ops_match_unbatched(name, small_eo):
    """Every backend's batched native ops == the unbatched ops applied
    column by column (hop, Dhat, Dhat^dag)."""
    Ue, Uo, _, _, kappa = small_eo
    Ue_, Uo_, e, _ = make_batched_eo((4, 4, 4, 8), NRHS, seed=11)
    bops = _bind(name, Ue_, Uo_)
    v = bops.to_domain_batched(e)
    out = bops.from_domain_batched(bops.apply_dhat_native_batched(v, kappa))
    hop = bops.from_domain_batched(bops.hop_oe_native_batched(v))
    dag = bops.from_domain_batched(
        bops.apply_dhat_dagger_native_batched(v, kappa))
    for n in range(NRHS):
        np.testing.assert_allclose(
            np.asarray(out[n]), np.asarray(bops.apply_dhat(e[n], kappa)),
            atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(hop[n]), np.asarray(bops.hop_oe(e[n])), atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(dag[n]),
            np.asarray(bops.apply_dhat_dagger(e[n], kappa)), atol=2e-5)


@pytest.mark.parametrize("name", BUILTIN_BACKENDS)
def test_batched_solve_matches_sequential(name):
    """Acceptance: a batched solve agrees column-by-column with N
    independent single-RHS solves, on every builtin backend."""
    Ue, Uo, e, o = make_batched_eo((4, 4, 4, 8), NRHS, seed=21)
    kappa = 0.13
    bops = _bind(name, Ue, Uo)
    session = api.SolveSession(
        api.WilsonMatrix.from_ops(bops, kappa, gauge=(Ue, Uo)),
        api.SolveSpec(method="bicgstab", tol=1e-5))
    xe_b, xo_b, res = session.solve(e, o)
    assert res.converged.shape == (NRHS,)
    assert bool(res.converged.all()), res
    for n in range(NRHS):
        xe_1, xo_1, _ = session.solve(e[n], o[n])
        for got, want in ((xe_b[n], xe_1), (xo_b[n], xo_1)):
            d = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            assert d < 1e-4, (name, n, d)


def test_gauge_loaded_once_per_grid_step(small_eo):
    """Acceptance: the batched hop lowers to ONE pallas_call (not nrhs of
    them / no vmap-unrolled kernels), its grid is the (T, Z) plane grid,
    and the traffic model's gauge term is nrhs-independent."""
    Ue, Uo, _, _, _ = small_eo
    bops = _bind("pallas", Ue, Uo)
    _, _, e, _ = make_batched_eo((4, 4, 4, 8), 4, seed=31)
    v = bops.to_domain_batched(e)
    jaxpr = jax.make_jaxpr(lambda w: bops.hop_oe_native_batched(w))(v)
    txt = str(jaxpr)
    assert txt.count("pallas_call") == 1, txt.count("pallas_call")
    # One batched Dhat through the fused backend is also a single kernel.
    bops_f = _bind("pallas_fused", Ue, Uo)
    vf = bops_f.to_domain_batched(e)
    txt_f = str(jax.make_jaxpr(
        lambda w: bops_f.apply_dhat_native_batched(w, 0.13))(vf))
    assert txt_f.count("pallas_call") == 1
    # Gauge bytes of the model don't grow with nrhs; spinor bytes do.
    m1 = hop_traffic_model(4, 4, 4, 4, nrhs=1)
    m8 = hop_traffic_model(4, 4, 4, 4, nrhs=8)
    assert m1["bytes_gauge"] == m8["bytes_gauge"]
    assert m8["bytes_spinor"] == 8 * m1["bytes_spinor"]
    assert (m8["intensity_flops_per_byte"]
            > 2 * m1["intensity_flops_per_byte"])


def test_hop_rhs_blocks_match_whole_block(monkeypatch):
    """Where the whole RHS block would overflow VMEM, the hop kernel
    walks it in smaller column blocks on an inner grid axis; every
    column comes out bit-identical to the one-block kernel."""
    from repro.kernels import ops
    from repro.kernels import wilson_stencil as ws
    Ue, Uo, e, _ = make_batched_eo((4, 4, 4, 8), 4, seed=37)
    u_e_p, u_o_p = ops.make_planar_fields(Ue, Uo)
    v = layout.spinor_to_planar(e)
    Y, Xh = v.shape[-2:]

    def hop(w):
        return ws.hop_block_planar(u_o_p, u_e_p, w, 1, interpret=True,
                                   axpy=(0.5, w))

    assert ws.hop_rhs_block(4, Y, Xh, with_axpy=True) == 4
    whole = hop(v)
    # A limit that admits two columns per grid step but not four.
    monkeypatch.setattr(ws, "_VMEM_LIMIT_BYTES",
                        ws.hop_vmem_bytes(2, Y, Xh, with_axpy=True))
    assert ws.hop_rhs_block(4, Y, Xh, with_axpy=True) == 2
    np.testing.assert_array_equal(np.asarray(hop(v)), np.asarray(whole))


def test_batched_cg_convergence_mask_freezes():
    """Converged columns freeze: a zero RHS converges at iteration 0 and
    its iterate never moves; scaled columns converge to scaled solutions
    with identical iteration counts."""
    n = 32
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, n))
    A = A @ A.T + n * jnp.eye(n)
    b1 = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    b = jnp.stack([jnp.zeros(n), b1, 3.0 * b1])
    res = solver.cg_batched(lambda v: (A @ v.T).T, b, tol=1e-7,
                            max_iters=200)
    assert bool(res.converged.all()), res
    assert int(res.iterations[0]) == 0
    assert float(jnp.abs(res.x[0]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(res.x[2]), 3 * np.asarray(res.x[1]),
                               rtol=1e-4)
    # Mixed difficulty: an easy (well-scaled) column must not keep
    # iterating while a harder one finishes — its recorded iteration
    # count is where it froze, <= the batch maximum.
    assert int(res.iterations[1]) <= int(res.iterations.max())


def test_bicgstab_breakdown_guard_unbatched():
    """Skew-symmetric system: <r0, v> = 0 at the first iteration — the
    classic BiCGStab breakdown.  The guard freezes the state and reports
    converged=False instead of NaN."""
    A = jnp.array([[0.0, 1.0], [-1.0, 0.0]])
    b = jnp.array([1.0, 0.0])
    res = solver.bicgstab(lambda v: A @ v, b, tol=1e-8, max_iters=50)
    assert not bool(res.converged)
    assert np.isfinite(np.asarray(res.x)).all()
    assert np.isfinite(float(res.residual))


def test_bicgstab_breakdown_guard_batched():
    """A broken-down column freezes (finite, converged=False) without
    poisoning its batch mates, and records the iteration it froze at."""
    A = jnp.array([[0.0, 1.0], [-1.0, 0.0]])
    b = jnp.stack([jnp.zeros(2), jnp.array([1.0, 0.0])])
    res = solver.bicgstab_batched(lambda v: (A @ v.T).T, b, tol=1e-8,
                                  max_iters=50)
    assert bool(res.converged[0])       # zero RHS: converged at start
    assert not bool(res.converged[1])   # breakdown column: frozen, honest
    assert np.isfinite(np.asarray(res.x)).all()
    assert int(res.iterations[0]) == 0
    assert int(res.iterations[1]) == 1  # broke down AT iteration 1, not 0


def test_bicgstab_batched_recompute_every():
    """recompute_every is honored inside the batched while_loop too."""
    n = 24
    key = jax.random.PRNGKey(7)
    A = jax.random.normal(key, (n, n))
    A = A @ A.T + n * jnp.eye(n)
    b = jax.random.normal(jax.random.fold_in(key, 1), (2, n))
    op = lambda v: (A @ v.T).T  # noqa: E731
    plain = solver.bicgstab_batched(op, b, tol=1e-6, max_iters=200)
    recomp = solver.bicgstab_batched(op, b, tol=1e-6, max_iters=200,
                                     recompute_every=3)
    assert bool(recomp.converged.all()), recomp
    np.testing.assert_allclose(np.asarray(recomp.x), np.asarray(plain.x),
                               atol=1e-4)


def test_bicgstab_healthy_solves_still_converge(small_eo):
    """The breakdown guards must not trip on a healthy Wilson solve."""
    Ue, Uo, e, o, kappa = small_eo
    xe, xo, res = api.solve(
        Ue, Uo, e, o, kappa,
        spec=api.SolveSpec(method="bicgstab", tol=1e-5))
    assert bool(res.converged), res


def test_mixed_precision_reaches_f64_tol():
    """Acceptance: inner_dtype=f32 refinement converges to the f64
    tolerance the pure-f64 solve reaches, with fewer f64 operator
    applications (counted: CGNR pays ~2/iteration in f64; refinement
    pays ~1 per outer pass)."""
    from repro.compat import enable_x64

    tol = 1e-10
    with enable_x64():
        Ue, Uo, e, o = make_batched_eo((4, 4, 4, 8), 1, seed=41)
        e, o = e[0].astype(jnp.complex128), o[0].astype(jnp.complex128)
        U64e = Ue.astype(jnp.complex128)
        U64o = Uo.astype(jnp.complex128)

        _, _, pure = api.solve(
            U64e, U64o, e, o, 0.13, backend="jnp",
            spec=api.SolveSpec(method="cgnr", tol=tol))
        assert bool(pure.converged)
        pure_applies = 2 * int(pure.iterations) + 2

        spec = api.SolveSpec(method="cgnr", tol=tol, max_iters=2000,
                             inner_dtype="f32")
        xe, xo, mix = api.solve(U64e, U64o, e, o, 0.13, backend="jnp",
                                spec=spec)
        assert bool(mix.converged), mix
        assert mix.f64_applies < pure_applies, (mix.f64_applies,
                                                pure_applies)
        # Independent f64 residual of the refined solution.
        rhs = e + 0.13 * evenodd.hop_eo(U64e, U64o, o)
        r = rhs - evenodd.apply_dhat(U64e, U64o,
                                     xe.astype(jnp.complex128), 0.13)
        rel = float(jnp.linalg.norm(r) / jnp.linalg.norm(rhs))
        assert rel <= tol, rel


def test_mixed_precision_requires_x64():
    Ue, Uo, e, o = make_batched_eo((4, 4, 4, 8), 1, seed=43)
    if jnp.zeros((), jnp.float64).dtype == jnp.dtype(jnp.float64):
        pytest.skip("x64 already enabled in this session")
    with pytest.raises(ValueError, match="x64"):
        api.solve(Ue, Uo, e[0], o[0], 0.13, backend="jnp",
                  spec=api.SolveSpec(inner_dtype="f32"))


def test_fused_dhat_fits_dtype_derived():
    """The scratch-budget check sizes elements by the ACTUAL dtype (and
    accepts batched shapes): a shape that fits in f32 can exceed the
    budget in f64, and a batched block multiplies the scratch by nrhs."""
    shape = (8, 8, 24, 8, 4)     # 6 MiB f32, 12 MiB f64 in (8, 128) tiles
    assert fused_dhat_fits(shape)                      # default f32
    assert fused_dhat_fits(shape, jnp.float32)
    assert not fused_dhat_fits(shape, jnp.float64)
    assert fused_dhat_fits(shape, jnp.bfloat16)
    assert fused_dhat_fits(shape, 4)                   # itemsize backcompat
    assert not fused_dhat_fits((4, *shape))            # nrhs=4 batched
    assert fused_dhat_fits((2,) + (4, 4, 24, 8, 4))


def test_bvdot_bf16_accumulates_in_f32(monkeypatch):
    """The compensated reduction's actual mechanism: bf16 PRODUCTS round
    to 8 mantissa bits before the sum, so a cancellation-heavy dot loses
    significance naively; upcasting the operands first makes every
    product exact in f32 (8x8 mantissa bits < 24).  Deterministic data,
    both the unbatched and per-column reductions."""
    rs = np.random.RandomState(0)
    x64 = rs.standard_normal(8192)
    y64 = np.random.RandomState(1).standard_normal(8192)
    x = jnp.asarray(x64, jnp.bfloat16)
    y = jnp.asarray(y64, jnp.bfloat16)
    # Truth = exact dot of the bf16-rounded inputs (what compensation
    # can and should recover; input rounding is not its job).
    truth = float(np.vdot(np.asarray(x, np.float64),
                          np.asarray(y, np.float64)))

    xb, yb = x.reshape(2, -1), y.reshape(2, -1)
    tb = np.vdot(np.asarray(xb[0], np.float64), np.asarray(yb[0], np.float64))

    monkeypatch.setattr(solver, "COMPENSATED_REDUCTIONS", False)
    naive_b = abs(float(solver._bvdot(xb, yb)[0]) - tb)
    monkeypatch.setattr(solver, "COMPENSATED_REDUCTIONS", True)
    comp = float(solver._vdot(x, y))
    comp_b = abs(float(solver._bvdot(xb, yb)[0]) - tb)

    assert abs(comp - truth) < 1e-3, (comp, truth)
    assert comp_b < 1e-3, comp_b
    assert naive_b > 0.01, naive_b          # ~0.06 observed: products
    assert naive_b > 10 * max(comp_b, 1e-9)  # rounded before the sum
    # And the scalars come back f32, not bf16.
    assert solver._vdot(x, y).dtype == jnp.float32
    assert solver._bvdot(xb, yb).dtype == jnp.float32


def test_compensated_scalars_do_not_promote_bf16_iterates():
    """f32-accumulated scalars must be cast DOWN at the axpy: the vector
    (and hence the solver's memory traffic) stays bf16."""
    x = jnp.ones((16,), jnp.bfloat16)
    y = jnp.ones((16,), jnp.bfloat16)
    alpha = jnp.float32(0.5)
    out = solver._axpy(alpha, x, y)
    assert out.dtype == jnp.bfloat16
    outb = solver._baxpy(jnp.ones((2,), jnp.float32) * 0.5,
                         x.reshape(2, 8), y.reshape(2, 8))
    assert outb.dtype == jnp.bfloat16
    # Complex/f32 domains are untouched (no spurious casts).
    xc = jnp.ones((4,), jnp.complex64)
    assert solver._axpy(jnp.float32(2.0), xc, xc).dtype == jnp.complex64
    assert solver._vdot(xc, xc).dtype == jnp.complex64


def _make_bf16_planar_ops(Ue, Uo, dtype=jnp.bfloat16):
    """Planar-native bf16 Wilson operators via the pure-XLA stencil
    (periodic wrap by halo padding) — the compile-cheap stand-in for the
    Pallas bf16 backend, wired through the public extension API."""
    from repro.kernels.wilson_stencil import hop_block_ext_planar_native

    u_e_p = layout.gauge_to_planar(Ue, dtype)
    u_o_p = layout.gauge_to_planar(Uo, dtype)

    def wrap_s(v):
        pad = [(0, 0)] * (v.ndim - 5) + [(1, 1), (1, 1), (0, 0), (0, 0),
                                         (0, 0)]
        return jnp.pad(v, pad, mode="wrap")

    def wrap_g(u):
        return jnp.pad(u, ((0, 0), (1, 1), (1, 1), (0, 0), (0, 0), (0, 0)),
                       mode="wrap")

    ue_ext, uo_ext = wrap_g(u_e_p), wrap_g(u_o_p)

    def hop_oe(v):
        return hop_block_ext_planar_native(u_o_p, ue_ext, wrap_s(v), 1)

    def hop_eo(v):
        return hop_block_ext_planar_native(u_e_p, uo_ext, wrap_s(v), 0)

    def dhat(v, kappa):
        return v - jnp.asarray(float(kappa) ** 2, dtype) * hop_eo(hop_oe(v))

    def dag(v, kappa):
        return layout.gamma5_planar(dhat(layout.gamma5_planar(v), kappa))

    to_d = lambda psi: layout.spinor_to_planar(psi, dtype=dtype)  # noqa: E731
    from_d = layout.spinor_from_planar
    return backends.WilsonOps.from_native(
        "planar_bf16_test", domain="planar",
        to_domain=to_d, from_domain=from_d,
        hop_oe=hop_oe, hop_eo=hop_eo,
        apply_dhat=dhat, apply_dhat_dagger=dag,
        to_domain_batched=to_d, from_domain_batched=from_d,
        hop_oe_batched=hop_oe, hop_eo_batched=hop_eo,
        apply_dhat_batched=dhat, apply_dhat_dagger_batched=dag)


def test_bf16_inner_converges_where_naive_stalls(monkeypatch):
    """Acceptance for the compensated reductions: at kappa = 0.24 (a hard,
    near-critical system) and inner_tol = 1e-3,

    * NAIVE bf16 accumulation stalls: the batched BiCGStab inner solve
      reports convergence but its bf16-product Krylov scalars (rho,
      <r0,v> — cancellation-heavy dots) are noise, and the iterate's TRUE
      residual is >= O(1): no actual progress, which would poison every
      refinement pass built on it;
    * with COMPENSATED (f32-accumulate) scalars the same inner solve
      genuinely contracts the error, and the full
      ``SolveSpec(inner_dtype="bf16", inner_tol=1e-3)`` refinement
      converges to the f64 tolerance.
    """
    from repro.compat import enable_x64

    kappa, nrhs, inner_tol = 0.24, 2, 1e-3
    Ue, Uo, e, o = make_batched_eo((4, 4, 4, 8), nrhs, seed=0)
    bops = _make_bf16_planar_ops(Ue, Uo)
    ops32 = _make_bf16_planar_ops(Ue, Uo, dtype=jnp.float32)
    v = bops.to_domain_batched(e)
    v32 = ops32.to_domain_batched(e)
    b2 = jnp.sum(v32 * v32, axis=(1, 2, 3, 4, 5))

    def true_rel(x):
        r = v32 - ops32.apply_dhat_native_batched(
            x.astype(jnp.float32), kappa)
        return np.sqrt(np.asarray(
            jnp.sum(r * r, axis=(1, 2, 3, 4, 5)) / b2))

    op = lambda w: bops.apply_dhat_native_batched(w, kappa)  # noqa: E731

    monkeypatch.setattr(solver, "COMPENSATED_REDUCTIONS", False)
    naive = solver.bicgstab_batched(op, v, tol=inner_tol, max_iters=100)
    naive_rel = true_rel(naive.x)

    monkeypatch.setattr(solver, "COMPENSATED_REDUCTIONS", True)
    comp = solver.bicgstab_batched(op, v, tol=inner_tol, max_iters=100)
    comp_rel = true_rel(comp.x)

    # Naive: at least one column made no real progress at all (true
    # residual >= ~1 while the bf16-scalar recursion *reported* 1e-3);
    # compensated: every column genuinely contracted.
    assert naive_rel.max() > 0.7, naive_rel
    assert comp_rel.max() < 0.5, comp_rel
    assert comp_rel.max() < naive_rel.max() / 2, (naive_rel, comp_rel)

    # End to end: --inner-dtype bf16 refinement through the same
    # operators reaches the f64 tolerance with compensated scalars.
    with enable_x64():
        e64, o64 = e.astype(jnp.complex128), o.astype(jnp.complex128)
        matrix = api.WilsonMatrix.from_ops(
            bops, kappa, gauge=(Ue.astype(jnp.complex128),
                                Uo.astype(jnp.complex128)))
        spec = api.SolveSpec(method="bicgstab", tol=1e-3,
                             inner_dtype="bf16", inner_tol=inner_tol,
                             max_outer=10)
        xe, _, res = api.SolveSession(matrix, spec).solve(e64, o64)
        assert bool(jnp.all(res.converged)), res
        assert res.outer_iterations <= 10
