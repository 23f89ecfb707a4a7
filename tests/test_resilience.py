"""Chaos suite: every injector in ``repro.resilience.inject`` fired at
the resilient solve runtime, asserting detection (the ``diverged``
flag), containment (healthy RHS columns bit-exact with the clean run),
and recovery (stagnation restarts, precision escalation to f64
tolerance, gauge repair, backend fallback, snapshot/resume)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import evenodd, solver, su3
from repro.resilience import (GaugeAuditReport, InjectedFault,
                              audit_gauge, bitflip_gauge, break_ops,
                              corrupt_halo_slab, dead_inner_ops,
                              fallback_chain, nan_operator,
                              nan_spinor_column, repair_gauge,
                              stagnating_system)
from repro.resilience.snapshot import RefinementSnapshot

KAPPA = 0.12
SHAPE = (4, 4, 4, 8)


def _x64():
    from repro.compat import enable_x64
    return enable_x64()


def _spd(n=32, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    G = jax.random.normal(key, (n, n), dtype=dtype)
    A = G @ G.T + n * jnp.eye(n, dtype=dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n,), dtype=dtype)
    return A, b


def _fields(dtype=jnp.complex64, seed=0):
    U = su3.random_gauge(jax.random.PRNGKey(seed), SHAPE, dtype=dtype)
    k = jax.random.PRNGKey(seed + 1)
    psi = (jax.random.normal(k, (*SHAPE, 4, 3))
           + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                    (*SHAPE, 4, 3))).astype(dtype)
    Ue, Uo = evenodd.pack_gauge(U)
    e, o = evenodd.pack(psi)
    return Ue, Uo, e, o


# --- divergence guards: detection at entry and mid-iteration ---------


@pytest.mark.parametrize("method", ["cg", "bicgstab"])
def test_nan_rhs_exits_immediately(method):
    A, b = _spd()
    b = b.at[0].set(jnp.nan)
    fn = solver.cg if method == "cg" else solver.bicgstab
    res = fn(lambda v: A @ v, b, tol=1e-6, max_iters=200)
    assert bool(res.diverged)
    assert not bool(res.converged)
    assert int(res.iterations) == 0


@pytest.mark.parametrize("method", ["cg", "bicgstab"])
def test_nan_operator_trips_guard_mid_iteration(method):
    # The operator starts emitting a NaN lane: divergence appears after
    # a healthy first residual, and the guard freezes a finite iterate
    # instead of running max_iters of NaN arithmetic.
    A, b = _spd()
    bad = nan_operator(lambda v: A @ v)
    fn = solver.cg if method == "cg" else solver.bicgstab
    res = fn(bad, b, tol=1e-10, max_iters=200)
    assert bool(res.diverged)
    assert not bool(res.converged)
    # Mid-iteration, not the entry exit: at least one healthy step ran.
    assert 1 <= int(res.iterations) < 200
    assert bool(jnp.all(jnp.isfinite(res.x)))


def test_guard_off_runs_blind():
    # The control: guard=False keeps the bare recurrence.  The loop
    # still *ends* on a NaN (NaN comparisons are False in the cond, and
    # a NaN pap trips the breakdown exit), but only the EXIT-TIME fold
    # on the non-finite relative residual refuses to call it converged
    # — the in-loop guard verdict, freeze, and stagnation machinery
    # are all gone (the budget-burning control is the `blind` leg of
    # the stagnation test below).
    A, b = _spd()
    bad = nan_operator(lambda v: A @ v)
    res = solver.cg(bad, b, tol=1e-10, max_iters=50, guard=False)
    assert int(res.iterations) >= 1       # the poisoned step did run
    assert bool(res.diverged)             # exit-time fold, not the guard
    assert not bool(res.converged)


@pytest.mark.parametrize("method", ["cg", "bicgstab"])
def test_batched_nan_column_contained_bit_exact(method):
    # One poisoned column; the other columns of the batched solve must
    # be BIT-EXACT with the uninjected run (per-column Krylov scalars
    # never mix columns) and the poisoned one must report diverged.
    A, b = _spd()
    B = jnp.stack([b, 2.0 * b, -b])
    fn = solver.cg_batched if method == "cg" else solver.bicgstab_batched
    op = (lambda v: v @ A.T)

    clean = fn(op, B, tol=1e-6, max_iters=200)
    bad_B = B.at[1, 0].set(jnp.nan)
    res = fn(op, bad_B, tol=1e-6, max_iters=200)

    assert bool(res.diverged[1]) and not bool(res.converged[1])
    for col in (0, 2):
        assert bool(res.converged[col])
        assert np.array_equal(np.asarray(res.x[col]),
                              np.asarray(clean.x[col])), \
            f"healthy column {col} was perturbed by the injected NaN"


def test_stagnation_guard_ends_hopeless_solve_early():
    # f32 CG on a cond=1e8 system cannot reach 1e-12; the stagnation
    # guard (restart, then freeze) must end it long before max_iters.
    A, b = stagnating_system()
    op = (lambda v: A @ v)
    res = solver.cg(op, b, tol=1e-12, max_iters=2000,
                    stagnation_window=20)
    blind = solver.cg(op, b, tol=1e-12, max_iters=2000, guard=False)
    assert bool(res.diverged)
    assert int(res.iterations) < 300
    assert int(blind.iterations) == 2000


def test_stagnation_restart_is_deterministic():
    A, b = stagnating_system()
    r1 = solver.cg(lambda v: A @ v, b, tol=1e-12, max_iters=2000,
                   stagnation_window=20)
    r2 = solver.cg(lambda v: A @ v, b, tol=1e-12, max_iters=2000,
                   stagnation_window=20)
    assert int(r1.iterations) == int(r2.iterations)
    assert np.array_equal(np.asarray(r1.x), np.asarray(r2.x))


# --- gauge audit / repair --------------------------------------------


def test_audit_flags_bitflip_and_repair_projects_back():
    Ue, Uo, _, _ = _fields()
    bad = bitflip_gauge(Ue, seed=3)
    report = audit_gauge(bad, Uo)
    assert not report.ok
    fixed_e, fixed_o, after = repair_gauge(bad, Uo)
    assert after.repaired and after.ok
    assert float(su3.unitarity_defect(fixed_e)) <= after.tolerance


def test_audit_counts_nonfinite_links():
    Ue, Uo, _, _ = _fields()
    bad = Ue.at[(0, 0, 0, 0, 0)].set(jnp.nan)
    report = audit_gauge(bad, Uo)
    assert report.nonfinite_links == 1 and not report.ok
    fixed_e, _, after = repair_gauge(bad, Uo)
    assert after.ok and bool(jnp.all(jnp.isfinite(fixed_e.real)))


def test_repair_is_identity_on_healthy_gauge():
    Ue, Uo, _, _ = _fields()
    fixed_e, fixed_o, report = repair_gauge(Ue, Uo)
    assert not report.repaired and report.ok
    assert np.array_equal(np.asarray(fixed_e), np.asarray(Ue))
    assert np.array_equal(np.asarray(fixed_o), np.asarray(Uo))


def test_bind_validate_warn_and_repair():
    Ue, Uo, e, o = _fields()
    bad = bitflip_gauge(Ue, seed=3)
    with pytest.warns(RuntimeWarning, match="SU\\(3\\) audit"):
        api.WilsonMatrix.bind(bad, Uo, KAPPA, backend="jnp",
                              validate="warn")
    D = api.WilsonMatrix.bind(bad, Uo, KAPPA, backend="jnp",
                              validate="repair")
    assert isinstance(D.gauge_audit, GaugeAuditReport)
    assert D.gauge_audit.repaired and D.gauge_audit.ok
    s = api.SolveSession(D, api.SolveSpec(method="bicgstab", tol=1e-5,
                                          max_iters=400))
    _, _, res = s.solve(e, o)
    assert bool(res.converged)
    with pytest.raises(ValueError, match="validate"):
        api.WilsonMatrix.bind(Ue, Uo, KAPPA, validate="maybe")


def test_repair_feeds_compressed_codecs():
    # The repair happens on the dense complex field BEFORE any codec
    # packs it, so a compressed bind of a corrupted gauge still solves.
    Ue, Uo, e, o = _fields()
    bad = bitflip_gauge(Ue, seed=3)
    spec = api.BackendSpec("pallas", interpret=True,
                           gauge_compression="two_row")
    D = api.WilsonMatrix.bind(bad, Uo, KAPPA, backend=spec,
                              validate="repair")
    assert D.gauge_audit.repaired
    s = api.SolveSession(D, api.SolveSpec(method="cgnr", tol=1e-5,
                                          max_iters=400))
    _, _, res = s.solve(e, o)
    assert bool(res.converged)


# --- halo corruption -------------------------------------------------


def test_corrupt_halo_slab_detected_and_recoverable():
    Ue, Uo, e, o = _fields()
    D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
    s = api.SolveSession(D, api.SolveSpec(method="cgnr", tol=1e-5,
                                          max_iters=400))
    torn = corrupt_halo_slab(e, axis=0, index=0)
    _, _, res = s.solve(torn, o)
    assert bool(res.diverged) and not bool(res.converged)
    # The session survives: a clean re-solve on the same compiled key.
    _, _, res2 = s.solve(e, o)
    assert bool(res2.converged)


# --- precision escalation --------------------------------------------


def test_escalation_rescues_dead_inner_backend():
    # The inner operator returns zero corrections (forced stagnation);
    # the outer loop must climb the ladder to f64 and still converge to
    # the f64 tolerance, recording the climb.
    with _x64():
        Ue, Uo, e, o = _fields(dtype=jnp.complex128)
        D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
        D._ops = dead_inner_ops(D.ops)
        s = api.SolveSession(D, api.SolveSpec(
            method="cgnr", tol=1e-10, max_iters=2000,
            inner_dtype="f32", inner_tol=1e-4, max_outer=25))
        _, _, res = s.solve(e, o)
        assert bool(res.converged)
        assert float(res.residual) <= 1e-10
        assert "f64" in res.escalations
        row = next(iter(s.stats()["keys"].values()))
        assert row["outer_iterations"] == [int(res.outer_iterations)]
        assert row["escalations"] == [list(res.escalations)]


def test_escalation_disabled_reports_divergence():
    with _x64():
        Ue, Uo, e, o = _fields(dtype=jnp.complex128)
        D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
        D._ops = dead_inner_ops(D.ops)
        s = api.SolveSession(D, api.SolveSpec(
            method="cgnr", tol=1e-10, max_iters=2000,
            inner_dtype="f32", inner_tol=1e-4, max_outer=5,
            escalate=False))
        _, _, res = s.solve(e, o)
        assert not bool(res.converged)
        assert res.escalations == ()


def test_healthy_refined_solve_never_escalates():
    with _x64():
        Ue, Uo, e, o = _fields(dtype=jnp.complex128)
        D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
        s = api.SolveSession(D, api.SolveSpec(
            method="cgnr", tol=1e-10, max_iters=2000,
            inner_dtype="f32", inner_tol=1e-4, max_outer=25))
        _, _, res = s.solve(e, o)
        assert bool(res.converged)
        assert res.escalations == ()


# --- backend fallback chain ------------------------------------------


def test_fallback_chain_declared_in_registry():
    assert fallback_chain("pallas_fused_stream") == (
        "pallas_fused_stream", "pallas_fused", "pallas", "jnp")
    assert fallback_chain("distributed") == ("distributed", "jnp")
    assert fallback_chain("jnp") == ("jnp",)


def test_session_falls_back_on_injected_compile_failure():
    Ue, Uo, e, o = _fields()
    spec = api.BackendSpec("pallas", interpret=True)
    D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend=spec, fallback=True)
    D._ops = break_ops(D.ops)
    s = api.SolveSession(D, api.SolveSpec(method="cgnr", tol=1e-5,
                                          max_iters=400))
    _, _, res = s.solve(e, o)
    assert bool(res.converged)
    st = s.stats()
    assert st["fallbacks"] >= 1
    assert st["backend"] == "jnp"
    assert st["degraded"]
    assert st["fallback_events"][0][0] == "pallas"
    assert "InjectedFault" in st["fallback_events"][0][1]
    assert s.matrix.degraded
    # Counters stay consistent after recovery: the failed attempt never
    # committed a solve/miss.
    s.solve(e, o)
    assert s.stats()["solves"] == 2
    assert s.stats()["cache_hits"] == 1


def test_fallback_disabled_raises():
    Ue, Uo, e, o = _fields()
    spec = api.BackendSpec("pallas", interpret=True)
    D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend=spec)
    D._ops = break_ops(D.ops)
    s = api.SolveSession(D, api.SolveSpec(method="cgnr", tol=1e-5,
                                          max_iters=400))
    with pytest.raises(InjectedFault):
        s.solve(e, o)
    assert s.stats()["fallbacks"] == 0
    assert s.stats()["solves"] == 0


def test_healthy_session_reports_not_degraded():
    Ue, Uo, e, o = _fields()
    D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp",
                              fallback=True)
    s = api.SolveSession(D, api.SolveSpec(method="cg", tol=1e-5,
                                          max_iters=400))
    s.solve(e, o)
    st = s.stats()
    assert not st["degraded"]
    assert st["fallbacks"] == 0 and st["fallback_events"] == []


# --- snapshot / resume -----------------------------------------------


def test_snapshot_resume_skips_completed_outer_passes(tmp_path):
    with _x64():
        Ue, Uo, e, o = _fields(dtype=jnp.complex128)
        D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
        kw = dict(method="cgnr", tol=1e-10, max_iters=2000,
                  inner_tol=1e-4, max_outer=25, batched=False)
        U64_e, U64_o = D.gauge_complex()

        fresh = solver.make_refined_solve(D.ops, U64_e, U64_o, KAPPA,
                                          **kw)
        _, _, ref = fresh(e, o)
        assert bool(ref.converged)

        snap_dir = str(tmp_path / "snap")
        snapped = solver.make_refined_solve(
            D.ops, U64_e, U64_o, KAPPA,
            snapshot=RefinementSnapshot(snap_dir), **kw)
        _, _, first = snapped(e, o)
        assert bool(first.converged)
        # Second run resumes from the last saved outer iterate: fewer
        # f64 reference applications, same converged answer.
        xe2, _, second = snapped(e, o)
        assert bool(second.converged)
        assert int(second.f64_applies) < int(first.f64_applies)
        assert float(second.residual) <= 1e-10


def test_snapshot_empty_directory_resumes_from_zero(tmp_path):
    snap = RefinementSnapshot(str(tmp_path / "empty"))
    x0 = jnp.zeros((4,))
    x, outer, extras = snap.resume(x0)
    assert outer == 0 and extras == {}
    assert np.array_equal(np.asarray(x), np.asarray(x0))
    assert snap.latest_outer() is None


# --- the injectors themselves are deterministic ----------------------


def test_injectors_are_pure_and_seeded():
    Ue, _, e, _ = _fields()
    b1, b2 = bitflip_gauge(Ue, seed=7), bitflip_gauge(Ue, seed=7)
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    assert not np.array_equal(np.asarray(b1), np.asarray(Ue))

    eb = jnp.stack([e, e])
    n1 = nan_spinor_column(eb, 1)
    assert bool(jnp.any(jnp.isnan(n1.real[1])))
    assert not bool(jnp.any(jnp.isnan(n1.real[0])))
    assert not bool(jnp.any(jnp.isnan(eb.real)))       # input untouched

    A, b = stagnating_system()
    A2, b2_ = stagnating_system()
    assert np.array_equal(np.asarray(A), np.asarray(A2))
    assert np.array_equal(np.asarray(b), np.asarray(b2_))


def test_break_ops_raises_at_trace():
    Ue, Uo, e, _ = _fields()
    D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
    broken = break_ops(D.ops, "kaboom")
    with pytest.raises(InjectedFault, match="kaboom"):
        broken.apply_dhat_native(broken.to_domain(e), KAPPA)


def test_corrupt_halo_slab_layouts():
    Ue, Uo, e, _ = _fields()
    torn = corrupt_halo_slab(e, axis=0, index=0)
    assert bool(jnp.all(jnp.isnan(torn.real[0])))
    assert bool(jnp.all(jnp.isfinite(torn.real[1:])))
    D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp")
    v = D.ops.to_domain(e)                 # planar-native layout
    torn_v = corrupt_halo_slab(v, axis=1, index=-1)
    assert bool(jnp.any(jnp.isnan(torn_v)))
    assert not bool(jnp.any(jnp.isnan(v)))


def test_solve_spec_resilience_knobs_validated():
    with pytest.raises(ValueError, match="stagnation_window"):
        api.SolveSpec(stagnation_window=1)
    with pytest.raises(ValueError, match="max_restarts"):
        api.SolveSpec(max_restarts=-1)
    tok = api.SolveSpec(guard=False).cache_token()
    assert "noguard" in tok
    tok2 = api.SolveSpec(inner_dtype="f32", escalate=False).cache_token()
    assert "noesc" in tok2


def test_warnings_clean_on_healthy_bind():
    Ue, Uo, _, _ = _fields()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        D = api.WilsonMatrix.bind(Ue, Uo, KAPPA, backend="jnp",
                                  validate="warn")
    assert D.gauge_audit is not None and D.gauge_audit.ok
